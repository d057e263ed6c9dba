"""The trace readers on a trace recorded on the chip: one traced
fixpoint of ``reach-s16.batch`` on a TPU v5 lite (``data/``). It pins
how a chip trace names what the readers look for (the device plane, the
``XLA Ops`` line, an op's HLO text as the event's name, the Pallas
probe as ``merge_probe_pallas.N``, XLA's sorts as ``sort.N``) and each
reader's number on it."""
from __future__ import annotations

import gzip
import shutil
from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench import trace as T

DATA = bench.HERE / "tests" / "data" / "reach-s16.fixpoint.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return T.read_xplane(path)


def _run(trace):
    return SimpleNamespace(trace=trace, chips=1, device_kind="TPU v5 lite",
                           window=SimpleNamespace(samples=[(0.0, 1.0, 1)],
                                                  traced_steps=1))


def test_the_chip_trace_names_what_the_readers_need(chip_trace):
    assert list(chip_trace.devices) == [0]
    assert [s[0] for s in chip_trace.spans] == ["window", "fixpoint",
                                                "check-bookkeeping"]
    kinds = {T.op_kind(o) for o in chip_trace.window_ops(0)}
    assert {"merge_probe_pallas", "sort", "fusion"} <= kinds
    probe = next(o for o in chip_trace.window_ops(0)
                 if T.op_kind(o) == "merge_probe_pallas")
    assert probe.name.startswith("merge_probe_pallas.")
    assert T.custom_call_bytes(probe) is not None


def test_window_and_busy_time(chip_trace):
    assert chip_trace.cut_at() is None      # the whole fixpoint is there
    assert len(chip_trace.dispatches) == 196
    lo, hi = chip_trace.window()
    assert (hi - lo) / 1e9 == pytest.approx(7.648008641)
    assert T.busy_ns(chip_trace, 0) / 1e9 == pytest.approx(5.526237823)


@pytest.mark.parametrize("name,want", [
    ("device_idle_share.batch", 27.74278792816024),
    ("xla_sort_s.batch", 0.110383747),
    ("merge_probe_roofline", 0.010898680705233272),
])
def test_reader_on_the_chip_trace(chip_trace, name, want):
    got = bench.load_module(bench.HERE / "metrics" / f"{name}.py").read(
        _run(chip_trace))
    assert got == pytest.approx(want)


def test_a_kernel_that_did_not_run_is_named(chip_trace):
    reader = bench.load_module(bench.HERE / "metrics"
                               / "segment_reduce_roofline.py")
    with pytest.raises(T.NothingToRead, match="segment_reduce_pallas"):
        reader.read(_run(chip_trace))


def test_breakdown_of_the_chip_trace(chip_trace):
    b = T.breakdown(chip_trace, 0)
    assert [k for k, _ in b["device_ops"][:3]] == [
        "merge_probe_pallas", "fusion", "sort"]
    assert b["device_ops"][0][1] == pytest.approx(4.123340489)
    assert len(b["idle_gaps"]) == 10
    assert all(span == "fixpoint" for span, _ in b["idle_gaps"])
