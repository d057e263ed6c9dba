"""The program's spans in traces recorded on the chip by a program that
opens them: one traced ``reach-s16.batch`` fixpoint (seed 2147490101)
and the four traced applies of a ``reach-s16.insert`` run (seed
2147490201), on a TPU v5 lite. Each ``.xplane.pb`` is cut to the
device's ``XLA Ops`` events (their metadata's name and ``tf_op``
alone) and, on the host, the harness's spans, the program's
``flowlog/`` spans and the dispatches. It pins how long the host spent
in each span the gaps of these cells fall in, and that the program's
spans explain the device's idle time inside the harness's steps."""
from __future__ import annotations

import gzip
import shutil
from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench import trace as T
from repro.observe import read_spans

DATA = bench.HERE / "tests" / "data"
RECORDS = {"batch": ("reach-s16.batch.spans.xplane.pb.gz", 1),
           "insert": ("reach-s16.insert.spans.xplane.pb.gz", 4)}


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    out = {}
    for cell, (name, steps) in RECORDS.items():
        logdir = tmp_path_factory.mktemp("xplane")
        path = logdir / "t.xplane.pb"
        with gzip.open(DATA / name, "rb") as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        out[cell] = SimpleNamespace(trace=T.read_xplane(path),
                                    spans=read_spans(logdir), steps=steps)
    return out


def _intersect(a, b) -> list[tuple[float, float]]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _named(record, name, lo, hi) -> list[tuple[float, float]]:
    return T.union(((s, e) for n, s, e in record.spans if n == name), lo, hi)


def _idle_in(record, intervals) -> float:
    return _length(_intersect(T.idle_gaps(record.trace, 0), intervals))


@pytest.mark.parametrize("cell,span,want", [
    ("batch", "edb-load", 2.065891066),
    ("insert", "refresh-edb", 7.5007061225),
    ("insert", "idb-diff", 0.26634331125),
    ("insert", "snapshot", 0.2772183985),
])
def test_host_seconds_per_step_in_a_span(records, cell, span, want):
    record = records[cell]
    lo, hi = record.trace.window_span()
    got = _length(_named(record, span, lo, hi)) / 1e9 / record.steps
    assert got == pytest.approx(want)


def test_device_idle_inside_the_iterations(records):
    """The host loop's per-iteration reads leave the device idle for
    0.025 s of a 7.65 s fixpoint: all a device-resident loop could
    win here."""
    record = records["batch"]
    assert record.trace.cut_at() is None
    lo, hi = record.trace.window()
    idle = _idle_in(record, _named(record, "iteration", lo, hi))
    assert idle / 1e9 == pytest.approx(0.024835722)


@pytest.mark.parametrize("cell,step", [("batch", "fixpoint"),
                                       ("insert", "apply")])
def test_program_spans_explain_the_idle_time(records, cell, step):
    record = records[cell]
    lo, hi = record.trace.window()
    steps = T.union(((s, e) for n, s, e in record.trace.spans if n == step),
                    lo, hi)
    covered = T.union(((s, e) for _, s, e in record.spans), lo, hi)
    idle = _idle_in(record, steps)
    assert idle > 2e9
    assert _idle_in(record, _intersect(steps, covered)) >= 0.9 * idle


def test_the_spans_of_a_fixpoint(records):
    record = records["batch"]
    names = [n for n, _, _ in record.spans]
    assert names[:3] == ["run", "edb-load", "stratum"]
    assert names.count("iteration") == 7 and names[-1] == "export"
    lo, hi = record.trace.window()
    load = _idle_in(record, _named(record, "edb-load", lo, hi))
    assert load >= 0.9 * _idle_in(record, [(lo, hi)])


def test_the_spans_of_an_apply_tile_it(records):
    spans = records["insert"].spans
    applies = [s for s in spans if s[0] == "apply"]
    assert len(applies) == 4
    for _, lo, hi in applies:
        inside = [s for s in spans if lo <= s[1] and s[2] <= hi
                  and s[0] != "apply"]
        top = [s for s in inside
               if not any(t[1] <= s[1] and s[2] <= t[2] and t is not s
                          for t in inside)]
        assert [s[0] for s in top] == [
            "mirror", "refresh-edb", "idb-diff", "maintain-stratum",
            "idb-diff", "snapshot"]
        assert sum(e - s for _, s, e in top) >= 0.99 * (hi - lo)


def test_the_probe_keeps_its_name_under_the_relop_scopes(records):
    """The roofline reader finds the Pallas probe by its kernel name,
    which the relops' named scopes leave alone."""
    probes = [o for o in records["batch"].trace.window_ops(0)
              if T.op_kind(o) == "merge_probe_pallas"]
    assert probes and T.custom_call_bytes(probes[0]) is not None
