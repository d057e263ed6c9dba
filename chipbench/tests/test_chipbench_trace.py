"""The trace reductions: device busy time, idle gaps and the host span
each falls in, on synthetic traces."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench import trace as T


def trace(ops, spans):
    return T.Trace(devices={0: [T.Op(n, s, d) for n, s, d in ops]},
                   spans=spans)


# a 100 ns window: ops cover 10-30 (two overlapping) and 50-60; the
# host is in a fixpoint for 0-70 and in bookkeeping for 70-100
TR = trace([("sort.1", 10, 15), ("fusion.2", 20, 10), ("sort.3", 50, 10),
            ("copy.4", 120, 5)],
           [("window", 0, 100), ("fixpoint", 0, 70),
            ("check-bookkeeping", 70, 100)])


def test_union_merges_and_clips():
    assert T.union([(5, 10), (0, 3), (2, 4), (9, 20)], 1, 15) == [
        (1, 4), (5, 15)]


def test_busy_and_idle():
    assert T.busy_ns(TR, 0) == 30
    assert T.idle_gaps(TR, 0) == [(0, 10), (30, 50), (60, 100)]
    assert T.idle_share(TR, 1) == pytest.approx(70.0)


def test_gaps_are_labelled_by_the_innermost_span():
    assert T.host_span_at(TR, 80) == "check-bookkeeping"
    assert T.host_span_at(TR, 40) == "fixpoint"
    assert T.host_span_at(TR, 500) == "none"
    b = T.breakdown(TR, 0)
    assert b["idle_gaps"] == [["check-bookkeeping", 40e-9],
                              ["fixpoint", 20e-9], ["fixpoint", 10e-9]]
    assert b["device_ops"] == [["sort", 25e-9], ["fusion", 10e-9]]


def test_sort_seconds_per_fixpoint():
    window = SimpleNamespace(samples=[(0, 1, 1), (1, 2, 1), (2, 3, 1)],
                             traced_steps=2)
    run = SimpleNamespace(trace=TR, window=window, chips=1)
    got = bench.load_module(bench.HERE / "metrics" / "xla_sort_s.batch.py")
    assert got.read(run) == pytest.approx(25e-9 / 2)


@pytest.mark.parametrize("dispatches,cut", [([5, 45], None), ([5, 45, 65], None),
                                             ([5, 45, 80], 60)])
def test_a_record_cut_short_ends_the_traced_stretch(monkeypatch, dispatches,
                                                    cut):
    """A program dispatched after the device's last op shows that the
    profiler's buffer was full: the stretch ends with the record, and
    readings per whole step are refused."""
    monkeypatch.setattr(T, "CUT_AFTER_NS", 10)
    tr = T.Trace(devices={0: TR.devices[0][:3]}, spans=TR.spans,
                 dispatches=dispatches)
    assert tr.cut_at() == cut
    assert tr.window() == (0, 100 if cut is None else cut)
    assert T.idle_share(tr, 1) == pytest.approx(
        70.0 if cut is None else 50.0)
    window = SimpleNamespace(samples=[(0, 1, 1)], traced_steps=1)
    sorts = bench.load_module(bench.HERE / "metrics" / "xla_sort_s.batch.py")
    run = SimpleNamespace(trace=tr, window=window, chips=1)
    if cut is None:
        assert sorts.read(run) == pytest.approx(25e-9)
    else:
        with pytest.raises(T.NothingToRead, match="buffer"):
            sorts.read(run)


DEVICE_READERS = ("device_idle_share.batch", "device_idle_share.insert",
                  "xla_sort_s.batch", "merge_probe_roofline",
                  "segment_reduce_roofline")


def test_readers_return_nothing_without_a_device():
    """A reader with nothing to read says why, and gives no number."""
    empty = T.Trace(devices={}, spans=[("window", 0, 10)])
    for tr, why in ((empty, "no 'XLA Ops' line"), (None, "no trace")):
        run = SimpleNamespace(trace=tr, window=None, chips=1,
                              device_kind="TPU v5 lite")
        for name in DEVICE_READERS:
            with pytest.raises(T.NothingToRead, match=why):
                bench.load_module(
                    bench.HERE / "metrics" / f"{name}.py").read(run)


def test_window_must_be_one_span():
    with pytest.raises(ValueError):
        trace([], [("window", 0, 1), ("window", 2, 3)]).window()


def test_traced_run_says_what_it_left_out(tiny, capsys):
    """A traced run whose trace holds no device plane (the CPU's) gives
    no device metric, names each one it left out and why, and still
    decides ``correct``."""
    import time
    from chipbench import run as R
    cell = tiny("cc-s14.batch")
    out = R.run_cell(cell, 2**31 + 7, 0.5, True,
                     require_chip=False, start=time.perf_counter())
    err = capsys.readouterr().err
    assert out["metrics"] == {} and out["correct"] is True
    names = [m["name"] for m in cell.per_layer]
    assert {"device_idle_share.batch", "segment_reduce_roofline"} <= set(names)
    for name in names:
        assert f"{name} left out: the trace has no 'XLA Ops' line" in err
