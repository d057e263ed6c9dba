"""Window arithmetic and the end-to-end metric readers, on the CPU."""
from __future__ import annotations

import math
from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench import drive as D


def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def window(samples, start=None, end=None):
    w = D.Window(seconds=0)
    w.samples = samples
    w.start = samples[0][0] if start is None else start
    w.end = samples[-1][1] if end is None else end
    return w


def run(w, **kw):
    return SimpleNamespace(window=w, **kw)


def test_rate_is_over_the_whole_window():
    # four updates of 1000 rows, with host gaps between them: 4000 rows
    # over 10 s of window, not over the 4 s spent inside updates
    w = window([(0, 1, 1000), (2, 3, 1000), (5, 6, 1000), (9, 10, 1000)])
    assert reader("update_rows_per_s").read(run(w)) == pytest.approx(400.0)


def test_fixpoint_time_is_window_over_count():
    w = window([(0, 2, 1), (2, 4.5, 1), (4.5, 6, 1)])
    assert reader("fixpoint_s").read(run(w)) == pytest.approx(2.0)


@pytest.mark.parametrize("n", [1, 19, 20, 21, 25, 100])
def test_p95_is_nearest_rank_over_all_samples(n):
    lat = [0.01 * (i + 1) for i in range(n)]
    samples, t = [], 0.0
    for x in reversed(lat):
        samples.append((t, t + x, 1000))
        t += x
    got = reader("update_p95_s").read(run(window(samples)))
    assert got == pytest.approx(lat[math.ceil(0.95 * n) - 1])


def test_a_stall_counts_in_rate_and_tail():
    """One update stalls for 10 s among 39 of 1 s: the rate falls by the
    stall's whole length, and with 40 samples the stall is the p95."""
    samples, t = [], 0.0
    for i in range(40):
        d = 10.0 if i == 7 else 1.0
        samples.append((t, t + d, 1000))
        t += d
    w = window(samples)
    assert reader("update_rows_per_s").read(run(w)) == pytest.approx(40000 / 49)
    # nearest rank 38 of 40 is a 1 s update: one stall in 40 lies
    # above the 95th percentile, three reach it
    assert reader("update_p95_s").read(run(w)) == pytest.approx(1.0)
    for i in (8, 9):
        samples[i] = (samples[i][0], samples[i][0] + 10.0, 1000)
    assert reader("update_p95_s").read(run(window(samples))) == pytest.approx(10.0)


def test_setup_and_compiles_readers():
    w = window([(0, 1, 1000), (1, 2, 1000)])
    assert reader("setup_s").read(run(w, setup_s=12.5)) == 12.5
    assert reader("compiles_per_update").read(
        run(w, compiles_in_window=3)) == pytest.approx(1.5)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("seconds,step,expect", [
    (10.0, 3.0, 3),     # 3, 6, 9: mean 3 > 1 left -> stop at 3
    (10.0, 2.5, 4),     # ends exactly at 10
    (10.0, 12.0, 1),    # always at least one
    (45.0, 21.3, 2),    # two CC fixpoints fit 45 s
    (45.0, 22.6, 1),    # ... but not when each takes 22.6 s
])
def test_window_stops_when_the_mean_no_longer_fits(monkeypatch, seconds,
                                                   step, expect):
    clock = FakeClock()
    monkeypatch.setattr(D.time, "perf_counter", clock)

    def op():
        clock.t += step
        return 1

    w = D.Window(seconds)
    w.run(op)
    assert len(w.samples) == expect
    assert w.length == pytest.approx(step * expect)


class FakeCapture:
    def __init__(self, clock, events):
        self.clock, self.events = clock, events

    def start(self):
        self.events.append(("start", self.clock.t))

    def stop(self):
        self.events.append(("stop", self.clock.t))


@pytest.mark.parametrize("traced,stop_after", [(2, 2), (1, 1), (9, 5)])
def test_a_traced_run_records_the_first_operations(monkeypatch, traced,
                                                   stop_after):
    """The profiler starts with the window and stops after its first
    ``traced`` operations, or at its end; the window runs on."""
    clock, events = FakeClock(), []
    monkeypatch.setattr(D.time, "perf_counter", clock)

    def op():
        clock.t += 2.0
        events.append(("op", clock.t))
        return 1

    w = D.Window(10.0)
    w.run(op, FakeCapture(clock, events), traced)
    ops = [("op", 100.0 + 2.0 * (i + 1)) for i in range(5)]
    stop = ("stop", 100.0 + 2.0 * stop_after)
    assert events == [("start", 100.0), *ops[:stop_after], stop,
                      *ops[stop_after:]]
    assert len(w.samples) == 5 and w.traced_steps == stop_after


def test_an_untraced_run_records_nothing():
    w = D.Window(0.0)
    w.run(lambda: 1)
    assert w.traced_steps == 0 and len(w.samples) == 1
