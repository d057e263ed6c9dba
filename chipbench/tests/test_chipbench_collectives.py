"""The readers of the four-chip cell's metrics (``all_to_all_s.x4``,
``all_to_all_bytes.x4``, ``shard_work_skew.x4``) on a synthetic trace
of four devices, one of which does twice its share of the work."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench import trace as T

# as a v5e compile of the sharded Reach step writes it
A2A = ("%all_to_all.2 = s32[4,8,2]{1,2,0:T(2,128)S(1)} all-to-all("
       "%bitcast.598), channel_id=1, replica_groups={{0,1,2,3}}, "
       "dimensions={0}, metadata={op_name=\"jit(step_fn)/shard_map/"
       "repartition/all_to_all\"}")
A2A_HEAD = ("%all_to_all.3 = s32[4,16,1]{1,2,0:T(1,128)S(1)} all-to-all("
            "%bitcast.638), channel_id=1, dimensions={0}")
PSUM = "%psum.5 = s32[] all-reduce(s32[] %n), to_apply=%add"


def op(name, start, dur, text=None):
    return T.Op(name, start, dur, {"long_name": text} if text else {})


def device(work):
    """A device's ops: ``work`` ns of compute (a ``while`` op around
    a fusion, which must count once), then the collectives, whose
    waits are not work."""
    return [op("while.7", 0, work), op("fusion.1", 10, work - 20),
            op("all_to_all.2", 500, 50, A2A),
            op("all_to_all.3", 600, 30, A2A_HEAD),
            op("psum.5", 700, 200, PSUM)]


def run_of(devices, steps=2):
    tr = T.Trace(devices=devices, spans=[("window", 0, 1000)])
    window = SimpleNamespace(samples=[(0, 1, 1)] * 3, traced_steps=steps)
    return SimpleNamespace(trace=tr, window=window, chips=4)


def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


RUN = run_of({0: device(200), 1: device(200), 2: device(200),
              3: device(400)})


def test_all_to_all_seconds_per_fixpoint():
    # first chip: both all-to-alls, not the all-reduce
    assert reader("all_to_all_s.x4").read(RUN) == pytest.approx(
        (50 + 30) * 1e-9 / 2)


def test_all_to_all_bytes_per_fixpoint():
    # s32[4,8,2] and s32[4,16,1], 256 B each
    assert reader("all_to_all_bytes.x4").read(RUN) == pytest.approx(
        (256 + 256) / 2)


def test_work_skew_is_the_largest_over_the_mean():
    # compute 200, 200, 200, 400 ns: the mean is 250
    assert reader("shard_work_skew.x4").read(RUN) == pytest.approx(1.6)
    even = run_of({d: device(300) for d in range(4)})
    assert reader("shard_work_skew.x4").read(even) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["all_to_all_s.x4", "all_to_all_bytes.x4",
                                  "shard_work_skew.x4"])
def test_nothing_to_read_without_an_all_to_all(name):
    alone = {d: [op("fusion.1", 0, 100), op("psum.5", 200, 10, PSUM)]
             for d in range(4)}
    with pytest.raises(T.NothingToRead):
        reader(name).read(run_of(alone))
    with pytest.raises(T.NothingToRead):
        reader(name).read(SimpleNamespace(trace=None, window=RUN.window,
                                          chips=4))


def test_bytes_need_the_hlo_text():
    # an op without text is known by its name, but has no shape
    bare = run_of({d: [op("all_to_all.2", 0, 50)] for d in range(4)})
    assert reader("all_to_all_s.x4").read(bare) == pytest.approx(25e-9)
    with pytest.raises(T.NothingToRead):
        reader("all_to_all_bytes.x4").read(bare)
