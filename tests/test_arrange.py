"""Arrangement layer (relation.py docstring): sort-order witness,
per-pass ArrangementCache, and incremental merge maintenance
(relops.merge_sorted).

Equivalence contract, same discipline as the kernel-backend and
sharded suites: the engine with the arrangement layer ON must produce
byte-identical fixpoints and identical iteration counts to the engine
with it OFF (the pre-arrangement sort-per-op baseline), on the shared
corpus, under both kernel backends, at 1/2/4/8 shards, and through
incremental maintenance. The layer changes per-iteration cost — never
results.

Run standalone (or via ``make test-sharded`` / the CI ``sharded``
step) with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so
the multi-shard cases execute; inside the full suite they skip.
"""
from benchmarks.hostdevices import force_host_device_count

force_host_device_count()  # must precede the first jax device init

import numpy as np
import pytest

import jax

from benchmarks.programs import equivalence_datasets
from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig
from repro.engine import relops as R
from repro.engine.backend import JnpDispatch, PallasDispatch
from repro.engine.incremental import IncrementalEngine
from repro.engine.relation import (
    Relation, UNSORTED, counter_scope, empty, force_multiword,
    from_numpy, to_numpy,
)
from repro.engine.semiring import COUNTING, MIN_MONOID, PRESENCE

_datasets = equivalence_datasets
BACKENDS = (JnpDispatch(), PallasDispatch(interpret=True))


def _cfg(arrangements, **kw):
    d = dict(idb_cap=1 << 10, intermediate_cap=1 << 12,
             kernel_backend="jnp", arrangements=arrangements)
    d.update(kw)
    return EngineConfig(**d)


def _need(shards: int):
    if shards > len(jax.devices()):
        pytest.skip(f"needs {shards} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count)")


# -- sort-order witness ------------------------------------------------------

def test_witness_identity_default():
    r = from_numpy(np.array([[3, 1], [1, 2]]), 8)
    assert r.order is None
    assert r.identity_sorted
    assert r.arranged_by((0,)) and r.arranged_by((0, 1))
    assert not r.arranged_by((1,))


def test_arrange_fastpath_skips_sort():
    """key_cols already the identity prefix: arrange is the identity —
    same object, no sort launch."""
    r = from_numpy(np.array([[3, 1], [1, 2], [2, 9]]), 8)
    with counter_scope() as c:
        assert R.arrange(r, (0,)) is r
        assert R.arrange(r, (0, 1)) is r
        assert R.arrange(r, ()) is r
    assert c["sorts"] == 0
    assert c["cache_fastpath"] == 3


def test_arrange_records_witness_and_reuses_it():
    r = from_numpy(np.array([[0, 9], [1, 1], [2, 5]]), 8)
    a = R.arrange(r, (1,))
    assert a.order == (1, 0)
    col1 = to_numpy(a)[:, 1].tolist()
    assert col1 == sorted(col1)
    # compatible follow-up arranges ride the recorded witness
    with counter_scope() as c:
        assert R.arrange(a, (1,)) is a
        assert R.arrange(a, (1, 0)) is a
    assert c["sorts"] == 0


def test_unsorted_witness_disables_fastpaths():
    r = from_numpy(np.array([[3, 1], [1, 2]]), 8)
    u = Relation(r.data, r.val, r.n, order=UNSORTED)
    assert not u.identity_sorted
    assert not u.arranged_by((0,))
    assert not u.arranged_by(())
    a = R.arrange(u, (0,))
    assert a is not u and a.order == (0, 1)


def test_compaction_preserves_witness():
    """semijoin/antijoin stable-compact their left operand, so the
    left's witness survives."""
    left = from_numpy(np.array([[0, 9], [1, 1], [2, 5]]), 8)
    arranged = R.arrange(left, (1,))
    keys = from_numpy(np.array([[1], [9]]), 8)
    semi, _ = R.semijoin(arranged, keys, (1,), (0,))
    assert semi.order == (1, 0)


def test_arrangement_cache_shares_and_guards_identity():
    r = from_numpy(np.array([[0, 9], [1, 1], [2, 5]]), 8)
    cache = R.ArrangementCache()
    a1 = cache.arrange(r, (1,))
    a2 = cache.arrange(r, (1,))
    assert a1 is a2
    assert cache.hits == 1 and cache.misses == 1
    # a different relation never aliases a cached entry, even if ids
    # were recycled — the keyed array is held and compared with `is`
    other = from_numpy(np.array([[5, 0], [6, 2]]), 8)
    b = cache.arrange(other, (1,))
    assert b is not a1
    assert cache.misses == 2


def test_arrangement_cache_no_alias_on_shared_data():
    """Two Relations sharing a data array but differing in live count
    (the sharded zero-key guard builds exactly this) must not alias to
    one cached arrangement — the lookup verifies every stored leaf."""
    import jax.numpy as jnp
    r = from_numpy(np.array([[0, 9], [1, 1], [2, 5]]), 8)
    recount = Relation(r.data, r.val, jnp.asarray(2, jnp.int32))
    cache = R.ArrangementCache()
    a = cache.arrange(r, (1,))
    b = cache.arrange(recount, (1,))
    assert b is not a
    assert int(a.n) == 3 and int(b.n) == 2
    assert cache.misses == 2


# -- merge_sorted: incremental maintenance vs the sort path ------------------

def _concat_oracle(full, delta, sr, cap, backend=None):
    return R.concat_all([full, delta], sr, cap, backend=backend)


def _assert_same(got, want):
    rel_g, ov_g = got
    rel_w, ov_w = want
    np.testing.assert_array_equal(np.asarray(rel_g.data),
                                  np.asarray(rel_w.data))
    assert int(rel_g.n) == int(rel_w.n)
    assert bool(ov_g) == bool(ov_w)
    if rel_w.val is None:
        assert rel_g.val is None
    else:
        np.testing.assert_array_equal(np.asarray(rel_g.val),
                                      np.asarray(rel_w.val))


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
@pytest.mark.parametrize("seed", range(3))
def test_merge_sorted_matches_concat_path(backend, seed):
    rng = np.random.default_rng(seed)
    full = from_numpy(rng.integers(0, 12, size=(30, 2)), 64)
    delta = from_numpy(rng.integers(0, 12, size=(10, 2)), 16)
    got = R.merge_sorted(full, delta, PRESENCE, 128, backend=backend)
    _assert_same(got, _concat_oracle(full, delta, PRESENCE, 128,
                                     backend=backend))


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
def test_merge_sorted_duplicates_across_boundary(backend):
    """Rows present in BOTH operands must collapse to one copy — the
    adjacency of equal keys across the merge boundary is the core
    stable-merge property."""
    full = from_numpy(np.array([[1, 1], [2, 2], [3, 3]]), 16)
    delta = from_numpy(np.array([[0, 0], [2, 2], [3, 3], [4, 4]]), 8)
    got = R.merge_sorted(full, delta, PRESENCE, 32, backend=backend)
    assert to_numpy(got[0]).tolist() == [
        [0, 0], [1, 1], [2, 2], [3, 3], [4, 4]]
    _assert_same(got, _concat_oracle(full, delta, PRESENCE, 32,
                                     backend=backend))


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
def test_merge_sorted_all_pad(backend):
    """Empty (all-PAD) operands on either or both sides."""
    occupied = from_numpy(np.array([[1, 5], [2, 6]]), 16)
    hollow = empty(8, 2)
    for full, delta in ((occupied, hollow), (hollow, occupied),
                        (hollow, hollow)):
        got = R.merge_sorted(full, delta, PRESENCE, 32, backend=backend)
        _assert_same(got, _concat_oracle(full, delta, PRESENCE, 32,
                                         backend=backend))


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
def test_merge_sorted_overflow(backend):
    """out_cap smaller than the distinct union: overflow flag set, same
    as the concat path."""
    full = from_numpy(np.arange(20)[:, None], 32)
    delta = from_numpy((np.arange(20) + 100)[:, None], 32)
    got = R.merge_sorted(full, delta, PRESENCE, 8, backend=backend)
    assert bool(got[1])
    want = _concat_oracle(full, delta, PRESENCE, 8, backend=backend)
    assert bool(want[1])
    np.testing.assert_array_equal(np.asarray(got[0].data),
                                  np.asarray(want[0].data))


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
def test_merge_sorted_counting_cancellation(backend):
    """COUNTING: multiplicities add across the boundary; zero-count
    rows drop (the retraction fixpoint)."""
    full = from_numpy(np.array([[1], [2], [3]]), 16,
                      val=np.array([1, 2, 1]), val_identity=0)
    delta = from_numpy(np.array([[1], [2], [4]]), 8,
                       val=np.array([-1, 3, 5]), val_identity=0)
    got = R.merge_sorted(full, delta, COUNTING, 32, backend=backend)
    _assert_same(got, _concat_oracle(full, delta, COUNTING, 32,
                                     backend=backend))
    assert to_numpy(got[0]).tolist() == [[2], [3], [4]]
    assert got[0].val[:3].tolist() == [5, 1, 5]


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
def test_merge_sorted_min_monoid(backend):
    full = from_numpy(np.array([[1], [2]]), 16, val=np.array([5, 5]),
                      val_identity=MIN_MONOID.identity)
    delta = from_numpy(np.array([[2], [3]]), 8, val=np.array([3, 9]),
                       val_identity=MIN_MONOID.identity)
    got = R.merge_sorted(full, delta, MIN_MONOID, 32, backend=backend)
    _assert_same(got, _concat_oracle(full, delta, MIN_MONOID, 32,
                                     backend=backend))
    assert got[0].val[:3].tolist() == [5, 3, 9]


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
@pytest.mark.parametrize("seed", range(2))
def test_merge_sorted_multiword_keys(backend, seed):
    """Wide (>= 4-column) rows merge on multi-word keys."""
    rng = np.random.default_rng(seed)
    full = from_numpy(rng.integers(0, 4, size=(40, 5)), 64)
    delta = from_numpy(rng.integers(0, 4, size=(12, 5)), 16)
    got = R.merge_sorted(full, delta, PRESENCE, 128, backend=backend)
    _assert_same(got, _concat_oracle(full, delta, PRESENCE, 128,
                                     backend=backend))


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.name)
def test_merge_sorted_forced_multiword_matches_fastpath(backend):
    """The multi-word rank-merge path agrees with the single-word fast
    path on narrow keys (relation.force_multiword)."""
    rng = np.random.default_rng(7)
    full = from_numpy(rng.integers(0, 9, size=(25, 2)), 32)
    delta = from_numpy(rng.integers(0, 9, size=(9, 2)), 16)
    narrow = R.merge_sorted(full, delta, PRESENCE, 64, backend=backend)
    with force_multiword():
        wide = R.merge_sorted(full, delta, PRESENCE, 64, backend=backend)
    _assert_same(wide, narrow)


def test_merge_falls_back_on_non_identity_witness():
    """merge() only takes the incremental path for identity-sorted
    operands; an arranged (non-identity) operand falls back to
    concat + sort with identical results."""
    full = from_numpy(np.array([[0, 9], [1, 1], [2, 5]]), 16)
    arranged = R.arrange(full, (1,))
    delta = from_numpy(np.array([[7, 0]]), 8)
    with counter_scope() as c:
        got = R.merge(arranged, delta, PRESENCE, 32)
    assert c["merge_sorted"] == 0 and c["sorts"] >= 1
    want = R.merge(full, delta, PRESENCE, 32)
    np.testing.assert_array_equal(np.asarray(got[0].data),
                                  np.asarray(want[0].data))


# -- whole-fixpoint equivalence: arrangements on vs off ----------------------

def _run_pair(src, edbs, on_cfg=None, off_cfg=None):
    out_on, st_on = Engine(compile_program(src),
                           on_cfg or _cfg(True)).run(dict(edbs))
    out_off, st_off = Engine(compile_program(src),
                             off_cfg or _cfg(False)).run(dict(edbs))
    assert out_on.keys() == out_off.keys()
    for name in out_on:
        np.testing.assert_array_equal(out_on[name], out_off[name])
        assert out_on[name].dtype == out_off[name].dtype
    assert st_on.iterations == st_off.iterations
    return st_on


@pytest.mark.parametrize("program", ["TC", "SG", "Reach", "Count",
                                     "Sum", "Negation",
                                     "WideReach", "WideReach2",
                                     "WideJoin", "WideAgg"])
def test_fixpoint_equivalence_corpus(program):
    """Cache-on == cache-off, byte for byte, on the shared corpus."""
    src, edbs = _datasets()[program]
    _run_pair(src, edbs)


@pytest.mark.parametrize("program", ["TC", "Sum", "WideReach2"])
def test_fixpoint_equivalence_pallas(program):
    """The incremental maintenance path through the Pallas merge-path
    kernels (interpret mode) pins the same equivalence."""
    src, edbs = _datasets()[program]
    _run_pair(src, edbs,
              on_cfg=_cfg(True, kernel_backend="pallas-interpret"),
              off_cfg=_cfg(False, kernel_backend="pallas-interpret"))


def test_fixpoint_equivalence_device_mode():
    """The cache lives inside the while_loop body in device mode."""
    src, edbs = _datasets()["TC"]
    _run_pair(src, edbs,
              on_cfg=_cfg(True, mode="device"),
              off_cfg=_cfg(False, mode="device"))


def test_fixpoint_fewer_sorts_with_arrangements():
    """The structural perf claim: with the layer on, the traced
    fixpoint contains strictly fewer sort launches and at least one
    rank-merge maintenance step."""
    src, edbs = _datasets()["TC"]
    with counter_scope() as on:
        Engine(compile_program(src), _cfg(True)).run(dict(edbs))
    with counter_scope() as off:
        Engine(compile_program(src), _cfg(False)).run(dict(edbs))
    assert on["merge_sorted"] > 0
    assert on["sorts"] < off["sorts"]


# -- sharded equivalence -----------------------------------------------------

@pytest.mark.parametrize("shards", (1, 2, 4, 8))
@pytest.mark.parametrize("program", ["TC", "WideReach2"])
def test_sharded_equivalence(program, shards):
    """ShardedEngine with the arrangement layer (incremental shard-
    local merges + memoized repartitions) == single-device baseline
    with the layer off."""
    from repro.engine.shard import ShardedEngine
    _need(shards)
    src, edbs = _datasets()[program]
    out_s, st_s = Engine(compile_program(src),
                         _cfg(False)).run(dict(edbs))
    eng = ShardedEngine(compile_program(src),
                        _cfg(True, shards=shards))
    out_p, st_p = eng.run(dict(edbs))
    assert out_s.keys() == out_p.keys()
    for name in out_s:
        np.testing.assert_array_equal(out_s[name], out_p[name])
    assert st_s.iterations == st_p.iterations


@pytest.mark.parametrize("shards", (2, 4))
def test_sharded_cache_off_equivalence(shards):
    """Sharded × arrangements-off still matches sharded × on (the flag
    composes with the sharded driver in both states)."""
    from repro.engine.shard import ShardedEngine
    _need(shards)
    src, edbs = _datasets()["TC"]
    out_on, st_on = ShardedEngine(
        compile_program(src), _cfg(True, shards=shards)).run(dict(edbs))
    out_off, st_off = ShardedEngine(
        compile_program(src), _cfg(False, shards=shards)).run(dict(edbs))
    for name in out_on:
        np.testing.assert_array_equal(out_on[name], out_off[name])
    assert st_on.iterations == st_off.iterations


# -- incremental maintenance equivalence -------------------------------------

def test_incremental_equivalence():
    """Seeded continuations (insert + DRed delete) under the
    arrangement layer match the layer-off engine state for state."""
    src, edbs = _datasets()["TC"]
    rng = np.random.default_rng(3)
    ins = {"edge": rng.integers(0, 16, size=(6, 2))}
    dels = {"edge": np.asarray(edbs["edge"][:4])}

    snaps = []
    for arrangements in (True, False):
        inc = IncrementalEngine(compile_program(src),
                                _cfg(arrangements))
        inc.initialize({k: v.copy() for k, v in edbs.items()})
        inc.apply(inserts={k: v.copy() for k, v in ins.items()})
        inc.apply(deletes={k: v.copy() for k, v in dels.items()})
        snaps.append(inc.snapshot())
    on, off = snaps
    assert on.keys() == off.keys()
    for name in on:
        np.testing.assert_array_equal(on[name], off[name])


def test_incremental_matches_batch_recompute():
    """End state of incremental maintenance with the arrangement layer
    == batch recompute of the final EDB state."""
    src, edbs = _datasets()["TC"]
    rng = np.random.default_rng(5)
    ins = {"edge": rng.integers(0, 16, size=(8, 2))}

    inc = IncrementalEngine(compile_program(src), _cfg(True))
    inc.initialize({k: v.copy() for k, v in edbs.items()})
    inc.apply(inserts={k: v.copy() for k, v in ins.items()})
    final_edb = {"edge": np.array(sorted(
        set(map(tuple, edbs["edge"])) | set(map(tuple, ins["edge"]))))}
    batch, _ = Engine(compile_program(src), _cfg(True)).run(final_edb)
    snap = inc.snapshot()
    np.testing.assert_array_equal(snap["tc"], batch["tc"])
