"""Sharded-vs-single-device equivalence (engine/shard.py).

The contract mirrors PR 1's backend equivalence: ``ShardedEngine`` must
produce byte-identical fixpoints and identical iteration counts to
``Engine`` at every shard count, under either kernel backend, in both
host and device modes — sharding changes where rows live, never what is
derived.

Run standalone (or via ``make test-sharded`` / the CI ``sharded`` step)
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so all
shard counts execute; inside the full suite, cases needing more devices
than are visible skip. Importing this module first (before jax device
init) sets the flag itself.
"""
from benchmarks.hostdevices import force_host_device_count

force_host_device_count()  # must precede the first jax device init

import numpy as np
import pytest

import jax

from benchmarks.programs import CC, TC, equivalence_datasets
from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig, make_engine
from repro.engine.relation import from_numpy
from repro.engine.shard import ShardedEngine, ShardedRelation

SHARD_COUNTS = (1, 2, 4, 8)


def _cfg(**kw):
    d = dict(idb_cap=1 << 10, intermediate_cap=1 << 12,
             kernel_backend="jnp")
    d.update(kw)
    return EngineConfig(**d)


def _need(shards: int):
    if shards > len(jax.devices()):
        pytest.skip(f"needs {shards} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count)")


# shared with tests/test_backend_equivalence.py — one corpus pins both
# equivalence axes (kernel backends there, shard counts here)
_datasets = equivalence_datasets


def _assert_equivalent(src, edbs, sharded_cfg, single_cfg=None):
    out_s, st_s = Engine(compile_program(src),
                         single_cfg or _cfg()).run(dict(edbs))
    # ShardedEngine directly (not make_engine) so shards=1 also
    # exercises the sharded driver on a 1-device mesh
    eng = ShardedEngine(compile_program(src), sharded_cfg)
    out_p, st_p = eng.run(dict(edbs))
    assert out_s.keys() == out_p.keys()
    for name in out_s:
        np.testing.assert_array_equal(out_s[name], out_p[name])
        assert out_s[name].dtype == out_p[name].dtype
    assert st_s.iterations == st_p.iterations
    return eng


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("program", ["TC", "SG", "Reach", "Count", "Sum"])
def test_sharded_fixpoint_equivalence(program, shards):
    """Byte-identical relations + identical iteration counts at every
    shard count, for graph recursion, mutual recursion, and stratified
    COUNT/SUM aggregation."""
    _need(shards)
    src, edbs = _datasets()[program]
    eng = _assert_equivalent(src, edbs, _cfg(shards=shards))
    assert eng.num_shards == shards


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("program", ["WideReach", "WideReach2",
                                     "WideJoin", "WideAgg"])
def test_sharded_wide_fixpoint_equivalence(program, shards):
    """Wide (4-6 stored column) programs: rows home by the any-arity
    FNV row hash and probe with multi-word keys shard-locally — still
    byte-identical to single-device at every shard count."""
    _need(shards)
    src, edbs = _datasets()[program]
    _assert_equivalent(src, edbs, _cfg(shards=shards))


@pytest.mark.parametrize("shards", (2, 8))
def test_sharded_monoid_lattice(shards):
    """MIN-monoid fixpoint (CC): lattice values combine across shards
    exactly as on one device."""
    _need(shards)
    rng = np.random.default_rng(3)
    edbs = {"edge": rng.integers(0, 30, size=(50, 2))}
    _assert_equivalent(CC, edbs, _cfg(shards=shards))


@pytest.mark.parametrize("shards", (2, 4))
def test_sharded_negation(shards):
    """Stratified negation: the sharded antijoin/membership path (and
    the psum'd zero-key ground guard) agree with single-device."""
    _need(shards)
    src, edbs = _datasets()["Negation"]
    _assert_equivalent(src, edbs, _cfg(shards=shards))


def test_sharded_device_mode():
    """The whole-stratum while_loop runs inside shard_map with a psum
    termination test; results and iteration counts still match the
    single-device device mode."""
    _need(4)
    src, edbs = _datasets()["TC"]
    _assert_equivalent(src, edbs, _cfg(shards=4, mode="device"),
                       single_cfg=_cfg(mode="device"))


def test_sharded_composes_with_pallas_backend():
    """sharded x pallas: the kernel dispatch runs shard-locally under
    shard_map (interpret mode on CPU) and stays byte-identical to the
    single-device jnp engine."""
    _need(2)
    src, edbs = _datasets()["TC"]
    _assert_equivalent(src, edbs,
                       _cfg(shards=2, kernel_backend="pallas-interpret"))


def test_sharded_skewed_keys():
    """Every edge shares one source node: the join key hashes to a
    single shard (worst-case skew) — still correct, just imbalanced."""
    _need(8)
    edbs = {"edge": np.stack(
        [np.zeros(30, int), np.arange(30)], axis=1)}
    _assert_equivalent(TC, edbs, _cfg(shards=8))


@pytest.mark.parametrize("cols", [(0,), (0, 1)])
def test_home_hash_spreads_small_ids(cols):
    """Vertex ids below 2**16 spread evenly over 4 shards, by one column
    (a join key, an arity-1 IDB) or by the full row, and the
    sanitizer's host mirror homes every row where the engine does.
    (Before the hash's finalizer every such row homed on one shard.)"""
    import jax.numpy as jnp
    from repro.core.analysis.sanitize import _host_row_hash
    from repro.engine.shard import shard_of

    rows = np.random.default_rng(3).integers(0, 1 << 16, (1 << 16, 2))
    dest = np.asarray(shard_of(jnp.asarray(rows), cols,
                               jnp.ones(len(rows), bool), 4))
    counts = np.bincount(dest, minlength=4)
    assert counts.shape == (4,)
    assert np.all(np.abs(counts - len(rows) / 4) < 0.03 * len(rows) / 4)
    host = (_host_row_hash(rows[:, list(cols)]) >> np.uint64(33)) \
        % np.uint64(4)
    np.testing.assert_array_equal(host, dest)


def test_sharded_empty_shards():
    """Fewer live rows than shards: most shards hold nothing at every
    iteration and the fixpoint still terminates identically."""
    _need(8)
    edbs = {"edge": np.array([[1, 2], [2, 3]])}
    _assert_equivalent(TC, edbs, _cfg(shards=8))


def test_sharded_empty_edb():
    _need(4)
    edbs = {"edge": np.zeros((0, 2), int)}
    _assert_equivalent(TC, edbs, _cfg(shards=4))


def test_make_engine_selection():
    prog = compile_program(TC)
    assert type(make_engine(prog)) is Engine
    assert type(make_engine(prog, _cfg())) is Engine
    assert type(make_engine(prog, _cfg(shards=1))) is Engine
    _need(2)
    assert isinstance(make_engine(prog, _cfg(shards=2)), ShardedEngine)


def test_shard_mesh_validation():
    import jax as j
    from repro.launch.mesh import make_shard_mesh
    with pytest.raises(ValueError):
        make_shard_mesh(0)
    with pytest.raises(ValueError):
        make_shard_mesh(len(j.devices()) + 1)
    m = make_shard_mesh(1)
    assert m.axis_names == ("shards",)


def test_sharded_relation_invariant():
    """Partition invariant: after a run, every shard block of every IDB
    is itself a sorted, distinct, PAD-tailed arrangement, and shard
    assignment matches the home hash."""
    _need(4)
    from repro.engine.relation import PAD
    from repro.engine.shard import shard_of
    import jax.numpy as jnp

    src, edbs = _datasets()["TC"]
    eng = make_engine(compile_program(src), _cfg(shards=4))
    eng.run(dict(edbs))
    rel = eng.last_env[("tc", "full")]
    assert isinstance(rel, ShardedRelation)
    data = np.asarray(rel.data)
    ns = np.asarray(rel.n)
    assert int(ns.sum()) > 0
    for s in range(rel.num_shards):
        block = data[s]
        n = int(ns[s])
        assert np.all(block[n:] == int(PAD))          # PAD tail
        live = block[:n]
        if n:
            order = np.lexsort(tuple(
                live[:, c] for c in reversed(range(live.shape[1]))))
            assert np.array_equal(order, np.arange(n))  # sorted
            assert np.unique(live, axis=0).shape[0] == n  # distinct
            dest = np.asarray(shard_of(
                jnp.asarray(live), tuple(range(live.shape[1])),
                jnp.ones((n,), bool), rel.num_shards))
            assert np.all(dest == s)                  # home partition


# -- gather/scatter round trip (the seam all incremental state crosses) ------

def _roundtrip_cases() -> dict:
    """Arbitrary arrangements: PAD tails, a relation full to capacity,
    empty, multi-word (5-column) keys, and payload values."""
    rng = np.random.default_rng(9)
    full_rows = np.unique(rng.integers(0, 99, size=(40, 2)), axis=0)[:16]
    val_rows = np.unique(rng.integers(0, 30, size=(25, 1)), axis=0)
    return {
        "sparse": from_numpy(rng.integers(0, 50, size=(20, 2)), 64),
        "full": from_numpy(full_rows, 16),
        "empty": from_numpy(np.zeros((0, 3), int), 32),
        "wide": from_numpy(rng.integers(0, 9, size=(30, 5)), 64),
        "valued": from_numpy(
            val_rows, 64,
            val=rng.integers(0, 100, size=(len(val_rows),)),
            val_identity=0, dedupe=False),
    }


def _assert_roundtrip(eng: ShardedEngine, name: str, rel) -> None:
    sh = eng._scatter_env({name: rel})[name]
    assert isinstance(sh, ShardedRelation)
    assert sh.num_shards == eng.num_shards
    back = eng._host_relation(sh)
    assert back.capacity == rel.capacity
    assert int(back.n) == int(rel.n)
    np.testing.assert_array_equal(np.asarray(back.data),
                                  np.asarray(rel.data))
    if rel.val is not None:
        n = int(rel.n)
        np.testing.assert_array_equal(np.asarray(back.val[:n]),
                                      np.asarray(rel.val[:n]))


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("case", sorted(_roundtrip_cases()))
def test_scatter_gather_roundtrip(case, shards):
    """``_host_relation`` ∘ ``_scatter_env`` is identity on arbitrary
    arrangements — every incremental seed and every export crosses
    this seam. Covers empty shards implicitly (fewer rows than shards
    in the 'empty'/'full' cases at 8 shards)."""
    _need(shards)
    eng = ShardedEngine(compile_program(TC), _cfg(shards=shards))
    _assert_roundtrip(eng, "r", _roundtrip_cases()[case])


@pytest.mark.parametrize("shards", (1, 2))
def test_scatter_gather_roundtrip_monoid(shards):
    """Monoid (MIN) relations round-trip with their lattice payload:
    the scatter uses the IDB's own semiring identity for dead rows."""
    _need(shards)
    eng = ShardedEngine(compile_program(CC), _cfg(shards=shards))
    rng = np.random.default_rng(5)
    rows = np.unique(rng.integers(0, 40, size=(30, 1)), axis=0)
    rel = from_numpy(rows, 64, val=rng.integers(0, 40, size=(len(rows),)),
                     val_identity=np.iinfo(np.int32).max, dedupe=False)
    _assert_roundtrip(eng, "cc", rel)


def test_host_relation_preserves_capacity():
    """Regression: ``_host_relation`` used to recompute capacity as
    next-pow2 of the row count, silently shrinking a sparse relation
    below its stored cap — a scatter/gather round trip could then
    overflow on the next merge. The gathered relation must keep the
    per-shard capacity (growing only when the combined rows exceed
    it)."""
    _need(1)
    from repro.engine import relops as R
    from repro.engine.semiring import PRESENCE

    eng = ShardedEngine(compile_program(TC), _cfg(shards=1))
    rng = np.random.default_rng(1)
    rel = from_numpy(rng.integers(0, 10, size=(3, 2)), 1024)
    back = eng._host_relation(eng._scatter_env({"r": rel})["r"])
    assert back.capacity == 1024  # used to shrink to 16
    delta = from_numpy(np.stack([np.arange(500), 1 + np.arange(500)],
                                axis=1), 1024)
    merged, ov = R.merge(back, delta, PRESENCE, 1024)
    assert not bool(ov)
    assert int(merged.n) >= 500
