"""Kernel-backend equivalence: the Pallas dispatch (interpret mode on
CPU — the exact kernel bodies that deploy on TPU) must be bit-for-bit
interchangeable with the pure-jnp dispatch across whole fixpoints, plus
direct adversarial property tests for the probe primitive itself."""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.programs import equivalence_datasets
from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig
from repro.engine.backend import (
    JNP, JnpDispatch, PallasDispatch, resolve_backend,
)
from repro.engine.relation import KEY_PAD
from repro.kernels import ops, ref

def _cfg(backend, **kw):
    d = dict(idb_cap=1 << 10, intermediate_cap=1 << 12,
             kernel_backend=backend)
    d.update(kw)
    return EngineConfig(**d)


# shared with tests/test_sharded.py — one corpus, two equivalence axes
_datasets = equivalence_datasets


@pytest.mark.parametrize("program", ["TC", "SG", "Reach", "Count",
                                     "Sum", "Negation",
                                     "WideReach", "WideReach2",
                                     "WideJoin", "WideAgg"])
def test_fixpoint_backend_equivalence(program):
    """jnp and Pallas backends: byte-identical relations, identical
    iteration counts — narrow (single-word fast path) and wide
    (multi-word key) programs alike."""
    src, edbs = _datasets()[program]
    out_j, st_j = Engine(compile_program(src),
                         _cfg("jnp")).run(dict(edbs))
    out_p, st_p = Engine(compile_program(src),
                         _cfg("pallas-interpret")).run(dict(edbs))
    assert out_j.keys() == out_p.keys()
    for name in out_j:
        np.testing.assert_array_equal(out_j[name], out_p[name])
    assert st_j.iterations == st_p.iterations


def test_fixpoint_backend_equivalence_device_mode():
    """The dispatch also holds inside the single-while_loop device
    path."""
    src, edbs = _datasets()["TC"]
    out_j, st_j = Engine(compile_program(src),
                         _cfg("jnp", mode="device")).run(dict(edbs))
    out_p, st_p = Engine(compile_program(src),
                         _cfg("pallas-interpret", mode="device")).run(
                             dict(edbs))
    np.testing.assert_array_equal(out_j["tc"], out_p["tc"])
    assert st_j.iterations == st_p.iterations


def test_resolve_backend():
    assert resolve_backend("jnp") is JNP
    assert isinstance(resolve_backend("jnp"), JnpDispatch)
    pb = resolve_backend("pallas-interpret")
    assert isinstance(pb, PallasDispatch) and pb.interpret
    # no TPU in CI: auto falls back to jnp, and "pallas" (compiled
    # kernels) refuses instead of silently interpreting
    import jax
    if jax.default_backend() != "tpu":
        assert isinstance(resolve_backend("auto"), JnpDispatch)
        with pytest.raises(RuntimeError, match="pallas-interpret"):
            resolve_backend("pallas")
    else:
        assert not resolve_backend("pallas").interpret
    assert resolve_backend(pb) is pb        # pass-through
    assert type(resolve_backend(None)) is type(resolve_backend("auto"))
    with pytest.raises(ValueError):
        resolve_backend("cuda")


# -- probe primitive: adversarial rank properties ----------------------------

def _assert_probe_matches(build, probe):
    """Pallas ranks == searchsorted ranks; for KEY_PAD probes only lo is
    contractually exact (hi may count kernel padding — relops masks
    dead-probe counts, see backend.py docstring)."""
    b, p = jnp.asarray(build), jnp.asarray(probe)
    lo, hi = ops.merge_probe_counts(b, p, backend="interpret",
                                    probe_block=128, build_block=128)
    rlo, rhi = ref.merge_probe_ref(b, p)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    live = np.asarray(probe) != int(KEY_PAD)
    np.testing.assert_array_equal(np.asarray(hi)[live],
                                  np.asarray(rhi)[live])


def test_probe_duplicate_keys():
    build = np.array([2, 2, 2, 2, 5, 5, 9, 9, 9], np.int64)
    probe = np.array([1, 2, 2, 3, 5, 9, 9, 10], np.int64)
    _assert_probe_matches(build, probe)


def test_probe_all_pad_build():
    build = np.full(64, int(KEY_PAD), np.int64)
    probe = np.sort(np.random.default_rng(1).integers(
        0, 1 << 40, 32)).astype(np.int64)
    _assert_probe_matches(build, probe)


def test_probe_all_pad_probe():
    build = np.sort(np.random.default_rng(2).integers(
        0, 1 << 40, 32)).astype(np.int64)
    probe = np.full(16, int(KEY_PAD), np.int64)
    _assert_probe_matches(build, probe)


def test_probe_empty_build():
    build = np.zeros((0,), np.int64)
    probe = np.array([0, 3, 1 << 40, int(KEY_PAD)], np.int64)
    _assert_probe_matches(build, probe)


def test_probe_mixed_pad_tail():
    """Arrangement shape: live sorted prefix, KEY_PAD tail on both
    sides — exactly what relops.join feeds the kernel."""
    rng = np.random.default_rng(3)
    build = np.concatenate([
        np.sort(rng.integers(0, 1000, 40)),
        np.full(24, int(KEY_PAD))]).astype(np.int64)
    probe = np.concatenate([
        np.sort(rng.choice(build[:40], 20)),
        np.full(12, int(KEY_PAD))]).astype(np.int64)
    _assert_probe_matches(build, probe)


@pytest.mark.parametrize("seed", range(3))
def test_probe_randomized_63bit(seed):
    """Random keys over the full packed range (3-column packs reach
    bit 62), straddling the in-kernel split point."""
    rng = np.random.default_rng(seed)
    hi = (1 << 63) - 1
    build = np.sort(rng.integers(0, hi, 200, dtype=np.int64))
    hit = rng.choice(build, 50)
    probe = np.sort(np.concatenate(
        [hit, rng.integers(0, hi, 77, dtype=np.int64)])).astype(np.int64)
    _assert_probe_matches(build, probe)


def test_probe_three_column_pack_bit62():
    """Regression: a 3-column packed key with the first column >= 2**20
    sets bit 62; a split that drops it collapses the key to a small
    value and returns wrong ranks (lo/hi = 1/1 for probe 5 below)."""
    big = (1 << 20) << 42                       # pack(2**20, 0, 0)
    build = np.array([big], np.int64)
    probe = np.array([5, big, big + 1], np.int64)
    _assert_probe_matches(build, probe)
    lo, hi = ops.merge_probe_counts(
        jnp.asarray(build), jnp.asarray(probe), backend="interpret",
        probe_block=8, build_block=8)
    assert lo.tolist() == [0, 0, 1] and hi.tolist() == [0, 1, 1]


def test_backend_probe_objects_agree():
    """The dispatch objects themselves, not just the raw ops."""
    rng = np.random.default_rng(7)
    build = np.sort(rng.integers(0, 1 << 40, 100)).astype(np.int64)
    probe = np.sort(rng.integers(0, 1 << 40, 100)).astype(np.int64)
    jl, jh = JnpDispatch().probe(jnp.asarray(build), jnp.asarray(probe))
    pl_, ph = PallasDispatch(interpret=True).probe(
        jnp.asarray(build), jnp.asarray(probe))
    np.testing.assert_array_equal(np.asarray(jl), np.asarray(pl_))
    np.testing.assert_array_equal(np.asarray(jh), np.asarray(ph))
    for bk in (JnpDispatch(), PallasDispatch(interpret=True)):
        np.testing.assert_array_equal(
            np.asarray(bk.probe_lo(jnp.asarray(build),
                                   jnp.asarray(probe))),
            np.asarray(jl))


# -- membership through the dispatch seam ------------------------------------

def _membership_oracle(left_rows, l_keys, right_rows, r_keys):
    rset = {tuple(r[c] for c in r_keys) for r in right_rows}
    return np.array(
        [tuple(r[c] for c in l_keys) in rset for r in left_rows])


@pytest.mark.parametrize("seed", range(3))
def test_membership_backend_equivalence(seed):
    """relops.membership probes through the injected backend. The probe
    side (left's key columns) is generally UNSORTED — the Pallas path
    must sort-and-scatter and still agree bit-for-bit with jnp."""
    from repro.engine import relops as R
    from repro.engine.relation import from_numpy

    rng = np.random.default_rng(seed)
    left = from_numpy(rng.integers(0, 12, size=(40, 2)), 64)
    right = from_numpy(rng.integers(0, 12, size=(25, 2)), 32)
    l_keys, r_keys = (1,), (0,)   # left col 1 is unsorted in row order
    want = _membership_oracle(
        np.asarray(left.data[:int(left.n)]), l_keys,
        np.asarray(right.data[:int(right.n)]), r_keys)
    for bk in (JnpDispatch(), PallasDispatch(interpret=True)):
        got = np.asarray(R.membership(left, right, l_keys, r_keys,
                                      backend=bk))
        np.testing.assert_array_equal(got[:int(left.n)], want)
        assert not got[int(left.n):].any()   # dead rows never members


def test_membership_backend_empty_and_pad():
    """Adversarial shapes: empty right side and all-dead left rows."""
    from repro.engine import relops as R
    from repro.engine.relation import empty, from_numpy

    left = from_numpy(np.array([[3, 1], [7, 2]]), 16)
    right = empty(8, 2)
    dead = empty(16, 2)
    occupied = from_numpy(np.array([[3, 9]]), 8)
    for bk in (JnpDispatch(), PallasDispatch(interpret=True)):
        assert not np.asarray(
            R.membership(left, right, (0,), (0,), backend=bk)).any()
        assert not np.asarray(
            R.membership(dead, occupied, (0,), (0,), backend=bk)).any()
        got = np.asarray(
            R.membership(left, occupied, (0,), (0,), backend=bk))
        np.testing.assert_array_equal(got[:2], [True, False])


def test_difference_backend_equivalence():
    """difference (the PRESENCE semi-naive delta) agrees across
    backends including the n/arity metadata."""
    from repro.engine import relops as R
    from repro.engine.relation import from_numpy

    rng = np.random.default_rng(11)
    a = from_numpy(rng.integers(0, 10, size=(30, 2)), 64)
    b = from_numpy(rng.integers(0, 10, size=(30, 2)), 64)
    outs = []
    for bk in (JnpDispatch(), PallasDispatch(interpret=True)):
        rel, ov = R.difference(a, b, backend=bk)
        assert not bool(ov)
        outs.append((np.asarray(rel.data), int(rel.n)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    assert outs[0][1] == outs[1][1]


def test_backend_segment_reduce_int_identities():
    """Integer reductions: occupied segments exact, empty segments get
    the jnp int32 identities (segment_min -> INT32_MAX etc.)."""
    seg = jnp.array([0, 0, 2, 2, 2], jnp.int32)
    val = jnp.array([5, -3, 7, 7, 1], jnp.int32)
    jd, pd = JnpDispatch(), PallasDispatch(interpret=True)
    for op in ("sum", "min", "max"):
        a = jd.segment_reduce(val, seg, 4, op)
        b = pd.segment_reduce(val, seg, 4, op)
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_backend_segment_reduce_int_exact_beyond_f24():
    """Regression: integer sums/extrema past 2**24 must stay exact —
    the kernel accumulates int32 natively, never through float32
    (which would round 16777217 -> 16777216)."""
    seg = jnp.array([0, 0, 0, 0, 1], jnp.int32)
    val = jnp.array([16777217, 1, 1, 1, -16777217], jnp.int32)
    jd, pd = JnpDispatch(), PallasDispatch(interpret=True)
    for op in ("sum", "min", "max"):
        a = jd.segment_reduce(val, seg, 3, op)
        b = pd.segment_reduce(val, seg, 3, op)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(pd.segment_reduce(val, seg, 3, "sum")[0]) == 16777220
    assert int(pd.segment_reduce(val, seg, 3, "max")[0]) == 16777217
