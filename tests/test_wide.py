"""Wide-relation (multi-word row key) suite.

Pins the multi-word arrangement contract of relation.py end-to-end:

* the key representation itself (fast-path bit-equality, PAD sentinel,
  order isomorphism with column-lexicographic order);
* the multi-word probe primitive (jnp binary-search reference vs a
  brute-force oracle; the Pallas word-loop kernel vs the reference);
* wide relops (join / membership / difference) against set oracles on
  both kernel backends;
* whole wide fixpoints: byte-identical across jnp/pallas, matching an
  independent Python closure oracle;
* ``relation.force_multiword()``: narrow programs pushed through the
  multi-word path must stay byte-identical to the fast path — the
  fast-path-preservation guarantee, tested from the other side;
* incremental maintenance (seeded continuations) over wide IDBs.

Sharded wide coverage lives in tests/test_sharded.py (same corpus,
1/2/4/8 shards).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.programs import WIDE_REACH2, equivalence_datasets
from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig
from repro.engine.backend import JnpDispatch, PallasDispatch
from repro.engine.incremental import IncrementalEngine
from repro.engine.relation import (
    KEY_PAD, MAX_STORED_COLUMNS, force_multiword, from_numpy, key_width,
    lex_order_words, pack_columns, pack_key_words,
)
from repro.engine import relops as R
from repro.engine.semiring import COUNTING, MIN_MONOID, PRESENCE
from repro.kernels import ops, ref

BACKENDS = (JnpDispatch(), PallasDispatch(interpret=True))


def _cfg(backend="jnp", **kw):
    d = dict(idb_cap=1 << 11, intermediate_cap=1 << 13,
             kernel_backend=backend)
    d.update(kw)
    return EngineConfig(**d)


# -- key representation ------------------------------------------------------

def test_key_width():
    assert [key_width(k) for k in range(0, 10)] == [
        1, 1, 1, 1, 2, 2, 2, 3, 3, 3]
    assert key_width(MAX_STORED_COLUMNS) == 3


def test_single_word_fast_path_bit_identical():
    """<= 3 key columns: word 0 is bit-for-bit the legacy packed key."""
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 1 << 20, size=(32, 3)), jnp.int32)
    live = jnp.arange(32) < 20
    for cols in [(0,), (1, 0), (0, 1, 2)]:
        words = pack_key_words(data, cols, live)
        assert words.shape == (32, 1)
        np.testing.assert_array_equal(
            np.asarray(words[:, 0]),
            np.asarray(pack_columns(data, cols, live)))


def test_multiword_pad_sentinel_every_word():
    """Dead rows are KEY_PAD in every word; live rows in none."""
    rng = np.random.default_rng(1)
    data = jnp.asarray(rng.integers(0, 100, size=(16, 5)), jnp.int32)
    live = jnp.arange(16) < 9
    words = np.asarray(pack_key_words(data, (0, 1, 2, 3, 4), live))
    assert words.shape == (16, 2)
    assert np.all(words[9:] == int(KEY_PAD))
    assert not np.any(words[:9] == int(KEY_PAD))


@pytest.mark.parametrize("ncols", [4, 5, 6, 8])
def test_multiword_order_isomorphism(ncols):
    """Sorting by word vectors == sorting by the column tuples."""
    rng = np.random.default_rng(ncols)
    rows = rng.integers(0, 4, size=(50, ncols))
    data = jnp.asarray(rows, jnp.int32)
    live = jnp.ones((50,), bool)
    words = pack_key_words(data, tuple(range(ncols)), live)
    assert words.shape[1] == key_width(ncols)
    by_words = np.asarray(lex_order_words(words))
    by_cols = np.lexsort(tuple(rows[:, c] for c in reversed(range(ncols))))
    np.testing.assert_array_equal(rows[by_words], rows[by_cols])


# -- multi-word probe primitive ----------------------------------------------

def _brute_ranks(build, probe):
    lo = np.array([sum(1 for r in build if tuple(r) < tuple(q))
                   for q in probe], np.int32)
    hi = np.array([sum(1 for r in build if tuple(r) <= tuple(q))
                   for q in probe], np.int32)
    return lo, hi


def _lexsorted(rows):
    w = rows.shape[1]
    return rows[np.lexsort(tuple(rows[:, c] for c in reversed(range(w))))]


@pytest.mark.parametrize("seed", range(3))
def test_probe_multi_ref_matches_bruteforce(seed):
    rng = np.random.default_rng(seed)
    build = _lexsorted(rng.integers(0, 5, size=(40, 3)).astype(np.int64))
    probe = rng.integers(0, 6, size=(25, 3)).astype(np.int64)
    lo, hi = ref.merge_probe_multi_ref(jnp.asarray(build),
                                       jnp.asarray(probe))
    blo, bhi = _brute_ranks(build, probe)
    np.testing.assert_array_equal(np.asarray(lo), blo)
    np.testing.assert_array_equal(np.asarray(hi), bhi)


def test_probe_multi_ref_w1_matches_searchsorted():
    """W = 1 multi-word ranks agree with the single-word reference."""
    rng = np.random.default_rng(5)
    build = np.sort(rng.integers(0, 1 << 40, 64)).astype(np.int64)
    probe = rng.integers(0, 1 << 40, 33).astype(np.int64)
    lo, hi = ref.merge_probe_multi_ref(
        jnp.asarray(build)[:, None], jnp.asarray(probe)[:, None])
    rlo, rhi = ref.merge_probe_ref(jnp.asarray(build), jnp.asarray(probe))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


def _assert_kernel_matches_ref(build, probe, **blocks):
    """Pallas multi kernel == reference; live probes only for hi (the
    same dead-probe contract as the single-word kernel)."""
    b, p = jnp.asarray(build), jnp.asarray(probe)
    lo, hi = ops.merge_probe_multi(b, p, backend="interpret", **blocks)
    rlo, rhi = ref.merge_probe_multi_ref(b, p)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    live = ~np.all(probe == int(KEY_PAD), axis=1)
    np.testing.assert_array_equal(np.asarray(hi)[live],
                                  np.asarray(rhi)[live])


@pytest.mark.parametrize("width", [2, 3])
@pytest.mark.parametrize("seed", range(2))
def test_probe_multi_kernel_randomized(width, seed):
    rng = np.random.default_rng(10 * width + seed)
    build = _lexsorted(
        rng.integers(0, 4, size=(70, width)).astype(np.int64))
    hit = build[rng.integers(0, 70, 20)]
    probe = _lexsorted(np.concatenate(
        [hit, rng.integers(0, 5, size=(17, width))]).astype(np.int64))
    _assert_kernel_matches_ref(build, probe,
                               probe_block=16, build_block=16)


def test_probe_multi_kernel_duplicates_and_pad_tail():
    """Arrangement shape: duplicate key runs, KEY_PAD tails both sides
    — exactly what relops.join feeds the kernel for a wide key."""
    rng = np.random.default_rng(42)
    live = _lexsorted(rng.integers(0, 3, size=(40, 2)).astype(np.int64))
    build = np.concatenate(
        [live, np.full((24, 2), int(KEY_PAD), np.int64)])
    probe = np.concatenate(
        [live[::2], np.full((12, 2), int(KEY_PAD), np.int64)])
    _assert_kernel_matches_ref(build, probe,
                               probe_block=16, build_block=16)


def test_probe_multi_kernel_empty_and_all_pad_build():
    probe = _lexsorted(
        np.random.default_rng(7).integers(
            0, 9, size=(10, 2)).astype(np.int64))
    _assert_kernel_matches_ref(np.zeros((0, 2), np.int64), probe,
                               probe_block=8, build_block=8)
    _assert_kernel_matches_ref(
        np.full((32, 2), int(KEY_PAD), np.int64), probe,
        probe_block=8, build_block=8)


def test_probe_multi_kernel_63bit_words():
    """Words spanning the full packed range straddle the in-kernel
    int32 split in every word position."""
    rng = np.random.default_rng(9)
    hi = (1 << 63) - 1
    build = _lexsorted(rng.integers(0, hi, size=(50, 2), dtype=np.int64))
    probe = _lexsorted(np.concatenate(
        [build[rng.integers(0, 50, 15)],
         rng.integers(0, hi, size=(9, 2), dtype=np.int64)]))
    _assert_kernel_matches_ref(build, probe,
                               probe_block=16, build_block=16)


def test_backend_probe_multi_objects_agree():
    rng = np.random.default_rng(11)
    build = _lexsorted(rng.integers(0, 6, size=(60, 3)).astype(np.int64))
    probe = _lexsorted(rng.integers(0, 6, size=(60, 3)).astype(np.int64))
    outs = []
    for bk in BACKENDS:
        lo, hi = bk.probe_multi(jnp.asarray(build), jnp.asarray(probe))
        lo2 = bk.probe_lo_multi(jnp.asarray(build), jnp.asarray(probe))
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo2))
        outs.append((np.asarray(lo), np.asarray(hi)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


# -- wide relops against set oracles -----------------------------------------

@pytest.mark.parametrize("seed", range(2))
def test_wide_join_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    lrows = rng.integers(0, 3, size=(40, 5))
    rrows = rng.integers(0, 3, size=(40, 5))
    left = from_numpy(lrows, 64)
    right = from_numpy(rrows, 64)
    keys = (0, 1, 2, 3)
    want = sorted({tuple(l) + (r[4],)
                   for l in map(tuple, np.unique(lrows, axis=0))
                   for r in map(tuple, np.unique(rrows, axis=0))
                   if l[:4] == r[:4]})
    for bk in BACKENDS:
        data, val, valid, total, ovf = R.join(
            left, right, keys, keys, (0, 1, 2, 3, 4), (4,),
            PRESENCE, 1 << 12, backend=bk)
        assert not bool(ovf)
        got = sorted(set(map(tuple, np.asarray(
            data)[np.asarray(valid)])))
        assert got == want


@pytest.mark.parametrize("seed", range(2))
def test_wide_membership_difference_match_oracle(seed):
    rng = np.random.default_rng(100 + seed)
    arows = rng.integers(0, 3, size=(30, 5))
    brows = rng.integers(0, 3, size=(30, 5))
    a, b = from_numpy(arows, 64), from_numpy(brows, 64)
    keys = tuple(range(5))
    bset = set(map(tuple, brows))
    want_mem = [tuple(r) in bset
                for r in np.asarray(a.data[:int(a.n)])]
    want_diff = sorted(set(map(tuple, arows)) - bset)
    for bk in BACKENDS:
        got = np.asarray(R.membership(a, b, keys, keys, backend=bk))
        assert list(got[:int(a.n)]) == want_mem
        assert not got[int(a.n):].any()
        diff, ov = R.difference(a, b, backend=bk)
        assert sorted(map(tuple, np.asarray(
            diff.data[:int(diff.n)]))) == want_diff


def test_wide_merge_with_delta_min_lattice():
    """Multi-word lattice lookup: only strictly-improved wide rows come
    back as the delta."""
    full = from_numpy(np.array([[1, 2, 3, 4], [5, 6, 7, 8]]), 16,
                      val=np.array([10, 20]),
                      val_identity=MIN_MONOID.identity, dedupe=False)
    derived = from_numpy(
        np.array([[1, 2, 3, 4], [5, 6, 7, 8], [9, 9, 9, 9]]), 16,
        val=np.array([5, 25, 7]),
        val_identity=MIN_MONOID.identity, dedupe=False)
    for bk in BACKENDS:
        nf, nd, ov = R.merge_with_delta(full, derived, MIN_MONOID, 16,
                                        backend=bk)
        rows = np.asarray(nd.data[:int(nd.n)])
        vals = np.asarray(nd.val[:int(nd.n)])
        got = sorted(map(tuple, np.concatenate([rows, vals[:, None]], 1)))
        # improved: [1,2,3,4] 10->5 and new row [9,9,9,9]=7; 20->20 not
        assert got == [(1, 2, 3, 4, 5), (9, 9, 9, 9, 7)]


# -- dedupe through the kernel-dispatch seam ---------------------------------

@pytest.mark.parametrize("sr", [COUNTING, MIN_MONOID])
def test_dedupe_combine_backend_equivalence(sr):
    """dedupe's duplicate-combine dispatches segment_reduce: both
    backends emit byte-identical relations (values included)."""
    rng = np.random.default_rng(3)
    data = jnp.asarray(rng.integers(0, 4, size=(64, 6)), jnp.int32)
    val = jnp.asarray(rng.integers(-5, 6, size=(64,)), jnp.int32)
    outs = []
    for bk in BACKENDS:
        rel, ov = R.dedupe(data, val, sr, 64, backend=bk)
        assert not bool(ov)
        outs.append((np.asarray(rel.data), np.asarray(rel.val),
                     int(rel.n)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert outs[0][2] == outs[1][2]


def test_dedupe_combine_matches_python_oracle():
    rng = np.random.default_rng(4)
    rows = rng.integers(0, 3, size=(40, 2))
    val = rng.integers(1, 5, size=(40,))
    want = {}
    for r, v in zip(map(tuple, rows), val):
        want[r] = want.get(r, 0) + int(v)
    for bk in BACKENDS:
        rel, _ = R.dedupe(jnp.asarray(rows, jnp.int32),
                          jnp.asarray(val, jnp.int32), COUNTING, 64,
                          backend=bk)
        got = {tuple(r): int(v) for r, v in zip(
            np.asarray(rel.data[:int(rel.n)]),
            np.asarray(rel.val[:int(rel.n)]))}
        assert got == want


# -- wide fixpoints -----------------------------------------------------------

def _wide_reach2_oracle(edge):
    from collections import defaultdict
    per_ctx = defaultdict(set)
    for c1, c2, f, x, y in edge:
        per_ctx[(c1, c2, f)].add((x, y))
    out = set()
    for ctx, es in per_ctx.items():
        tc = set(es)
        while True:
            new = {(x, z) for (x, y) in tc
                   for (y2, z) in es if y == y2} - tc
            if not new:
                break
            tc |= new
        out |= {ctx + xy for xy in tc}
    return np.array(sorted(out))


# backend equivalence for the wide family (byte-identical fixpoints on
# jnp vs Pallas) is parametrized into
# tests/test_backend_equivalence.py::test_fixpoint_backend_equivalence
# via the shared corpus; here we pin the *meaning* of those fixpoints
# against independent Python oracles plus the device-mode path.

def test_wide_reach2_matches_python_closure():
    src, edbs = equivalence_datasets()["WideReach2"]
    out, _ = Engine(compile_program(src), _cfg()).run(dict(edbs))
    np.testing.assert_array_equal(
        out["reach"], _wide_reach2_oracle(edbs["edge"]))


def test_wide_fixpoint_device_mode():
    src, edbs = equivalence_datasets()["WideReach2"]
    out_h, st_h = Engine(compile_program(src), _cfg()).run(dict(edbs))
    out_d, st_d = Engine(compile_program(src),
                         _cfg(mode="device")).run(dict(edbs))
    np.testing.assert_array_equal(out_h["reach"], out_d["reach"])
    assert st_h.iterations == st_d.iterations


def test_wide_agg_matches_python_groupby():
    src, edbs = equivalence_datasets()["WideAgg"]
    out, _ = Engine(compile_program(src), _cfg()).run(dict(edbs))
    want = {}
    for c, f, x, y, v in edbs["fact"]:
        want.setdefault((c, f, x, y), set()).add(v)
    want = np.array(sorted(k + (len(vs),) for k, vs in want.items()))
    np.testing.assert_array_equal(out["agg"], want)


# -- forced multi-word on the narrow corpus ----------------------------------

@pytest.mark.parametrize("backend", [
    "jnp", pytest.param("pallas-interpret", id="pallas")])
@pytest.mark.parametrize("program", ["TC", "SG", "Count", "Negation"])
def test_force_multiword_narrow_equivalence(program, backend):
    """The fast-path guarantee from the other side: pushing narrow
    programs through the multi-word machinery (extra constant word)
    yields byte-identical fixpoints and iteration counts."""
    src, edbs = equivalence_datasets()[program]
    base, st_b = Engine(compile_program(src), _cfg()).run(dict(edbs))
    with force_multiword():
        forced, st_f = Engine(compile_program(src),
                              _cfg(backend)).run(dict(edbs))
    assert base.keys() == forced.keys()
    for name in base:
        np.testing.assert_array_equal(base[name], forced[name])
    assert st_b.iterations == st_f.iterations


# -- incremental maintenance over wide IDBs ----------------------------------

def test_wide_incremental_insert_matches_batch():
    rng = np.random.default_rng(21)
    edge = np.concatenate([rng.integers(0, 2, size=(60, 3)),
                           rng.integers(0, 6, size=(60, 2))], axis=1)
    inc = IncrementalEngine(compile_program(WIDE_REACH2), _cfg())
    inc.initialize({"edge": edge[:40]})
    snap = inc.apply(inserts={"edge": edge[40:]})
    want, _ = Engine(compile_program(WIDE_REACH2), _cfg()).run(
        {"edge": np.unique(edge, axis=0)})
    np.testing.assert_array_equal(snap["reach"], want["reach"])


def test_wide_incremental_delete_matches_batch():
    rng = np.random.default_rng(22)
    edge = np.concatenate([rng.integers(0, 2, size=(50, 3)),
                           rng.integers(0, 5, size=(50, 2))], axis=1)
    inc = IncrementalEngine(compile_program(WIDE_REACH2), _cfg())
    inc.initialize({"edge": edge})
    snap = inc.apply(deletes={"edge": edge[:15]})
    rest = np.array(sorted(inc.edbs["edge"])) if inc.edbs["edge"] else (
        np.zeros((0, 5), np.int64))
    want, _ = Engine(compile_program(WIDE_REACH2), _cfg()).run(
        {"edge": rest})
    np.testing.assert_array_equal(snap["reach"], want["reach"])
