"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp refs,
across shapes and dtypes."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


# -- segment_reduce -----------------------------------------------------------

@pytest.mark.parametrize("n,d,num_segments", [
    (64, 8, 16), (200, 16, 50), (1024, 128, 128),
    (513, 4, 100),                       # non-multiple of block
    (128, 8, 9000),                      # forces the tiled (large-N) path
    (2048, 8, 10000),
])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_segment_reduce_sweep(rng, n, d, num_segments, op):
    segs = np.sort(rng.integers(0, num_segments, size=n)).astype(np.int32)
    vals = rng.normal(size=(n, d)).astype(np.float32)
    got = ops.segment_reduce(
        jnp.asarray(vals), jnp.asarray(segs), num_segments, op,
        backend="interpret", rows_block=128, seg_tile=512)
    want = ref.segment_reduce_ref(
        jnp.asarray(vals), jnp.asarray(segs), num_segments, op)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_segment_reduce_out_of_range_dropped(rng):
    segs = jnp.array([0, 0, 1, 5, 99], jnp.int32)   # 99 out of range
    vals = jnp.ones((5, 4), jnp.float32)
    got = ops.segment_reduce(vals, segs, 8, "sum", backend="interpret")
    want = ref.segment_reduce_ref(vals, segs, 8, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_segment_reduce_1d(rng):
    segs = jnp.asarray(np.sort(rng.integers(0, 10, size=50)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=50), jnp.float32)
    got = ops.segment_reduce(vals, segs, 10, "sum", backend="interpret")
    want = ref.segment_reduce_ref(vals, segs, 10, "sum")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# -- merge_probe --------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(100, 50), (1024, 1024), (37, 2000),
                                 (5000, 333), (1, 1)])
def test_merge_probe_sweep(rng, m, n):
    build = np.sort(rng.integers(0, 1 << 40, size=m)).astype(np.int64)
    probe = np.sort(np.concatenate([
        rng.choice(build, size=min(n // 2, m)),
        rng.integers(0, 1 << 40, size=n - min(n // 2, m)),
    ])).astype(np.int64)
    lo, hi = ops.merge_probe_counts(
        jnp.asarray(build), jnp.asarray(probe), backend="interpret",
        probe_block=128, build_block=256)
    rlo, rhi = ref.merge_probe_ref(jnp.asarray(build), jnp.asarray(probe))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(rlo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(rhi))


def test_merge_probe_duplicates():
    build = jnp.asarray(np.array([2, 2, 2, 5, 5, 9], np.int64))
    probe = jnp.asarray(np.array([1, 2, 3, 5, 9, 10], np.int64))
    lo, hi = ops.merge_probe_counts(build, probe, backend="interpret",
                                    probe_block=8, build_block=8)
    assert (hi - lo).tolist() == [0, 3, 0, 2, 1, 0]


KEY_PAD = np.iinfo(np.int64).max
PROBE_BLOCK, BUILD_BLOCK = 128, 256


def _padded_keys(rng, rows, live, words):
    """``rows`` sorted keys whose last ``rows - live`` are KEY_PAD: [rows]
    int64 keys (``words`` 0) or [rows, words] int64 words, KEY_PAD in
    every word of a padding row. Few distinct values, so keys repeat."""
    if not words:
        keys = np.sort(rng.integers(0, 400, size=live))
        return np.concatenate([keys, np.full(rows - live, KEY_PAD)])
    keys = rng.integers(0, 20, size=(live, words))
    keys = keys[np.lexsort(keys.T[::-1])]
    return np.concatenate([keys, np.full((rows - live, words), KEY_PAD)])


def _ranks_ref(build, probe):
    if build.ndim == 1:
        return ref.merge_probe_ref(jnp.asarray(build), jnp.asarray(probe))
    return ref.merge_probe_multi_ref(jnp.asarray(build), jnp.asarray(probe))


def _probe(build, probe):
    if build.ndim == 1:
        return ops.merge_probe_counts(
            jnp.asarray(build), jnp.asarray(probe), backend="interpret",
            probe_block=PROBE_BLOCK, build_block=BUILD_BLOCK)
    return ops.merge_probe_multi(
        jnp.asarray(build), jnp.asarray(probe), backend="interpret",
        probe_block=PROBE_BLOCK, build_block=BUILD_BLOCK)


def _is_pad(keys):
    pad = keys == KEY_PAD
    return pad if keys.ndim == 1 else pad.all(axis=1)


# (probe rows, live probe rows): whole KEY_PAD blocks, a partly padded
# last block, no padding. The build (1000 rows, 700 live) has a KEY_PAD
# tail and is padded further to 4 blocks of 256.
@pytest.mark.parametrize("words", [0, 1, 3])
@pytest.mark.parametrize("n,n_live", [(1024, 200), (1000, 950), (640, 640)],
                         ids=["pad-blocks", "pad-tail", "no-pad"])
def test_merge_probe_padding_ranks_closed_form(rng, words, n, n_live):
    m, m_live = 1000, 700
    build = _padded_keys(rng, m, m_live, words)
    probe = _padded_keys(rng, n, n_live, words)
    lo, hi = map(np.asarray, _probe(build, probe))
    rlo, rhi = map(np.asarray, _ranks_ref(build, probe))
    live = ~_is_pad(probe)
    np.testing.assert_array_equal(lo[live], rlo[live])
    np.testing.assert_array_equal(hi[live], rhi[live])
    # padding rows: lo = the build rows below KEY_PAD, hi = the build as
    # the kernel pads it to whole blocks
    m_pad = -(-m // BUILD_BLOCK) * BUILD_BLOCK
    assert (lo[~live] == m_live).all()
    assert (hi[~live] == m_pad).all()


@pytest.mark.parametrize("words", [0, 2])
def test_merge_ranks_padded_tails(rng, words):
    """Merge positions of two arrangements with KEY_PAD tails: the live
    rows land where ``ref.merge_ranks_ref`` puts them, and no padding
    row lands on a live row's position."""
    m, m_live, n, n_live = 900, 600, 700, 300
    a = _padded_keys(rng, m, m_live, words)
    b = _padded_keys(rng, n, n_live, words)
    if words:
        pos_a, pos_b = ops.merge_ranks_multi(
            jnp.asarray(a), jnp.asarray(b), backend="interpret",
            probe_block=PROBE_BLOCK, build_block=BUILD_BLOCK)
        ra, rb = ref.merge_ranks_multi_ref(jnp.asarray(a), jnp.asarray(b))
    else:
        pos_a, pos_b = ops.merge_ranks(
            jnp.asarray(a), jnp.asarray(b), backend="interpret",
            probe_block=PROBE_BLOCK, build_block=BUILD_BLOCK)
        ra, rb = ref.merge_ranks_ref(jnp.asarray(a), jnp.asarray(b))
    pos_a, pos_b, ra, rb = map(np.asarray, (pos_a, pos_b, ra, rb))
    live_a, live_b = ~_is_pad(a), ~_is_pad(b)
    np.testing.assert_array_equal(pos_a[live_a], ra[live_a])
    np.testing.assert_array_equal(pos_b[live_b], rb[live_b])
    live_pos = np.concatenate([pos_a[live_a], pos_b[live_b]])
    assert np.array_equal(np.sort(live_pos), np.arange(m_live + n_live))
    dead_pos = np.concatenate([pos_a[~live_a], pos_b[~live_b]])
    assert not np.isin(dead_pos, live_pos).any()


def test_probe_band_walks_live_blocks_only(rng):
    """A probe 90% KEY_PAD into a build 90% KEY_PAD compares at most
    (live probe blocks + live build blocks) block pairs, and an observed
    dispatch counts exactly those and the whole grid."""
    from repro.engine import observe as O
    from repro.engine.backend import PallasDispatch
    from repro.kernels import merge_probe as MP

    block = 1024                    # the dispatch's block sizes
    m = n = 20 * block
    live = m // 10
    draw = rng.choice(1 << 40, size=2 * live, replace=False)
    build = np.concatenate([np.sort(draw[:live]), np.full(m - live, KEY_PAD)])
    probe = np.concatenate([np.sort(draw[live:]), np.full(n - live, KEY_PAD)])
    r_lo, r_hi = MP._block_bounds(jnp.asarray(build), jnp.asarray(probe),
                                  block, block)
    compared = int(np.sum(np.asarray(r_hi) - np.asarray(r_lo)))
    live_blocks = 2 * (-(-live // block))
    assert 0 < compared <= live_blocks
    grid = (n // block) * (m // block)

    obs = O.Observation()
    lo, hi = PallasDispatch(interpret=True, observe=obs).probe(
        jnp.asarray(build), jnp.asarray(probe))
    jax.effects_barrier()
    assert obs.registry.get("kernels.probe.compared_blocks") == compared
    assert obs.registry.get("kernels.probe.grid_blocks") == grid
    rlo, rhi = ref.merge_probe_ref(jnp.asarray(build), jnp.asarray(probe))
    np.testing.assert_array_equal(np.asarray(lo)[:live],
                                  np.asarray(rlo)[:live])
    np.testing.assert_array_equal(np.asarray(hi)[:live],
                                  np.asarray(rhi)[:live])


def test_probe_counter_tally_loses_no_update():
    """Shards call the counter back from threads of their own: many
    threads adding at once, with thread switches forced often, lose no
    count."""
    import sys
    import threading

    from repro.engine import observe as O
    from repro.engine.backend import PallasDispatch

    obs = O.Observation()
    dispatch = PallasDispatch(interpret=True, observe=obs)
    threads, adds = 32, 2000

    def work():
        for _ in range(adds):
            dispatch._tally(3, np.int32(1))

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in pool)
    assert obs.registry.get("kernels.probe.compared_blocks") == threads * adds
    assert obs.registry.get("kernels.probe.grid_blocks") == 3 * threads * adds


def test_probe_counter_compiles_only_when_observed():
    """The block counter's host callback is in an observed dispatch's
    program and in no other."""
    from repro.engine import observe as O
    from repro.engine.backend import PallasDispatch

    keys = jnp.arange(2048, dtype=jnp.int64)
    plain = PallasDispatch(interpret=True)
    assert plain.observed(None) is plain

    def hlo(dispatch):
        return jax.jit(dispatch.probe).lower(keys, keys).as_text()

    assert "callback" not in hlo(plain)
    assert "callback" in hlo(plain.observed(O.Observation()))


# -- fm_interaction -----------------------------------------------------------

@pytest.mark.parametrize("b,f,k", [(32, 39, 10), (1000, 39, 10),
                                   (4096, 26, 16), (7, 13, 4)])
def test_fm_interaction_sweep(rng, b, f, k):
    x = rng.normal(size=(b, f)).astype(np.float32)
    v = rng.normal(size=(f, k)).astype(np.float32)
    got = ops.fm_interaction(jnp.asarray(x), jnp.asarray(v),
                             backend="interpret", batch_block=256)
    want = ref.fm_interaction_ref(jnp.asarray(x), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_fm_matches_bruteforce(rng):
    x = rng.normal(size=(4, 6)).astype(np.float32)
    v = rng.normal(size=(6, 3)).astype(np.float32)
    brute = np.zeros(4)
    for i in range(6):
        for j in range(i + 1, 6):
            brute += (v[i] @ v[j]) * x[:, i] * x[:, j]
    got = ops.fm_interaction(jnp.asarray(x), jnp.asarray(v),
                             backend="interpret", batch_block=8)
    np.testing.assert_allclose(np.asarray(got), brute, rtol=1e-4,
                               atol=1e-5)


# -- flash_attention ----------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,skv,d", [
    (1, 4, 4, 128, 128, 64),        # MHA square
    (2, 8, 2, 128, 128, 64),        # GQA 4:1
    (1, 4, 1, 64, 256, 64),         # MQA, sq < skv (chunked prefill)
    (1, 16, 8, 256, 256, 32),       # GQA 2:1
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, b, hq, hkv, sq, skv, d, causal, dtype):
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, backend="interpret",
                              q_block=64, kv_block=64)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


# -- flash_decode -------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,S,d", [
    (2, 4, 4, 512, 64), (1, 8, 2, 1024, 64), (3, 16, 8, 256, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(rng, b, hq, hkv, S, d, dtype):
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, S, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, S, d)), dtype)
    kv_len = jnp.asarray(rng.integers(1, S, size=(b,)), jnp.int32)
    got = ops.flash_decode(q, k, v, kv_len, backend="interpret",
                           kv_block=128)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_decode_full_cache(rng):
    q = jnp.asarray(rng.normal(size=(1, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    got = ops.flash_decode(q, k, v, 256, backend="interpret")
    want = ref.decode_attention_ref(q, k, v, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
