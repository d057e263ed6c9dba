"""Blocked sort-merge probe Pallas kernel — the count/locate phase of the
engine's join (DD's ``join_core`` on arrangements, adapted to TPU).

Problem: given build keys B (sorted, m) and probe keys P (sorted, n),
compute for every probe key its lower/upper bound rank in B. The engine
then turns ranks into match counts + a bounded expand (relops.join).

GPU engines binary-search per thread; TPUs want regular, vectorized
data flow instead of data-dependent loops. We compute *ranks by guarded
block compares* (a merge-path variant):

    lo[p] = #{ j : B[j] <  P[p] } = sum over build blocks of a
            [probe_block x build_block] comparison reduction

Both sides sorted => a build block whose min exceeds the probe block's
max contributes nothing (skip via ``pl.when``); one whose max is below
the probe block's min contributes its full size (cheap add, no compare).
Only the O(1) diagonal band of block pairs does real VPU compare work,
so total compare volume is O(n * build_block), like a classic merge.

TPU has no native int64: packed engine keys (up to 63 bits — 3-column
packs reach bit 62; KEY_PAD is 2**63 - 1) are split into an int32 pair
(hi = bits 32..62; lo = bits 0..31 biased by -2**31 so signed order
matches unsigned chunk order) and compared lexicographically in-kernel
with plain signed compares.

``merge_probe_multi_pallas`` runs the same kernel on the engine's
multi-word lexicographic keys (relation.pack_key_words): a key of W
int64 words becomes 2W int32 chunks, and the in-kernel compare folds
over the chunks (a static Python loop, unrolled at trace time). A
single-word key is the W = 1 case: two chunks. The engine keeps routing
narrow keys through ``merge_probe_pallas``, whose block bounds come
from a plain searchsorted on the int64 keys.

TPU layout. The skip decision needs each block's min/max, which are
scalars: the kernel reads them from SMEM. Both sides are sorted, so the
build blocks wholly below probe block ``p`` are a prefix ``[0, r_lo[p])``
and those wholly above it a suffix ``[r_hi[p], nb)``. The wrapper
computes the two bounds per probe block from the block min/max keys
(a searchsorted over the ``nb`` block ends) and scalar-prefetches them
(``pltpu.PrefetchScalarGridSpec``): two int32 words per probe block, so
SMEM (1 MiB on v5e) holds the bounds of up to 2**26 probe rows. Key
chunks travel as 1-D int32 blocks whose length is a multiple of 1024
(XLA's tiling of a 1-D int32 array on TPU), and every in-kernel
reduction is int32 (the package turns x64 on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_lex_lt_le(a_chunks, b_chunks):
    """Fold a lexicographic (lt, le) compare over a static sequence of
    int32 chunk arrays (broadcastable shapes)."""
    lt = None
    eq = None
    for a, b in zip(a_chunks, b_chunks):
        if lt is None:
            lt = a < b
            eq = a == b
        else:
            lt = lt | (eq & (a < b))
            eq = eq & (a == b)
    return lt, lt | eq


def _probe_kernel(rlo_ref, rhi_ref, *refs, build_block: int, nchunks: int):
    """One (probe block, build block) step. ``refs`` holds the probe
    block's ``nchunks`` int32 key chunks, the build block's, then the
    (lo, hi) output blocks."""
    p_refs, b_refs = refs[:nchunks], refs[nchunks:2 * nchunks]
    lo_ref, hi_ref = refs[2 * nchunks:]
    p = pl.program_id(0)
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    below_all = r < rlo_ref[p]
    above_all = r >= rhi_ref[p]

    @pl.when(below_all)
    def _full():
        # entire build block strictly below every probe key
        lo_ref[...] += build_block
        hi_ref[...] += build_block

    @pl.when(jnp.logical_not(below_all | above_all))
    def _compare():
        lt, le = _chunk_lex_lt_le(
            [b[...][None, :] for b in b_refs],   # [1, build_block]
            [q[...][:, None] for q in p_refs])   # [probe_block, 1]
        lo_ref[...] += _count_true(lt)
        hi_ref[...] += _count_true(le)


def _count_true(mask):
    """Row-wise count of a [probe_block, build_block] mask, in int32
    (a bool sum would promote to int64 under x64)."""
    return mask.astype(jnp.int32).sum(axis=1, dtype=jnp.int32)


def _block_rank(build, probe, upper: bool):
    """Per probe row, the number of ``build`` rows below it (``upper``:
    at or below it). ``build`` is sorted: [k] int64 keys, or [k, W]
    int64 words in lexicographic order, searched by a vectorized binary
    search (``bit_length(k)`` unrolled steps)."""
    if build.ndim == 1:
        return jnp.searchsorted(build, probe,
                                side="right" if upper else "left")
    k = build.shape[0]
    lo = jnp.zeros(probe.shape[:1], jnp.int32)
    hi = jnp.full(probe.shape[:1], k, jnp.int32)
    for _ in range(k.bit_length()):
        mid = (lo + hi) >> 1
        rows = jnp.take(build, mid, axis=0, mode="clip")
        lt, le = _chunk_lex_lt_le(list(rows.T), list(probe.T))
        active, below = lo < hi, le if upper else lt
        lo = jnp.where(active & below, mid + 1, lo)
        hi = jnp.where(active & ~below, mid, hi)
    return lo


def _block_bounds(build_min, build_max, probe_min, probe_max):
    """Per probe block, the build-block range ``[r_lo, r_hi)`` that needs
    compares: ``r_lo`` = #build blocks whose max is below the probe
    block's min, ``r_hi`` = #build blocks whose min is at or below its
    max."""
    r_lo = _block_rank(build_max, probe_min, upper=False)
    r_hi = _block_rank(build_min, probe_max, upper=True)
    return r_lo.astype(jnp.int32), r_hi.astype(jnp.int32)


def _probe_call(probe_chunks, build_chunks, bounds, probe_block,
                build_block, interpret):
    """The block-pair grid shared by both probe entry points:
    scalar-prefetched build-block bounds, then the probe and build key
    chunks as 1-D int32 arrays."""
    n_pad = probe_chunks[0].shape[0]
    nb = build_chunks[0].shape[0] // build_block
    nchunks = len(probe_chunks)
    probe_spec = pl.BlockSpec((probe_block,), lambda p, r, *_: (p,))
    build_spec = pl.BlockSpec((build_block,), lambda p, r, *_: (r,))
    return pl.pallas_call(
        functools.partial(_probe_kernel, build_block=build_block,
                          nchunks=nchunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_pad // probe_block, nb),
            in_specs=[probe_spec] * nchunks + [build_spec] * nchunks,
            out_specs=[probe_spec, probe_spec]),
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.int32)] * 2,
        interpret=interpret,
    )(*bounds, *probe_chunks, *build_chunks)


@functools.partial(
    jax.jit,
    static_argnames=("probe_block", "build_block", "interpret"))
def merge_probe_pallas(
    build_keys: jax.Array,    # [m] int64 sorted ascending (pad: int64 max)
    probe_keys: jax.Array,    # [n] int64 sorted ascending
    probe_block: int = 1024,
    build_block: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (lo, hi) int32 ranks per probe key."""
    m, n = build_keys.shape[0], probe_keys.shape[0]
    MAXK = jnp.iinfo(jnp.int64).max

    def split(k):
        # order-isomorphic (hi, lo) int32 pair for any non-negative
        # int64 key: hi = bits 32..62 (31 bits, fits non-negative
        # int32), lo = bits 0..31 shifted by -2**31 so the kernel's
        # signed lex compare ranks the 32-bit chunk correctly
        k = k.astype(jnp.int64)
        return (k >> 32).astype(jnp.int32), (
            (k & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)

    m_pad = pl.cdiv(max(m, 1), build_block) * build_block
    n_pad = pl.cdiv(max(n, 1), probe_block) * probe_block
    build_keys = jnp.pad(build_keys.astype(jnp.int64), (0, m_pad - m),
                         constant_values=MAXK)
    probe_keys = jnp.pad(probe_keys.astype(jnp.int64), (0, n_pad - n),
                         constant_values=MAXK)
    nb = m_pad // build_block
    bblk = build_keys.reshape(nb, build_block)
    pblk = probe_keys.reshape(n_pad // probe_block, probe_block)
    bounds = _block_bounds(bblk[:, 0], bblk[:, -1], pblk[:, 0],
                           pblk[:, -1])
    lo, hi = _probe_call(split(probe_keys), split(build_keys), bounds,
                         probe_block, build_block, interpret)
    # padded build rows carry MAXK; probes that are real never count them
    # as < or <= unless the probe itself is MAXK (a padded probe) —
    # those rows are sliced off here.
    return lo[:n], hi[:n]


# -- merge ranks (incremental arrangement maintenance) -----------------------

def merge_ranks_pallas(a_keys: jax.Array, b_keys: jax.Array,
                       probe_block: int = 1024, build_block: int = 1024,
                       interpret: bool = False):
    """Merge-path output positions for a stable two-pointer merge of two
    sorted key sequences (``a`` wins ties) — the Pallas counterpart of
    ``ref.merge_ranks_ref``, reusing the blocked merge-path partitioner
    of ``merge_probe_pallas`` for both rank passes: pos_a needs a's
    lower rank in b, pos_b needs b's upper rank in a, and both sides
    are sorted arrangements, so each pass is exactly the probe kernel's
    contract (block min/max skip + diagonal-band compares).

    PAD caveat (inherited from the probe kernel): for KEY_PAD rows of b
    the upper rank may additionally count a's block padding, pushing
    pos_b past m + n. Consumers scatter with drop mode — dead rows
    carry PAD data and identity payload, so landing in the tail and
    being dropped are byte-identical outcomes."""
    m, n = a_keys.shape[0], b_keys.shape[0]
    lo_a, _ = merge_probe_pallas(b_keys, a_keys,
                                 probe_block=probe_block,
                                 build_block=build_block,
                                 interpret=interpret)
    _, hi_b = merge_probe_pallas(a_keys, b_keys,
                                 probe_block=probe_block,
                                 build_block=build_block,
                                 interpret=interpret)
    pos_a = jnp.arange(m, dtype=jnp.int32) + lo_a
    pos_b = jnp.arange(n, dtype=jnp.int32) + hi_b
    return pos_a, pos_b


def merge_ranks_multi_pallas(a_words: jax.Array, b_words: jax.Array,
                             probe_block: int = 1024,
                             build_block: int = 1024,
                             interpret: bool = False):
    """Multi-word ``merge_ranks_pallas``: [m, W] / [n, W] int64 key
    vectors through the chunked merge-path kernel."""
    m, n = a_words.shape[0], b_words.shape[0]
    lo_a, _ = merge_probe_multi_pallas(b_words, a_words,
                                       probe_block=probe_block,
                                       build_block=build_block,
                                       interpret=interpret)
    _, hi_b = merge_probe_multi_pallas(a_words, b_words,
                                       probe_block=probe_block,
                                       build_block=build_block,
                                       interpret=interpret)
    pos_a = jnp.arange(m, dtype=jnp.int32) + lo_a
    pos_b = jnp.arange(n, dtype=jnp.int32) + hi_b
    return pos_a, pos_b


# -- multi-word keys ---------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("probe_block", "build_block", "interpret"))
def merge_probe_multi_pallas(
    build_words: jax.Array,   # [m, W] int64, lexicographically ascending
    probe_words: jax.Array,   # [n, W] int64, lexicographically ascending
    probe_block: int = 1024,
    build_block: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) int32 ranks per probe key vector — the multi-word
    variant of ``merge_probe_pallas``; pad rows are KEY_PAD in every
    word (relation.pack_key_words) and sort last."""
    m, w = build_words.shape
    n = probe_words.shape[0]
    assert probe_words.shape[1] == w
    MAXK = jnp.iinfo(jnp.int64).max
    nchunks = 2 * w

    def split(words):             # [k, W] int64 -> 2W int32 [k] chunks
        hi = (words >> 32).astype(jnp.int32)
        lo = ((words & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)
        # chunk order word0_hi, word0_lo, word1_hi, ... keeps the
        # chunk-wise lex order isomorphic to the word-wise lex order
        return [hi[:, c // 2] if c % 2 == 0 else lo[:, c // 2]
                for c in range(nchunks)]

    m_pad = pl.cdiv(max(m, 1), build_block) * build_block
    n_pad = pl.cdiv(max(n, 1), probe_block) * probe_block
    build_words = jnp.pad(build_words.astype(jnp.int64),
                          ((0, m_pad - m), (0, 0)), constant_values=MAXK)
    probe_words = jnp.pad(probe_words.astype(jnp.int64),
                          ((0, n_pad - n), (0, 0)), constant_values=MAXK)
    nb = m_pad // build_block
    bblk = build_words.reshape(nb, build_block, w)
    pblk = probe_words.reshape(n_pad // probe_block, probe_block, w)
    bounds = _block_bounds(bblk[:, 0], bblk[:, -1], pblk[:, 0],
                           pblk[:, -1])
    lo, hi = _probe_call(split(probe_words), split(build_words), bounds,
                         probe_block, build_block, interpret)
    # padded build rows carry MAXK in every word; real probes never
    # count them. Padded probes are sliced off here (their hi may count
    # block padding — same dead-probe contract as the 1-D kernel).
    return lo[:n], hi[:n]
