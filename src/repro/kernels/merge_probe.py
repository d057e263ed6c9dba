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

Both sides sorted => the build blocks wholly below probe block ``p`` are
a prefix ``[0, r_lo[p])``, each contributing its full size without a
compare, and those wholly above it a suffix ``[r_hi[p], nb)``,
contributing nothing. Only the band ``[r_lo[p], r_hi[p])`` does VPU
compare work.

Padding. Every operand the engine hands in is padded to a power-of-two
capacity with KEY_PAD (int64 max; for multi-word keys, KEY_PAD in every
word), and the wrapper pads both sides further to whole blocks. Left in
the band, a probe block of padding would overlap every padded build
block and compare against all of them, for ranks the callers discard.
So padding rows get their ranks in closed form, outside the kernel:
``lo`` = the number of padded build rows below KEY_PAD (the live build
rows) and ``hi`` = the padded build length — exactly what the compares
would give. The band's upper end is taken from each probe block's
largest non-padding key: an all-padding probe block has an empty band,
and the block where live rows end stops at the live build blocks. A
probe without padding does the compares it always did. Summed over the
probe blocks, the band of distinct live keys is at most (live probe
blocks + live build blocks) block pairs: the merge path's length.

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

TPU layout. The grid runs over probe blocks only. The wrapper computes
the band of each probe block from the block min/max keys (a
searchsorted over the ``nb`` block ends) and scalar-prefetches it
(``pltpu.PrefetchScalarGridSpec``): two int32 words per probe block, so
SMEM (1 MiB on v5e) holds the bounds of up to 2**26 probe rows. The
probe block's chunks arrive as VMEM blocks; the build chunks stay in
HBM, and the kernel walks its band in a loop, copying build block
``r + 1`` into one half of a two-slot VMEM buffer while it compares
block ``r`` in the other. The wholly-below prefix is added once, as
``r_lo * build_block``. Key chunks travel as 1-D int32 arrays whose
block length is a multiple of 1024 (XLA's tiling of a 1-D int32 array
on TPU), and every in-kernel reduction is int32 (the package turns x64
on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KEY_PAD = jnp.iinfo(jnp.int64).max


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
    """One probe block. ``refs`` holds the probe block's ``nchunks``
    int32 key chunks (VMEM), the whole build chunks (HBM), the (lo, hi)
    output blocks, then per chunk a two-slot VMEM buffer and its two DMA
    semaphores."""
    p_refs, b_refs = refs[:nchunks], refs[nchunks:2 * nchunks]
    lo_ref, hi_ref = refs[2 * nchunks:2 * nchunks + 2]
    bufs = refs[2 * nchunks + 2:3 * nchunks + 2]
    sems = refs[3 * nchunks + 2:]
    p = pl.program_id(0)
    r_lo, r_hi = rlo_ref[p], rhi_ref[p]

    def block(i):
        return pl.ds(pl.multiple_of(i * build_block, build_block),
                     build_block)

    def fetch(r, slot):
        return [pltpu.make_async_copy(b.at[block(r)], buf.at[block(slot)],
                                      sem.at[slot])
                for b, buf, sem in zip(b_refs, bufs, sems)]

    @pl.when(r_lo < r_hi)
    def _first():
        for copy in fetch(r_lo, jnp.int32(0)):
            copy.start()

    probe = [q[...][:, None] for q in p_refs]     # [probe_block, 1]

    def step(r, ranks):
        slot = jax.lax.rem(r - r_lo, jnp.int32(2))

        @pl.when(r + 1 < r_hi)
        def _next():
            for copy in fetch(r + 1, 1 - slot):
                copy.start()

        for copy in fetch(r, slot):
            copy.wait()
        lt, le = _chunk_lex_lt_le(
            [buf[block(slot)][None, :] for buf in bufs],  # [1, build_block]
            probe)
        lo, hi = ranks
        return lo + _count_true(lt), hi + _count_true(le)

    # every build row of the wholly-below prefix is below every probe key
    below = jnp.full(lo_ref.shape, r_lo * build_block, jnp.int32)
    lo, hi = jax.lax.fori_loop(r_lo, r_hi, step, (below, below))
    lo_ref[...] = lo
    hi_ref[...] = hi


def _count_true(mask):
    """Row-wise count of a [probe_block, build_block] mask, in int32
    (a bool sum would promote to int64 under x64)."""
    return mask.astype(jnp.int32).sum(axis=1, dtype=jnp.int32)


def _is_pad(keys):
    """Padding rows of [k] int64 keys or [k, W] int64 words (KEY_PAD in
    every word)."""
    pad = keys == KEY_PAD
    return pad if keys.ndim == 1 else jnp.all(pad, axis=1)


def _pad_to(keys, block: int):
    """``keys`` padded with KEY_PAD rows to a whole number of blocks (at
    least one)."""
    k = keys.shape[0]
    k_pad = pl.cdiv(max(k, 1), block) * block
    widths = ((0, k_pad - k),) + ((0, 0),) * (keys.ndim - 1)
    return jnp.pad(keys.astype(jnp.int64), widths,
                   constant_values=KEY_PAD)


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


def _block_bounds(build, probe, probe_block: int, build_block: int):
    """Per probe block, the band ``[r_lo, r_hi)`` of build blocks that
    needs compares, for block-padded sorted ``build`` / ``probe`` keys
    ([k] int64 or [k, W] int64 words): ``r_lo`` = #build blocks whose
    max is below the probe block's min, ``r_hi`` = #build blocks whose
    min is at or below the block's largest non-padding key. A probe
    block of padding alone gets ``r_hi = r_lo``."""
    nb = build.shape[0] // build_block
    nblk = probe.shape[0] // probe_block
    bblk = build.reshape((nb, build_block) + build.shape[1:])
    pblk = probe.reshape((nblk, probe_block) + probe.shape[1:])
    # rows sort ascending and padding sorts last, so a block's live
    # rows are its first ``live`` rows
    live = jnp.sum(~_is_pad(probe).reshape(nblk, probe_block), axis=1,
                   dtype=jnp.int32)
    top = pblk[jnp.arange(nblk), jnp.maximum(live - 1, 0)]
    r_lo = _block_rank(bblk[:, -1], pblk[:, 0], upper=False)
    r_lo = r_lo.astype(jnp.int32)
    r_hi = _block_rank(bblk[:, 0], top, upper=True).astype(jnp.int32)
    return r_lo, jnp.where(live > 0, r_hi, r_lo)


def _banded(build, probe, probe_block: int, build_block: int):
    """Both sides padded to whole blocks, and the probe blocks' bands."""
    build = _pad_to(build, build_block)
    probe = _pad_to(probe, probe_block)
    return build, probe, _block_bounds(build, probe, probe_block,
                                       build_block)


def band_blocks(build, probe, probe_block: int = 1024,
                build_block: int = 1024):
    """The block pairs a probe of sorted ``probe`` keys into sorted
    ``build`` keys ([k] int64 or [k, W] int64 words) compares: the sum
    of its bands (a traced int32 scalar), and the ``nP * nB`` pairs of
    the full block grid (a static int)."""
    build, probe, (r_lo, r_hi) = _banded(build, probe, probe_block,
                                         build_block)
    grid = (probe.shape[0] // probe_block) * (build.shape[0] // build_block)
    return jnp.sum(r_hi - r_lo, dtype=jnp.int32), grid


def _probe_call(probe_chunks, build_chunks, bounds, probe_block,
                build_block, interpret):
    """The probe-block grid shared by both probe entry points:
    scalar-prefetched bands, then the probe and build key chunks as 1-D
    int32 arrays (the build chunks left in HBM)."""
    n_pad = probe_chunks[0].shape[0]
    nchunks = len(probe_chunks)
    probe_spec = pl.BlockSpec((probe_block,), lambda p, *_: (p,))
    build_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_probe_kernel, build_block=build_block,
                          nchunks=nchunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_pad // probe_block,),
            in_specs=[probe_spec] * nchunks + [build_spec] * nchunks,
            out_specs=[probe_spec, probe_spec],
            scratch_shapes=(
                [pltpu.VMEM((2 * build_block,), jnp.int32)] * nchunks
                + [pltpu.SemaphoreType.DMA((2,))] * nchunks)),
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.int32)] * 2,
        interpret=interpret,
    )(*bounds, *probe_chunks, *build_chunks)


def _ranks(build, probe, split, probe_block, build_block, interpret):
    """(lo, hi) int32 ranks of every row of sorted ``probe`` keys in
    sorted ``build`` keys ([k] int64 or [k, W] int64 words; ``split``
    turns them into int32 chunks): the kernel's over the bands, padding
    rows' in closed form."""
    n = probe.shape[0]
    build, probe, bounds = _banded(build, probe, probe_block, build_block)
    lo, hi = _probe_call(split(probe), split(build), bounds,
                         probe_block, build_block, interpret)
    pad = _is_pad(probe)
    live_build = jnp.sum(~_is_pad(build), dtype=jnp.int32)
    lo = jnp.where(pad, live_build, lo)
    hi = jnp.where(pad, build.shape[0], hi)
    return lo[:n], hi[:n]


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
    """Returns (lo, hi) int32 ranks per probe key, in ``build_keys``
    padded with KEY_PAD to whole build blocks: a KEY_PAD probe's ``hi``
    counts that block padding too."""

    def split(k):
        # order-isomorphic (hi, lo) int32 pair for any non-negative
        # int64 key: hi = bits 32..62 (31 bits, fits non-negative
        # int32), lo = bits 0..31 shifted by -2**31 so the kernel's
        # signed lex compare ranks the 32-bit chunk correctly
        return (k >> 32).astype(jnp.int32), (
            (k & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)

    return _ranks(build_keys, probe_keys, split, probe_block,
                  build_block, interpret)


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
    contract (block min/max skip + band compares).

    PAD caveat (inherited from the probe kernel): for KEY_PAD rows of b
    the upper rank also counts a's block padding, pushing pos_b past
    m + n. Consumers scatter with drop mode — dead rows carry PAD data
    and identity payload, so landing in the tail and being dropped are
    byte-identical outcomes."""
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
    w = build_words.shape[1]
    assert probe_words.shape[1] == w
    nchunks = 2 * w

    def split(words):             # [k, W] int64 -> 2W int32 [k] chunks
        hi = (words >> 32).astype(jnp.int32)
        lo = ((words & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)
        # chunk order word0_hi, word0_lo, word1_hi, ... keeps the
        # chunk-wise lex order isomorphic to the word-wise lex order
        return [hi[:, c // 2] if c % 2 == 0 else lo[:, c // 2]
                for c in range(nchunks)]

    return _ranks(build_words, probe_words, split, probe_block,
                  build_block, interpret)
