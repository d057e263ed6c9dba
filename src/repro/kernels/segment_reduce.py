"""Sorted-segment reduction Pallas kernel (TPU target).

The workhorse of three subsystems: Datalog grouped aggregation
(engine/relops.reduce_groups), GNN message aggregation (messages sorted
by destination node), and recsys embedding-bag pooling.

TPU adaptation of the GPU scatter-reduce idiom: TPUs have no atomics, so
we require ``seg_ids`` sorted ascending — which the engine guarantees
(relations are arrangements) and the GNN layer establishes once per graph
by pre-sorting edges by destination. Two strategies:

* ``resident`` (num_segments small enough for VMEM): grid walks row
  blocks sequentially; each block folds its rows into the full segment
  axis kept resident in VMEM. Output revisiting across the sequential
  grid accumulates boundary segments for free.
* ``tiled`` (large num_segments): 2-D grid (segment tiles x row blocks);
  each step accumulates the overlap of its segment tile with its row
  block. Sortedness makes most (tile, block) pairs disjoint: a
  precomputed per-row-block [min_seg, max_seg] range, scalar-prefetched
  into SMEM, lets the kernel skip non-overlapping steps with
  ``pl.when`` (compute-skip; the grid itself is static, as TPU
  requires).

Both fold a row block into a segment range the same way: a
[rows_block, chunk] one-hot compare of segment ids against a lane iota,
a select of the values (or the op's identity), and a reduction over the
rows, one chunk of ``_SEG_CHUNK`` segments at a time so the temporaries
stay near 2 MiB of VMEM. Sums run on the VPU in the accumulator dtype:
int32 sums are exact and wrap like ``jax.ops.segment_sum`` (Mosaic has
no int32 matmul). Values travel as 1-D blocks whose length is a multiple
of 1024 (XLA's tiling of a 1-D 32-bit array on TPU); ``d > 1`` columns
are vmapped over the 1-D kernel (the engine passes one column).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

RESIDENT_MAX_SEGMENTS = 8192
# segments compared per step of the row-block fold (VMEM temporaries
# of rows_block x _SEG_CHUNK)
_SEG_CHUNK = 512


def _neutral(op: str, dtype):
    """Identity element per (op, accumulator dtype). Integer min/max use
    the iinfo extremes — identical to jax.ops.segment_min/max, so the
    engine's integer aggregates are bit-equal across backends."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if op == "min" else info.min, dtype)
    return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)


def _fold_rows(out_ref, seg, vals, base, op: str):
    """Fold one row block (segment ids ``seg``, values ``vals``, both
    [rows_block]) into ``out_ref`` [width], whose entry ``i`` is segment
    ``base + i``."""
    width = out_ref.shape[0]
    chunk = min(width, _SEG_CHUNK)
    neutral = _neutral(op, vals.dtype)
    col_seg, col_val = seg[:, None], vals[:, None]         # [rows, 1]
    for c0 in range(0, width, chunk):
        c1 = min(c0 + chunk, width)
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, c1 - c0), 1) + (
            base + c0)
        sel = jnp.where(col_seg == ids, col_val, neutral)  # [rows, chunk]
        cur = out_ref[c0:c1]
        if op == "sum":
            out_ref[c0:c1] = cur + sel.sum(axis=0, dtype=cur.dtype)
        elif op == "min":
            out_ref[c0:c1] = jnp.minimum(cur, sel.min(axis=0))
        else:
            out_ref[c0:c1] = jnp.maximum(cur, sel.max(axis=0))


def _resident_kernel(seg_ref, val_ref, out_ref, *, op: str):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(
            out_ref, _neutral(op, out_ref.dtype))

    _fold_rows(out_ref, seg_ref[...], val_ref[...], 0, op)


def _tiled_kernel(lo_ref, hi_ref, seg_ref, val_ref, out_ref, *, op: str,
                  seg_tile: int):
    s = pl.program_id(0)
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.full_like(
            out_ref, _neutral(op, out_ref.dtype))

    base = s * seg_tile
    overlap = (lo_ref[r] < base + seg_tile) & (hi_ref[r] >= base)

    @pl.when(overlap)
    def _work():
        _fold_rows(out_ref, seg_ref[...], val_ref[...], base, op)


def _per_column(fn, values):
    """Apply a 1-D kernel call to each column of ``values`` [n, d]."""
    if values.shape[1] == 1:
        return fn(values[:, 0])[:, None]
    return jax.vmap(fn, in_axes=1, out_axes=1)(values)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "op", "rows_block", "seg_tile",
                     "interpret"))
def segment_reduce_pallas(
    values: jax.Array,         # [n, d]
    seg_ids: jax.Array,        # [n] int32 sorted ascending; out-of-range
                               # (negative or >= num_segments) = dropped
    num_segments: int,
    op: str = "sum",
    rows_block: int = 1024,
    seg_tile: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    n, d = values.shape
    rows_block = min(rows_block, max(8, pl.next_power_of_2(n)))
    n_pad = pl.cdiv(n, rows_block) * rows_block
    # integer inputs accumulate in int32 (exact; the float32 path
    # rounds above 2**24), everything else in float32
    acc_dtype = (jnp.int32 if jnp.issubdtype(values.dtype, jnp.integer)
                 else jnp.float32)
    values = values.astype(acc_dtype)
    if n_pad != n:
        values = jnp.pad(values, ((0, n_pad - n), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, n_pad - n), constant_values=-1)
    seg_ids = seg_ids.astype(jnp.int32)

    if num_segments <= RESIDENT_MAX_SEGMENTS:
        segs_p = max(128, pl.next_power_of_2(num_segments + 1))
        # out-of-range rows -> sacrificial last segment
        ids = jnp.where((seg_ids < 0) | (seg_ids >= num_segments),
                        segs_p - 1, seg_ids)
        call = pl.pallas_call(
            functools.partial(_resident_kernel, op=op),
            grid=(n_pad // rows_block,),
            in_specs=[
                pl.BlockSpec((rows_block,), lambda i: (i,)),
                pl.BlockSpec((rows_block,), lambda i: (i,)),
            ],
            # int32 block index: a literal 0 would trace as int64 under
            # x64, which Mosaic cannot return from an index map
            out_specs=pl.BlockSpec((segs_p,),
                                   lambda i: (jnp.zeros((), jnp.int32),)),
            out_shape=jax.ShapeDtypeStruct((segs_p,), acc_dtype),
            interpret=interpret,
        )
        return _per_column(lambda v: call(ids, v), values)[:num_segments]

    segs_p = pl.cdiv(num_segments, seg_tile) * seg_tile + seg_tile
    ids = jnp.where((seg_ids < 0) | (seg_ids >= num_segments),
                    segs_p - 1, seg_ids)
    nblocks = n_pad // rows_block
    blk = ids.reshape(nblocks, rows_block)
    blk_lo = blk.min(axis=1).astype(jnp.int32)
    blk_hi = jnp.where(
        (blk < segs_p - 1).any(axis=1),
        jnp.where(blk < segs_p - 1, blk, -1).max(axis=1), -1
    ).astype(jnp.int32)
    call = pl.pallas_call(
        functools.partial(_tiled_kernel, op=op, seg_tile=seg_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(segs_p // seg_tile, nblocks),
            in_specs=[
                pl.BlockSpec((rows_block,), lambda s, r, *_: (r,)),
                pl.BlockSpec((rows_block,), lambda s, r, *_: (r,)),
            ],
            out_specs=pl.BlockSpec((seg_tile,), lambda s, r, *_: (s,))),
        out_shape=jax.ShapeDtypeStruct((segs_p,), acc_dtype),
        interpret=interpret,
    )
    return _per_column(lambda v: call(blk_lo, blk_hi, ids, v),
                       values)[:num_segments]
