"""Public jit'd wrappers for the Pallas kernels.

Each op takes ``backend=``:
  "pallas"     — compiled Pallas kernel (TPU deployment path)
  "interpret"  — Pallas kernel body interpreted on CPU (how CPU tests
                 validate the kernels)
  "xla"        — the pure-jnp reference (also the dry-run lowering path,
                 so cost_analysis reflects XLA collectives/fusions)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fm_interaction import fm_interaction_pallas
from repro.kernels.flash_attention import (
    flash_attention_pallas, flash_decode_pallas,
)
from repro.kernels.merge_probe import (
    merge_probe_multi_pallas, merge_probe_pallas,
    merge_ranks_multi_pallas, merge_ranks_pallas,
)
from repro.kernels.segment_reduce import segment_reduce_pallas

DEFAULT_BACKEND = "xla"


def _resolve(backend):
    return backend or DEFAULT_BACKEND


def segment_reduce(values, seg_ids, num_segments, op="sum", backend=None,
                   **kw):
    backend = _resolve(backend)
    if backend == "xla":
        return ref.segment_reduce_ref(values, seg_ids, num_segments, op)
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    out = segment_reduce_pallas(
        values, seg_ids, num_segments, op,
        interpret=(backend == "interpret"), **kw)
    out = out.astype(values.dtype)
    return out[:, 0] if squeeze else out


def merge_probe_counts(build_keys, probe_keys, backend=None, **kw):
    backend = _resolve(backend)
    if backend == "xla":
        return ref.merge_probe_ref(build_keys, probe_keys)
    return merge_probe_pallas(
        build_keys, probe_keys, interpret=(backend == "interpret"), **kw)


def merge_probe_multi(build_words, probe_words, backend=None, **kw):
    """Multi-word variant of ``merge_probe_counts``: [m, W] / [n, W]
    int64 lexicographic key vectors (relation.pack_key_words)."""
    backend = _resolve(backend)
    if backend == "xla":
        return ref.merge_probe_multi_ref(build_words, probe_words)
    return merge_probe_multi_pallas(
        build_words, probe_words, interpret=(backend == "interpret"), **kw)


def merge_ranks(a_keys, b_keys, backend=None, **kw):
    """Stable two-pointer merge positions of two sorted int64 key
    sequences (incremental arrangement maintenance; see
    ``ref.merge_ranks_ref`` for the rank formulation)."""
    backend = _resolve(backend)
    if backend == "xla":
        return ref.merge_ranks_ref(a_keys, b_keys)
    return merge_ranks_pallas(
        a_keys, b_keys, interpret=(backend == "interpret"), **kw)


def merge_ranks_multi(a_words, b_words, backend=None, **kw):
    """Multi-word variant of ``merge_ranks``: [m, W] / [n, W] int64
    lexicographic key vectors (relation.pack_key_words)."""
    backend = _resolve(backend)
    if backend == "xla":
        return ref.merge_ranks_multi_ref(a_words, b_words)
    return merge_ranks_multi_pallas(
        a_words, b_words, interpret=(backend == "interpret"), **kw)


def expand_indices(offsets, out_cap, backend=None):
    """The join's bounded expand (repeat-by-counts). jnp reference on
    every backend for now — a dedicated Pallas expand kernel plugs in
    behind this same entry point later (ROADMAP 'Kernel-dispatch
    seam')."""
    del backend  # single implementation today; seam kept stable
    return ref.expand_indices_ref(offsets, out_cap)


def fm_interaction(x, v, backend=None, **kw):
    backend = _resolve(backend)
    if backend == "xla":
        return ref.fm_interaction_ref(x, v)
    return fm_interaction_pallas(
        x, v, interpret=(backend == "interpret"), **kw).astype(x.dtype)


# above this sequence length the XLA path switches to blockwise online-
# softmax attention (never materializes [S, S] scores)
XLA_BLOCKWISE_THRESHOLD = 4096


def flash_attention(q, k, v, causal=True, backend=None, **kw):
    backend = _resolve(backend)
    if backend == "xla":
        if k.shape[2] >= XLA_BLOCKWISE_THRESHOLD:
            return ref.blockwise_attention(q, k, v, causal=causal)
        return ref.attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(
        q, k, v, causal=causal, interpret=(backend == "interpret"), **kw)


def flash_decode(q, k, v, kv_len, backend=None, **kw):
    backend = _resolve(backend)
    if backend == "xla":
        if isinstance(kv_len, int):
            kv_len_arr = kv_len
        else:
            kv_len_arr = kv_len
        return ref.decode_attention_ref(q, k, v, kv_len_arr)
    if isinstance(kv_len, int):
        kv_len = jnp.full((q.shape[0],), kv_len, jnp.int32)
    return flash_decode_pallas(
        q, k, v, kv_len, interpret=(backend == "interpret"), **kw)
