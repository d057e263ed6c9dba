"""Pallas TPU kernels for FlowLog-JAX's compute hot-spots.

Each kernel ships three layers:
  <name>.py — pl.pallas_call body + BlockSpec VMEM tiling (TPU target,
              validated with interpret=True on CPU; the engine kernels
              are compiled for a described v5e by
              tests/test_tpu_compile.py)
  ops.py    — jit'd public wrappers with shape plumbing + fallback
  ref.py    — pure-jnp oracles the tests assert against

Kernels:
  segment_reduce  — sorted-segment sum/min/max. Serves Datalog grouped
                    aggregation, GNN message aggregation (the
                    jax.ops.segment_sum hot path), and recsys
                    embedding-bag reduction.
  merge_probe     — blocked binary search of probe keys into a sorted
                    build array: the count/locate phase of the engine's
                    sort-merge join (DD's arrangement probe on TPU).
  fm_interaction  — factorization-machine 2-way interaction via the
                    O(nk) sum-square trick, fused over batch blocks.
  flash_attention — blocked online-softmax attention (causal/full, GQA)
                    for the LM architectures' train/prefill path.
  flash_decode    — split-KV decode attention for 32k..512k contexts.

The engine backend seam
-----------------------
The Datalog engine consumes ``segment_reduce`` and
``merge_probe_counts`` through the kernel-dispatch layer in
``repro.engine.backend`` (selected by ``EngineConfig.kernel_backend``:
"auto" | "pallas" | "pallas-interpret" | "jnp"), so these two kernels
ARE the engine's
physical execution backend on TPU rather than standalone demos:

  merge_probe_counts — the count/locate phase of ``relops.join``
                       (both sides are arrangements, so build and probe
                       key arrays arrive sorted with KEY_PAD tails),
                       the lattice lookup of ``relops.merge_with_delta``
                       (lo rank only), and — via the sort-and-scatter
                       wrapper in ``relops.membership`` — semijoin/
                       antijoin/difference. Packed row keys (up to 63 bits;
                       3-column packs reach bit 62) split into an
                       order-isomorphic int32 pair in-kernel; KEY_PAD
                       maps to the max pair, so dead rows sort last on
                       both sides.
  merge_probe_multi  — the same probe for multi-word lexicographic keys
                       (wide relations, >= 4 key columns;
                       relation.pack_key_words): W int64 words become
                       2W int32 chunks, compared by a static in-kernel
                       fold. Narrow keys keep the single-word kernel.
  segment_reduce     — the sorted-segment aggregation behind
                       ``relops.reduce_groups`` (Datalog COUNT/SUM/
                       MIN/MAX) and the duplicate-combine of
                       ``relops.dedupe`` (valued semirings). Integer
                       columns accumulate natively in
                       int32 — no float32 rounding; overflow past
                       2**31 - 1 wraps exactly like jax.ops.segment_sum
                       — with the same empty-segment identities, so jnp
                       and Pallas backends emit byte-identical
                       relations (tests/test_backend_equivalence.py).

  merge_ranks        — output positions of a stable two-pointer merge
                       of two sorted key sequences (plus the
                       ``merge_ranks_multi`` word-vector variant):
                       incremental arrangement maintenance behind
                       ``relops.merge_sorted`` — the semi-naive
                       frontier step merges the sorted ``full`` with
                       the small sorted ``delta`` by rank instead of
                       concat + full re-sort. The Pallas path reuses
                       the merge-path probe kernel for both rank
                       passes (one lower-rank, one upper-rank).
  expand_indices     — the join's bounded expand behind
                       ``KernelDispatch.expand``: jnp reference on
                       every backend today; a dedicated Pallas expand
                       kernel plugs in behind the same entry point.

Still jnp-only (future kernels plug into the same dispatch seam):
the Pallas body for ``expand_indices`` and a fused dedupe-compare
kernel.
"""
from repro.kernels.ops import (
    segment_reduce, merge_probe_counts, merge_probe_multi,
    merge_ranks, merge_ranks_multi, expand_indices,
    fm_interaction, flash_attention, flash_decode,
)

__all__ = [
    "segment_reduce", "merge_probe_counts", "merge_probe_multi",
    "merge_ranks", "merge_ranks_multi", "expand_indices",
    "fm_interaction", "flash_attention", "flash_decode",
]
