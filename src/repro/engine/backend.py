"""Kernel-dispatch layer — the seam between the engine's logical
operators (relops.py) and their physical implementations.

FlowLog's logical/physical split (paper Sec. 2) says the executor should
be free to swap "off-the-shelf database primitives" under the Datalog
optimizer. Concretely, two primitives dominate the fixpoint hot path:

  probe(build, probe) -> (lo, hi)
      The count/locate phase of the sort-merge join: for every probe key
      (packed row key — up to 63 bits — int64, sorted ascending, dead
      rows = KEY_PAD) its lower/upper rank in the sorted build keys.
      Serves ``relops.join``, the lattice lookup of
      ``relops.merge_with_delta``, and (via the sort-and-scatter wrapper
      in ``relops.membership``) semijoin/antijoin/difference.
      ``needs_sorted_probe`` declares whether the implementation
      requires sorted probe keys: the Pallas merge-path kernel does
      (its block min/max skip logic assumes both sides ascend), plain
      ``searchsorted`` does not — membership only pays the probe-side
      sort where the kernel needs it.

  probe_multi(build_words, probe_words) -> (lo, hi)
      The same ranks for multi-word lexicographic keys ([*, W] int64
      word vectors, relation.pack_key_words; dead rows = KEY_PAD in
      every word) — the wide-relation generalization. relops squeezes
      W = 1 keys onto ``probe`` so narrow programs keep the exact
      single-word fast path; ``probe_multi`` only runs for keys of
      >= 4 columns (or under relation.force_multiword()).

  segment_reduce(values, seg_ids, num_segments, op) -> [num_segments]
      Sorted-segment aggregation (op in sum/min/max) behind
      ``relops.reduce_groups`` (Datalog COUNT/SUM/MIN/MAX) and the
      duplicate-combine of ``relops.dedupe`` for valued semirings
      (COUNTING multiplicities, MIN/MAX lattice merge).

  merge_ranks(a_keys, b_keys) -> (pos_a, pos_b)
      Output positions of a stable two-pointer merge of two sorted key
      sequences (a wins ties) — incremental arrangement maintenance:
      ``relops.merge_sorted`` scatters the already-sorted ``full`` and
      the small sorted ``delta`` by rank instead of concat + full
      re-sort, turning the hottest per-iteration cost from O(n log n)
      into O(n + |delta|). ``merge_ranks_multi`` is the word-vector
      variant. jnp = two searchsorted passes; Pallas = the merge-path
      probe kernel run once per rank side.

  expand(offsets, out_cap) -> (row_idx, within_idx, valid, total)
      The join's bounded expand (repeat-by-counts). jnp reference on
      every backend today; a Pallas expand kernel plugs in behind the
      same entry point later.

A ``KernelDispatch`` bundles one implementation of each. Two are
provided:

  * ``JnpDispatch``    — pure jnp (``searchsorted`` / ``jax.ops.segment_*``):
    the XLA fallback, also what the dry-run lowers so cost analysis sees
    plain XLA ops.
  * ``PallasDispatch`` — the TPU Pallas kernels in ``repro.kernels``
    (``merge_probe_counts`` blocked merge-path probe,
    ``segment_reduce`` one-hot segment reduction), compiled for the TPU,
    or run in interpret mode on request so CPU CI validates the exact
    kernel bodies that deploy.

Selection happens ONCE at engine construction from
``EngineConfig.kernel_backend``:

  "auto"             -> "pallas" on TPU, "jnp" otherwise
  "pallas"           -> compiled kernels; raises when JAX has no TPU
  "pallas-interpret" -> the same kernel bodies in interpret mode (a
                        validation tool, not a fast CPU path)
  "jnp"              -> pure-jnp everywhere

Contracts the dispatch boundary guarantees (and the equivalence tests
in tests/test_backend_equivalence.py pin down):

  * ``lo`` ranks are identical to ``searchsorted(..., 'left')`` for all
    probe keys, including KEY_PAD; ``hi`` ranks are identical to
    ``searchsorted(..., 'right')`` for every *live* probe key. For a
    KEY_PAD probe the Pallas kernel's ``hi`` may additionally count its
    own block padding — relops masks dead-probe counts to zero, so this
    never reaches a result.
  * integer segment reductions accumulate natively in int32 inside the
    kernel — no float32 rounding; sums past 2**31 - 1 wrap exactly
    like ``jax.ops.segment_sum`` does — with the same empty-segment
    identities as ``jax.ops.segment_min/max``, so both backends emit
    byte-identical relations.

Every hot physical op of the fixpoint now routes through this seam
(probe, segment reduce, merge ranks, expand); the remaining candidate
for a dedicated kernel body is a fused dedupe-compare and the Pallas
implementation of ``expand``. See ROADMAP "Open items".
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from repro.engine import observe as O
from repro.engine.observe import trace_count
from repro.kernels import ops
from repro.kernels.merge_probe import band_blocks


class KernelDispatch:
    """Injected probe/reduce implementations for the engine hot path.

    Instances are Python-level configuration (closed over by the jitted
    iteration body), never traced values; methods must be traceable.
    """

    name = "abstract"
    # True if ``probe`` requires ascending probe keys (the Pallas
    # merge-path kernel does); relops.membership then sorts-and-scatters
    # its unsorted probe side instead of calling probe directly.
    needs_sorted_probe = False

    def _count(self, op: str) -> None:
        """Trace-time kernel-launch counter (``kernel.<backend>.<op>``
        in observe.REGISTRY): under jit it counts dispatches emitted
        into the compiled graph, once per compilation. Concrete
        methods call it; the abstract default derivations don't (they
        bottom out in counted concrete probes)."""
        trace_count(f"kernel.{self.name}.{op}")

    def probe(self, build_keys: jax.Array, probe_keys: jax.Array):
        """(lo, hi) int32 ranks of sorted int64 probe keys in sorted
        int64 build keys (see module docstring for the PAD contract)."""
        raise NotImplementedError

    def probe_lo(self, build_keys: jax.Array, probe_keys: jax.Array):
        """Lower rank only (merge_with_delta's lattice lookup needs no
        hi). Default derives from ``probe``; backends whose lo-only
        form is cheaper override it."""
        return self.probe(build_keys, probe_keys)[0]

    def probe_multi(self, build_words: jax.Array,
                    probe_words: jax.Array):
        """(lo, hi) int32 ranks of [n, W] probe word vectors in sorted
        [m, W] build word vectors under word-wise lexicographic order
        (the multi-word key contract of relation.pack_key_words)."""
        raise NotImplementedError

    def probe_lo_multi(self, build_words: jax.Array,
                       probe_words: jax.Array):
        """Lower rank only, multi-word keys."""
        return self.probe_multi(build_words, probe_words)[0]

    def segment_reduce(self, values: jax.Array, seg_ids: jax.Array,
                       num_segments: int, op: str) -> jax.Array:
        """Reduce ``values`` [n] over sorted ``seg_ids`` (out-of-range
        ids dropped) with op in {"sum", "min", "max"}."""
        raise NotImplementedError

    def merge_ranks(self, a_keys: jax.Array, b_keys: jax.Array):
        """(pos_a, pos_b) int32 output positions of the stable merge of
        two sorted int64 key sequences (a wins ties):
        pos_a[i] = i + #{b < a[i]}, pos_b[j] = j + #{a <= b[j]}.
        Both sides sorted, so the default derivation runs ``probe``
        once per side; backends with a fused merge-path kernel
        override. For KEY_PAD rows of b, pos_b may overshoot (the
        probe's dead-probe hi contract) — consumers scatter with drop
        mode, which is byte-identical for dead rows."""
        m = a_keys.shape[0]
        n = b_keys.shape[0]
        lo_a = self.probe_lo(b_keys, a_keys)
        _, hi_b = self.probe(a_keys, b_keys)
        return (jnp.arange(m, dtype=jnp.int32) + lo_a,
                jnp.arange(n, dtype=jnp.int32) + hi_b)

    def merge_ranks_multi(self, a_words: jax.Array, b_words: jax.Array):
        """Multi-word ``merge_ranks``: [m, W] / [n, W] int64 key-word
        vectors under word-wise lexicographic order."""
        m = a_words.shape[0]
        n = b_words.shape[0]
        lo_a = self.probe_lo_multi(b_words, a_words)
        _, hi_b = self.probe_multi(a_words, b_words)
        return (jnp.arange(m, dtype=jnp.int32) + lo_a,
                jnp.arange(n, dtype=jnp.int32) + hi_b)

    def observed(self, obs) -> "KernelDispatch":
        """This dispatch with the observation ``obs`` attached (None:
        this dispatch as it is). Only the Pallas probe reports to it."""
        return self

    def expand(self, offsets: jax.Array, out_cap: int):
        """The join's bounded expand: output slot j -> (input row,
        within-group index, valid, total). Routed through the seam so a
        Pallas expand kernel can replace the jnp reference without
        touching relops."""
        self._count("expand")
        return ops.expand_indices(offsets, out_cap, backend="xla")

    def __repr__(self):
        return f"<KernelDispatch {self.name}>"


class JnpDispatch(KernelDispatch):
    """Pure-jnp implementations — the portable XLA fallback."""

    name = "jnp"

    def probe(self, build_keys, probe_keys):
        self._count("probe")
        lo, hi = ops.merge_probe_counts(build_keys, probe_keys,
                                        backend="xla")
        return lo.astype(jnp.int32), hi.astype(jnp.int32)

    def probe_lo(self, build_keys, probe_keys):
        # one searchsorted pass, not two (matters when jit is off;
        # under jit XLA would DCE the unused hi anyway)
        self._count("probe_lo")
        return jnp.searchsorted(build_keys, probe_keys,
                                side="left").astype(jnp.int32)

    def probe_multi(self, build_words, probe_words):
        self._count("probe_multi")
        return ops.merge_probe_multi(build_words, probe_words,
                                     backend="xla")

    def merge_ranks(self, a_keys, b_keys):
        self._count("merge_ranks")
        return ops.merge_ranks(a_keys, b_keys, backend="xla")

    def merge_ranks_multi(self, a_words, b_words):
        self._count("merge_ranks_multi")
        return ops.merge_ranks_multi(a_words, b_words, backend="xla")

    def segment_reduce(self, values, seg_ids, num_segments, op):
        self._count("segment_reduce")
        return ops.segment_reduce(values, seg_ids, num_segments, op,
                                  backend="xla")


class PallasDispatch(KernelDispatch):
    """Routes to the Pallas kernels: compiled for the TPU, or run in
    interpret mode (``interpret=True``) so CPU tests exercise the
    deployed kernel bodies."""

    needs_sorted_probe = True

    def __init__(self, interpret: bool, observe=None):
        self.interpret = interpret
        self.name = "pallas-interpret" if interpret else "pallas"
        self._mode = "interpret" if interpret else "pallas"
        self.observe = observe

    def observed(self, obs) -> "PallasDispatch":
        if obs is None or obs is self.observe:
            return self
        return PallasDispatch(self.interpret, obs)

    def _count_blocks(self, build, probe) -> None:
        """With an observation attached, count a probe's block pairs
        each time the traced program runs: the band pairs the kernel
        compares (``kernels.probe.compared_blocks``) and the ``nP * nB``
        pairs of the whole block grid (``kernels.probe.grid_blocks``).
        The count reaches the host through a callback, which programs
        traced without an observation do not compile."""
        if self.observe is None:
            return
        compared, grid = band_blocks(build, probe)
        jax.debug.callback(functools.partial(self._tally, grid), compared)

    def _tally(self, grid: int, compared) -> None:
        """The block counter's host side. The shards of one program call
        back at once, so the counts are added under a lock."""
        with _TALLY_LOCK:
            O.count(self.observe, "kernels.probe.compared_blocks",
                    int(compared))
            O.count(self.observe, "kernels.probe.grid_blocks", grid)

    def probe(self, build_keys, probe_keys):
        self._count("probe")
        self._count_blocks(build_keys, probe_keys)
        return ops.merge_probe_counts(build_keys, probe_keys,
                                      backend=self._mode)

    def probe_multi(self, build_words, probe_words):
        self._count("probe_multi")
        self._count_blocks(build_words, probe_words)
        return ops.merge_probe_multi(build_words, probe_words,
                                     backend=self._mode)

    def merge_ranks(self, a_keys, b_keys):
        # both rank passes through the blocked merge-path kernel (both
        # sequences are sorted arrangements — the kernel's contract)
        self._count("merge_ranks")
        self._count_blocks(b_keys, a_keys)
        self._count_blocks(a_keys, b_keys)
        return ops.merge_ranks(a_keys, b_keys, backend=self._mode)

    def merge_ranks_multi(self, a_words, b_words):
        self._count("merge_ranks_multi")
        self._count_blocks(b_words, a_words)
        self._count_blocks(a_words, b_words)
        return ops.merge_ranks_multi(a_words, b_words,
                                     backend=self._mode)

    def segment_reduce(self, values, seg_ids, num_segments, op):
        # The kernel accumulates integer inputs natively in int32
        # (exact; a float32 accumulator would round above 2**24) with
        # the same empty-segment identities as jax.ops.segment_*, so
        # no post-processing is needed for bit-equality.
        self._count("segment_reduce")
        return ops.segment_reduce(values, seg_ids, num_segments, op,
                                  backend=self._mode)


JNP = JnpDispatch()
_TALLY_LOCK = threading.Lock()

_CHOICES = ("auto", "pallas", "pallas-interpret", "jnp")


def resolve_backend(spec: "str | KernelDispatch | None" = "auto",
                    ) -> KernelDispatch:
    """Resolve an ``EngineConfig.kernel_backend`` spec to a dispatch
    object. Called once at engine construction — never per-op."""
    if spec is None:
        spec = "auto"
    if isinstance(spec, KernelDispatch):
        return spec
    on_tpu = jax.default_backend() == "tpu"
    if spec == "auto":
        spec = "pallas" if on_tpu else "jnp"
    if spec == "jnp":
        return JNP
    if spec == "pallas":
        if not on_tpu:
            raise RuntimeError(
                "kernel_backend='pallas' compiles the kernels for a TPU, "
                f"but JAX's default backend is {jax.default_backend()!r}; "
                "use 'pallas-interpret' to run them in interpret mode")
        return PallasDispatch(interpret=False)
    if spec == "pallas-interpret":
        return PallasDispatch(interpret=True)
    raise ValueError(
        f"kernel_backend={spec!r}: expected one of {_CHOICES}")
