"""Physical relational operators in JAX — the differential-operator layer.

Every op consumes/produces the sorted, distinct, fixed-capacity
``Relation`` struct (see relation.py) and returns an overflow flag when a
bounded data-dependent output may have been truncated. Ops are pure and
shape-static, so the whole iteration body fuses under jit, and the same
code lowers under pjit/shard_map for scale-out (DESIGN.md §7).

Hot physical primitives (the join's count/locate probe and bounded
expand, the merge_with_delta lattice lookup, the membership probe
behind semijoin/antijoin/difference, grouped segment aggregation,
``dedupe``'s duplicate-combine, and the incremental merge ranks) are
not hard-coded: ops take an injected ``KernelDispatch``
(engine/backend.py) that routes them to the Pallas TPU kernels or the
pure-jnp fallback. ``backend=None`` means jnp.

Arrangement layer (relation.py docstring): ``arrange`` consults the
relation's sort-order witness and skips no-op sorts; ops additionally
take an optional ``ArrangementCache`` so all rules/subplans of one
evaluation pass share one sort per (relation, key); and ``merge`` /
``merge_with_delta`` maintain the sorted ``full`` incrementally
(``merge_sorted``: a two-pointer rank merge with the small sorted
delta) instead of concat + full re-sort — O(n + |delta|) per
iteration, byte-identical results.

Row keys are multi-word lexicographic (relation.pack_key_words): keys
of <= 3 columns stay on the legacy single-word probe seam bit-for-bit
(the narrow fast path), wider keys probe word vectors through
``probe_multi`` — which is how relations of any arity flow through
join/membership/merge unchanged at the logical level.

Correspondence to DD operators (paper Sec. 2.3):
    arrange        -> ``arrange`` (sort by join-key prefix)
    join_core      -> ``join`` (sort-merge: searchsorted + bounded expand)
    distinct       -> ``dedupe``
    concat         -> ``concat_all`` + ``dedupe``
    antijoin       -> ``antijoin`` (the Boolean-lift of Sec. 8: membership
                      materialized as 0/1, subtracted, thresholded)
    reduce         -> ``reduce`` (sorted segment aggregation)
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.engine.backend import JNP, KernelDispatch
from repro.engine.observe import trace_count
from repro.engine.relation import (
    KEY_PAD, PAD, Relation, lex_order, lex_order_words,
    live_mask, pack_key_words, rows_equal_prev,
)
from repro.engine.semiring import Semiring, PRESENCE


def _scoped(relop):
    """Run ``relop`` under a ``jax.named_scope`` of its own name, so that
    every device op it emits inside a jitted program carries the relop
    in its ``op_name`` metadata (a relop called inside another nests
    its scope)."""
    @functools.wraps(relop)
    def scoped(*args, **kwargs):
        with jax.named_scope(relop.__name__):
            return relop(*args, **kwargs)
    return scoped


def _probe_ranks(bk: KernelDispatch, build_words, probe_words):
    """(lo, hi) ranks for [*, W] key-word vectors; W = 1 squeezes onto
    the legacy single-word seam (the narrow fast path)."""
    if build_words.shape[1] == 1:
        return bk.probe(build_words[:, 0], probe_words[:, 0])
    return bk.probe_multi(build_words, probe_words)


def _probe_lo_ranks(bk: KernelDispatch, build_words, probe_words):
    if build_words.shape[1] == 1:
        return bk.probe_lo(build_words[:, 0], probe_words[:, 0])
    return bk.probe_lo_multi(build_words, probe_words)


def _take_rows(data: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take(data, idx, axis=0, mode="clip")


def _scatter_compact(data, val, keep, out_cap, val_identity):
    """Stable compaction: keep[i] rows move to positions cumsum-1; result
    preserves input order. Returns (data, val, n, overflow)."""
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    n = jnp.where(keep.any(), pos[-1] + 1, 0).astype(jnp.int32) if (
        keep.shape[0]) else jnp.zeros((), jnp.int32)
    overflow = n > out_cap
    tgt = jnp.where(keep, pos, out_cap)  # out-of-bounds -> dropped
    out = jnp.full((out_cap, data.shape[1]), PAD, jnp.int32)
    out = out.at[tgt].set(data, mode="drop")
    vout = None
    if val is not None:
        vout = jnp.full((out_cap,) + val.shape[1:], val_identity, val.dtype)
        vout = vout.at[tgt].set(val, mode="drop")
    return out, vout, jnp.minimum(n, out_cap), overflow


@_scoped
def dedupe(data: jax.Array, val: Optional[jax.Array], sr: Semiring,
           out_cap: int, assume_sorted: bool = False,
           backend: Optional[KernelDispatch] = None):
    """Sort rows, combine duplicate rows' values with ``sr.add`` (presence:
    drop duplicates), emit sorted distinct rows. PAD rows (data == PAD in
    every column) are dropped. Returns (Relation, overflow).

    The duplicate-combine is a sorted-segment reduction (segment ids
    ascend because rows are sorted; dead rows map out of range), so it
    dispatches through the injected ``backend`` exactly like
    ``reduce_groups``."""
    bk = backend or JNP
    trace_count("relops.dedupe")
    if sr.has_value and val is None:
        val = jnp.ones((data.shape[0],), sr.dtype)  # implicit lift (Sec. 8)
    if not assume_sorted:
        order = lex_order(data)
        data = data[order]
        if val is not None:
            val = val[order]
    if data.shape[1] == 0:
        raise ValueError("zero-arity relations are stored with a dummy "
                         "constant column (see engine)")
    live = ~jnp.all(data == PAD, axis=1)
    dup = rows_equal_prev(data) & live
    first = live & ~dup
    if val is not None and sr.has_value:
        seg = jnp.cumsum(first.astype(jnp.int32)) - 1
        seg = jnp.where(live, seg, data.shape[0])  # drop dead rows
        op = "sum" if sr.name == "counting" else sr.name
        combined = bk.segment_reduce(val, seg, data.shape[0], op)
        # positions of firsts get the combined value
        val = jnp.where(first, combined[jnp.cumsum(first) - 1], val)
        if sr.name == "counting":
            # drop rows whose combined count is 0 (retraction fixpoint)
            first = first & (val != 0)
    d, v, n, ov = _scatter_compact(
        data, val, first, out_cap, sr.identity if sr.has_value else 0)
    if not sr.has_value:
        v = None
    return Relation(d, v, n), ov


@_scoped
def arrange(rel: Relation, key_cols: tuple[int, ...]) -> Relation:
    """Sort a relation so ``key_cols`` form the primary sort order (the
    DD 'arrangement'). Fast path: when ``key_cols`` is already a prefix
    of the relation's sort-order witness the relation IS the requested
    arrangement and no sort (or column-permutation round-trip) runs at
    all — a no-op arrange used to pay a full ``lex_order`` every call.

    Guarantee: rows come back sorted primarily by the ``key_cols``
    sequence; the exact tie-breaking order among the remaining columns
    is whatever the output's witness records — ascending column order
    when a fresh sort runs, the pre-existing witness tail when the
    fast path applies (e.g. ``arrange(arrange(r, (2, 1)), (2,))``
    keeps (2, 1, 0) order rather than re-sorting to (2, 0, 1)). Every
    key-prefix consumer (join probe, membership, segment boundaries)
    is tie-order-insensitive, and materialization always goes through
    a witness-blind ``dedupe`` — do not rely on a specific tie order
    across the fast path."""
    key_cols = tuple(key_cols)
    if rel.arranged_by(key_cols):
        trace_count("arrange.cache_fastpath")
        return rel
    perm = tuple(key_cols) + tuple(c for c in range(rel.arity)
                                   if c not in key_cols)
    reordered = rel.data[:, jnp.array(perm)]
    order = lex_order(reordered)
    data = rel.data[order]
    val = rel.val[order] if rel.val is not None else None
    return Relation(data, val, rel.n, order=perm)


class ArrangementCache:
    """Shares arrangements across all rules/subplans of one evaluation
    pass — the executor realization of the Sec. 7 plan-level sharing
    the optimizer annotates (`SharedRef`s memoize whole subplans; this
    memoizes the physical sort under every join/membership/reduce).

    Keying: ``(id(rel.data), key_cols)``, verified on lookup by ``is``
    against ALL three stored leaves (data, val, n) — the leaves are
    held strongly so a recycled CPython id can never alias a dead
    relation, and a relation sharing a data array but carrying a
    different live count or payload (e.g. the sharded zero-key guard's
    psum-recounted view) never aliases a cached entry either. Lifetime
    is one evaluation pass (one iteration body / one seed pass): the
    engine constructs a fresh cache per pass, which under jit means
    per *trace* — a hit removes the duplicate sort from the compiled
    step entirely.

    Entries are plain Relations, so a cached arrangement's witness
    makes a later compatible request (e.g. key (2, 0) after (2,))
    resolve via the no-sort fast path as well."""

    def __init__(self):
        self._entries: dict = {}
        self.hits = 0
        self.misses = 0

    def arrange(self, rel: Relation, key_cols: tuple[int, ...]
                ) -> Relation:
        key_cols = tuple(key_cols)
        if rel.arranged_by(key_cols):
            trace_count("arrange.cache_fastpath")
            return rel
        key = (id(rel.data), key_cols)
        ent = self._entries.get(key)
        if ent is not None and ent[0] is rel.data and (
                ent[1] is rel.val) and ent[2] is rel.n:
            self.hits += 1
            trace_count("arrange.cache_hits")
            return ent[3]
        self.misses += 1
        trace_count("arrange.cache_misses")
        arranged = arrange(rel, key_cols)
        self._entries[key] = (rel.data, rel.val, rel.n, arranged)
        return arranged

    def memo(self, tag, keyed_leaves: tuple, compute):
        """Generic sharing for non-sort physical work keyed on a
        relation's identity — e.g. a sharded repartition whose result
        many ops of the same pass reuse (shard.ShardedEvaluator).
        ``keyed_leaves`` is the tuple of objects the work depends on;
        every leaf is held strongly and re-verified with ``is``."""
        key = (tag,) + tuple(id(x) for x in keyed_leaves)
        ent = self._entries.get(key)
        if ent is not None and all(
                a is b for a, b in zip(ent[0], keyed_leaves)):
            self.hits += 1
            trace_count("arrange.cache_hits")
            return ent[1]
        self.misses += 1
        trace_count("arrange.cache_misses")
        out = compute()
        self._entries[key] = (keyed_leaves, out)
        return out


def _arrange(cache: "ArrangementCache | None", rel: Relation,
             key_cols: tuple[int, ...]) -> Relation:
    if cache is not None:
        return cache.arrange(rel, key_cols)
    return arrange(rel, key_cols)


def _searchsorted(sorted_keys, query):
    lo = jnp.searchsorted(sorted_keys, query, side="left")
    hi = jnp.searchsorted(sorted_keys, query, side="right")
    return lo, hi


def expand_indices(counts: jax.Array, offsets: jax.Array, out_cap: int):
    """The bounded 'repeat' pattern: output slot j maps to input row
    i = searchsorted(offsets, j, 'right') with within-group index
    j - offsets[i-1]. Returns (row_idx, within_idx, valid, total).
    Kept as the jnp reference; ``join`` dispatches through
    ``KernelDispatch.expand``."""
    del counts  # offsets alone determine the expansion
    from repro.kernels import ref
    return ref.expand_indices_ref(offsets, out_cap)


@_scoped
def join(left: Relation, right: Relation,
         l_keys: tuple[int, ...], r_keys: tuple[int, ...],
         l_out: tuple[int, ...], r_out: tuple[int, ...],
         sr: Semiring, out_cap: int,
         arranged: bool = False,
         backend: Optional[KernelDispatch] = None,
         cache: Optional[ArrangementCache] = None):
    """Sort-merge inner join. Output columns = left[l_out] ++ right[r_out]
    (unsorted; callers dedupe/arrange downstream). Returns
    (data, val, valid_mask, total, overflow) — 'loose rows', so fused
    consumers (Join-FlatMap) can filter/project before compaction.

    Both operand arrangements resolve through ``cache`` when given, so
    rules/subplans of the same evaluation pass share one sort per
    (relation, key). The count/locate phase (probe ranks) and the
    bounded expand both go through the injected ``backend``
    (backend.py): both sides are arrangements, so the key word vectors
    are sorted and the blocked Pallas merge-path probe applies —
    single-word for <= 3 key columns (the narrow fast path), word-wise
    for wider keys."""
    bk = backend or JNP
    trace_count("relops.join")
    if not arranged:
        left = _arrange(cache, left, l_keys)
        right = _arrange(cache, right, r_keys)
    lk = pack_key_words(left.data, l_keys, live_mask(left))
    rk = pack_key_words(right.data, r_keys, live_mask(right))
    lo, hi = _probe_ranks(bk, rk, lk)
    counts = jnp.where(live_mask(left), hi - lo, 0)
    offsets = jnp.cumsum(counts)
    li, within, valid, total = bk.expand(offsets, out_cap)
    ri = _take_rows(lo, li) + within
    ldata = _take_rows(left.data, li)
    rdata = _take_rows(right.data, ri)
    cols = []
    if l_out:
        cols.append(ldata[:, jnp.array(l_out)])
    if r_out:
        cols.append(rdata[:, jnp.array(r_out)])
    data = jnp.concatenate(cols, axis=1) if cols else jnp.zeros(
        (out_cap, 0), jnp.int32)
    val = None
    if sr.has_value and sr.mul is not None:
        lval = _take_rows(left.val, li) if left.val is not None else 1
        rval = _take_rows(right.val, ri) if right.val is not None else 1
        val = sr.mul(lval, rval)
    overflow = total > out_cap
    return data, val, valid, total, overflow


@_scoped
def membership(left: Relation, right: Relation,
               l_keys: tuple[int, ...], r_keys: tuple[int, ...],
               right_arranged: bool = False,
               backend: Optional[KernelDispatch] = None,
               cache: Optional[ArrangementCache] = None) -> jax.Array:
    """Boolean mask over left rows: does the key appear in right?
    (The lift operator of Sec. 8 materializes this 0/1.)

    The rank probe goes through the injected ``backend``. The Pallas
    merge-path kernel requires *sorted* probe keys (it skips blocks by
    min/max bounds), but left here is arranged by its own row order, not
    by ``l_keys`` — so for backends with ``needs_sorted_probe`` we sort
    the probe keys, probe, and scatter the verdicts back through the
    argsort permutation (the "sort-and-scatter variant" named by the
    ROADMAP). KEY_PAD probes sort last and may overcount their hi rank
    in-kernel; the trailing live-mask AND discards them."""
    bk = backend or JNP
    trace_count("relops.membership")
    if not right_arranged:
        right = _arrange(cache, right, r_keys)
    if len(l_keys) == 0:
        # ground guard: right non-empty? (dead left rows stay dead —
        # without the mask a zero-key semijoin would resurrect the PAD
        # tail as live rows and the fixpoint would never drain)
        return jnp.broadcast_to(right.n > 0, (left.capacity,)) & (
            live_mask(left))
    lk = pack_key_words(left.data, l_keys, live_mask(left))
    rk = pack_key_words(right.data, r_keys, live_mask(right))
    if bk.needs_sorted_probe:
        order = lex_order_words(lk)
        lo, hi = _probe_ranks(bk, rk, jnp.take(lk, order, axis=0))
        found = jnp.zeros((left.capacity,), bool).at[order].set(hi > lo)
    else:
        lo, hi = _probe_ranks(bk, rk, lk)
        found = hi > lo
    return found & live_mask(left)


def semijoin(left: Relation, right: Relation,
             l_keys: tuple[int, ...], r_keys: tuple[int, ...],
             out_cap: Optional[int] = None, sr: Semiring = PRESENCE,
             backend: Optional[KernelDispatch] = None,
             cache: Optional[ArrangementCache] = None):
    out_cap = out_cap or left.capacity
    keep = membership(left, right, l_keys, r_keys, backend=backend,
                      cache=cache)
    d, v, n, ov = _scatter_compact(
        left.data, left.val, keep, out_cap,
        sr.identity if sr.has_value else 0)
    return Relation(d, v if left.val is not None else None, n,
                    order=left.order), ov


def antijoin(left: Relation, right: Relation,
             l_keys: tuple[int, ...], r_keys: tuple[int, ...],
             out_cap: Optional[int] = None, sr: Semiring = PRESENCE,
             backend: Optional[KernelDispatch] = None,
             cache: Optional[ArrangementCache] = None):
    out_cap = out_cap or left.capacity
    keep = (~membership(left, right, l_keys, r_keys, backend=backend,
                        cache=cache)) & (live_mask(left))
    d, v, n, ov = _scatter_compact(
        left.data, left.val, keep, out_cap,
        sr.identity if sr.has_value else 0)
    return Relation(d, v if left.val is not None else None, n,
                    order=left.order), ov


def difference(a: Relation, b: Relation,
               backend: Optional[KernelDispatch] = None,
               cache: Optional[ArrangementCache] = None,
               ) -> tuple[Relation, jax.Array]:
    """Rows of a (all columns as key) not present in b. b is identity-
    sorted in the engine (it is a maintained full arrangement), so with
    the witness fast path its arrange is free."""
    cols = tuple(range(a.arity))
    return antijoin(a, b, cols, cols, backend=backend, cache=cache)


@_scoped
def concat_all(rels: Sequence[Relation], sr: Semiring, out_cap: int,
               backend: Optional[KernelDispatch] = None):
    """Multiway union with value combine (ConcatAll, Sec. 4)."""
    data = jnp.concatenate([r.data for r in rels], axis=0)
    val = None
    if sr.has_value:
        val = jnp.concatenate(
            [r.val if r.val is not None
             else jnp.ones((r.capacity,), sr.dtype) for r in rels])
    return dedupe(data, val, sr, out_cap, backend=backend)


def merge_sorted(full: Relation, delta: Relation, sr: Semiring,
                 out_cap: int,
                 backend: Optional[KernelDispatch] = None):
    """Incremental arrangement maintenance: full ∪ delta for two
    identity-sorted arrangements WITHOUT re-sorting the world.

    Both operands are sorted, distinct, PAD-tailed arrangements, so the
    union is a stable two-pointer merge: the ``merge_ranks`` dispatch
    entry (backend.py) computes each side's output position by rank
    (full wins ties, so duplicate rows land adjacent with full's copy
    first — exactly the order the old concat + stable lexsort
    produced), rows scatter once into a [cap_f + cap_d] buffer, and
    ``dedupe(assume_sorted=True)`` combines duplicates and compacts.
    Per-iteration cost drops from O((n + Δ) log (n + Δ)) sort-everything
    to O(n + Δ) merge — byte-identical output.

    Row order is the full-row packed key (relation.pack_key_words), the
    same keys ``merge_with_delta``'s lattice lookup and ``difference``
    already rely on — so this path adds no new value-range assumption.
    Dead rows key as KEY_PAD and land in (or are dropped past) the PAD
    tail; either way the buffer byte-matches across backends."""
    bk = backend or JNP
    trace_count("arrange.merge_sorted")
    m, n = full.capacity, delta.capacity
    cols = tuple(range(full.arity))
    fk = pack_key_words(full.data, cols, live_mask(full))
    dk = pack_key_words(delta.data, cols, live_mask(delta))
    if fk.shape[1] == 1:
        pos_f, pos_d = bk.merge_ranks(fk[:, 0], dk[:, 0])
    else:
        pos_f, pos_d = bk.merge_ranks_multi(fk, dk)
    data = jnp.full((m + n, full.arity), PAD, jnp.int32)
    data = data.at[pos_f].set(full.data, mode="drop")
    data = data.at[pos_d].set(delta.data, mode="drop")
    val = None
    if sr.has_value:
        fval = full.val if full.val is not None else jnp.ones(
            (m,), sr.dtype)
        dval = delta.val if delta.val is not None else jnp.ones(
            (n,), sr.dtype)
        val = jnp.full((m + n,), sr.identity, sr.dtype)
        val = val.at[pos_f].set(fval, mode="drop")
        val = val.at[pos_d].set(dval, mode="drop")
    return dedupe(data, val, sr, out_cap, assume_sorted=True,
                  backend=backend)


@_scoped
def merge(full: Relation, delta: Relation, sr: Semiring, out_cap: int,
          backend: Optional[KernelDispatch] = None,
          incremental: bool = True):
    """full ∪ delta with sr.add combine. Returns (Relation, overflow).

    When both operands are identity-sorted arrangements (the engine's
    maintained fulls and deltas always are) the union runs through
    ``merge_sorted`` — incremental maintenance with no full re-sort.
    ``incremental=False`` (or an operand with a non-identity witness)
    falls back to concat + sort; the two paths are byte-identical."""
    if incremental and full.identity_sorted and delta.identity_sorted:
        return merge_sorted(full, delta, sr, out_cap, backend=backend)
    return concat_all([full, delta], sr, out_cap, backend=backend)


@_scoped
def merge_with_delta(full: Relation, derived: Relation, sr: Semiring,
                     out_cap: int,
                     backend: Optional[KernelDispatch] = None,
                     cache: Optional[ArrangementCache] = None,
                     incremental: bool = True):
    """Merge ``derived`` into ``full``; return (new_full, new_delta, ovf).

    PRESENCE: delta = derived rows not already in full (set difference).
    MIN/MAX:  delta = rows whose lattice value strictly improved.
    This single primitive is the semi-naive frontier step (Sec. 2.2) and
    the monoid iteration of Sec. 9. The full-arrangement update is the
    incremental ``merge_sorted`` path (see ``merge``); the difference's
    arrange of ``full`` resolves via ``cache``/witness, so the frontier
    step re-sorts nothing.
    """
    new_full, ov1 = merge(full, derived, sr, out_cap, backend=backend,
                          incremental=incremental)
    if not sr.has_value:
        delta, ov2 = difference(derived, full, backend=backend,
                                cache=cache)
        return new_full, delta, ov1 | ov2
    # lattice: look up each new_full row's key in old full, compare
    # values. Both arrays are sorted arrangements, so the lookup is a
    # probe (lo rank only) and dispatches like the join's locate phase —
    # the key is ALL stored columns, so wide IDBs take the multi-word
    # probe while <= 3-column IDBs stay on the single-word fast path.
    bk = backend or JNP
    cols = tuple(range(full.arity))
    fk = pack_key_words(full.data, cols, live_mask(full))
    nk = pack_key_words(new_full.data, cols, live_mask(new_full))
    lo = _probe_lo_ranks(bk, fk, nk)
    if fk.shape[1] == 1:
        found = (jnp.take(fk[:, 0], lo, mode="clip") == nk[:, 0]) & (
            nk[:, 0] != KEY_PAD)
    else:
        found = jnp.all(
            jnp.take(fk, lo, axis=0, mode="clip") == nk, axis=1) & (
            live_mask(new_full))
    old_val = jnp.where(found, jnp.take(full.val, lo, mode="clip"),
                        sr.identity)
    improved = jnp.where(
        live_mask(new_full), sr.improves(new_full.val, old_val), False)
    d, v, n, ov2 = _scatter_compact(
        new_full.data, new_full.val, improved, out_cap, sr.identity)
    return new_full, Relation(d, v, n), ov1 | ov2


@_scoped
def reduce_groups(rel: Relation, group_cols: tuple[int, ...],
                  aggs: tuple[tuple[str, int], ...], out_cap: int,
                  backend: Optional[KernelDispatch] = None,
                  cache: Optional[ArrangementCache] = None):
    """Stratified grouped aggregation: sort by group key, segment-reduce.
    Output data columns = group_cols ++ one column per agg. COUNT counts
    *distinct* tuples (set semantics, matching Datalog COUNT(y)).

    The segment reduction dispatches through ``backend`` — segment ids
    are sorted ascending by construction (rows are arranged by group
    key), which is exactly the Pallas kernel's contract. The group-key
    arrangement resolves through ``cache``/witness like the join's."""
    bk = backend or JNP
    trace_count("relops.reduce_groups")
    r = _arrange(cache, rel, group_cols)
    live = live_mask(r)
    gkey = pack_key_words(r.data, group_cols, live)
    first = jnp.concatenate(
        [live[:1],
         jnp.any(gkey[1:] != gkey[:-1], axis=1) & live[1:]])
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    seg = jnp.where(live, seg, r.capacity)
    outs = []
    for func, col in aggs:
        x = r.data[:, col]
        if func == "COUNT":
            res = bk.segment_reduce(jnp.ones_like(x), seg, r.capacity,
                                    "sum")
        elif func == "SUM":
            res = bk.segment_reduce(x, seg, r.capacity, "sum")
        elif func == "MIN":
            res = bk.segment_reduce(x, seg, r.capacity, "min")
        elif func == "MAX":
            res = bk.segment_reduce(x, seg, r.capacity, "max")
        else:
            raise ValueError(func)
        outs.append(res)
    ngroups = jnp.sum(first.astype(jnp.int32))
    # first-row group tuples, compacted
    gcols = r.data[:, jnp.array(group_cols)] if group_cols else jnp.zeros(
        (r.capacity, 0), jnp.int32)
    agg_mat = jnp.stack(outs, axis=1).astype(jnp.int32)  # [cap, n_aggs]
    # compacted positions for firsts
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    tgt = jnp.where(first, pos, out_cap)
    width = len(group_cols) + len(aggs)
    out = jnp.full((out_cap, width), PAD, jnp.int32)
    if group_cols:
        out = out.at[tgt, :len(group_cols)].set(gcols, mode="drop")
    out = out.at[tgt, len(group_cols):].set(
        agg_mat[seg.clip(0, r.capacity - 1)], mode="drop")
    overflow = ngroups > out_cap
    n = jnp.minimum(ngroups, out_cap)
    # rows already emitted in group-key order; re-sort to full-row order
    return dedupe(out, None, PRESENCE, out_cap, assume_sorted=False,
                  backend=backend)[0], overflow


def as_columns(rel: Relation) -> jax.Array:
    """Expose a monoid relation's value as a trailing data column (Scan of
    a monoid IDB, e.g. cc(y, i) reads i from the diff; Sec. 9)."""
    if rel.val is None:
        return rel.data
    vcol = jnp.where(live_mask(rel), rel.val, PAD).astype(jnp.int32)
    return jnp.concatenate([rel.data, vcol[:, None]], axis=1)
