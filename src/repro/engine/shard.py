"""Sharded multi-device fixpoint execution — hash-partitioned semi-naive
evaluation under ``jax.shard_map`` (the RecStep / "Datalog on the GPU"
parallel-join lever, grafted onto this engine's arrangement relops).

Design
======

**Partition invariant.** A ``ShardedRelation`` is the engine's sorted-
arrangement ``Relation`` hash-partitioned across a 1-D device mesh
(axis ``"shards"``, ``launch.mesh.make_shard_mesh``): each leaf carries
a leading mesh axis (``data[s]``, ``val[s]``, ``n[s]`` are shard ``s``'s
block) and **every shard block is itself a valid Relation** — rows
``[0, n)`` live, sorted by packed row key, duplicate-free, PAD tail.
All shard-local relops therefore apply unchanged, including the Pallas
kernel dispatch (sharded × {jnp, pallas} composes for free).

Rows are placed by an FNV-1a hash of selected columns, finalized by
MurmurHash3's mix (``_row_hash``).
Materialized relations live on their **home** shard — the hash of the
*full* row — which makes equal rows co-locate, so the duplicate- and
value-combining ops of the fixpoint (``merge``, ``merge_with_delta``'s
set difference / lattice lookup, ``dedupe`` of concatenations) are
purely shard-local: no communication in the frontier step itself.
``_row_hash`` folds over any number of columns, so wide (>= 4-column)
relations home and repartition exactly like narrow ones — the
shard-local relops then key them with multi-word lexicographic keys
(relation.pack_key_words), and sharded × wide composes for free.

**Repartitioning.** Binary ops keyed on a column subset (join,
semijoin/antijoin, grouped reduce) first repartition their operands on
the operation key with a padded-bucket ``jax.lax.all_to_all``
(``repartition_rows``): each shard buckets its rows by destination into
an ``[S, cap]`` send buffer, the all-to-all swaps buckets, and a
shard-local ``dedupe`` re-sorts the received rows — restoring the
partition invariant and removing cross-shard duplicates (identical rows
hash identically, so they always meet). After the local join, derived
rows are re-homed by their full output row before merging into an IDB
(``ShardedEngine._merge_head``), which is what makes the sharded delta
*exactly* the single-device delta, shard by shard.

**Arrangements.** Every shard block is a valid sorted arrangement, so
the arrangement layer (relation.py docstring) applies shard-locally
unchanged: full/delta merges maintain each shard's arrangement
incrementally (``relops.merge_sorted`` — no per-iteration re-sort),
and the per-pass ``ArrangementCache`` additionally memoizes
*repartitions* by operand identity (``ShardedEvaluator._repart``), so
a shard-local arrangement built by one rule's all-to-all survives for
every other rule of the pass keyed the same way.

**Fixpoint driver.** ``ShardedEngine`` mirrors ``Engine._run_stratum``:

* ``host`` mode — one jitted ``shard_map`` step per iteration; the
  host reads the per-shard delta counts (a [S] array) to terminate.
* ``device`` mode — the whole stratum fixpoint is a single
  ``jax.lax.while_loop`` *inside* ``shard_map``; the ``any_delta``
  termination test is a cheap ``psum`` of delta counts, so every shard
  agrees on the loop condition without host synchronization (the
  paper's criticism of per-iteration sync, answered with a one-scalar
  collective).

Equivalence discipline: ``ShardedEngine`` produces byte-identical
fixpoints and identical iteration counts to ``Engine`` at any shard
count (tests/test_sharded.py), the same contract PR 1 pinned for
kernel backends. Sharding never changes *what* is derived — only where
each row lives between iterations.

Develop/test on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import ir as I
from repro.engine import faults as F
from repro.engine import observe as O
from repro.engine import relops as R
from repro.engine.engine import (
    Engine, EngineConfig, OverflowError_,
)
from repro.engine.observe import trace_count
from repro.engine.lower import Evaluator, LowerConfig
from repro.engine.relation import (
    PAD, Relation, from_numpy, live_mask, pow2_cap,
)
from repro.engine.semiring import Semiring
from repro.launch.mesh import SHARD_AXIS, make_shard_mesh

_SPEC = PartitionSpec(SHARD_AXIS)
_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)
# MurmurHash3's 64-bit finalizer (fmix64)
_MIX = (np.uint64(0xFF51AFD7ED558CCD), np.uint64(0xC4CEB9FE1A85EC53))


class ShardedRelation(NamedTuple):
    """A Relation hash-partitioned across the shard mesh: every leaf is
    the single-device leaf with a leading mesh axis, and every shard
    block satisfies the full Relation invariant (sorted, distinct,
    PAD-tailed) on its own."""
    data: jax.Array            # int32[shards, cap, arity]
    val: Optional[jax.Array]   # int32[shards, cap] or None
    n: jax.Array               # int32[shards]

    @property
    def num_shards(self) -> int:
        return self.data.shape[0]

    @property
    def capacity(self) -> int:
        return self.data.shape[1]

    @property
    def arity(self) -> int:
        return self.data.shape[2]

    @property
    def total(self):
        return self.n.sum()


def _to_local(sr: ShardedRelation) -> Relation:
    """Inside shard_map: strip the leading (length-1) mesh axis."""
    val = sr.val[0] if sr.val is not None else None
    return Relation(sr.data[0], val, sr.n[0])


def _to_global(rel: Relation) -> ShardedRelation:
    val = rel.val[None] if rel.val is not None else None
    return ShardedRelation(rel.data[None], val, rel.n[None])


def _is_rel(x) -> bool:
    return isinstance(x, (ShardedRelation, Relation))


def _unstack(tree):
    return jax.tree.map(_to_local, tree, is_leaf=_is_rel)


def _restack(tree):
    return jax.tree.map(_to_global, tree, is_leaf=_is_rel)


# -- hash partitioning -------------------------------------------------------

def _row_hash(data: jax.Array, cols: tuple[int, ...]) -> jax.Array:
    """FNV-1a over the selected columns (uint64), one round a column,
    then MurmurHash3's finalizer. Works for any arity — unlike the
    62-bit packed row key, so intermediate schemas wider than 3 columns
    still partition fine. Without the finalizer a column's bits reach
    bits 33+ (which pick the shard) only through rare carries: every
    row of ids below 2**16 then homed on one shard."""
    h = jnp.full((data.shape[0],), _FNV_OFFSET, jnp.uint64)
    for c in cols:
        h = (h ^ data[:, c].astype(jnp.uint64)) * _FNV_PRIME
    shift = jnp.uint64(33)
    for m in _MIX:
        h = (h ^ jnp.right_shift(h, shift)) * m
    return h ^ jnp.right_shift(h, shift)


def shard_of(data: jax.Array, cols: tuple[int, ...], live: jax.Array,
             num_shards: int) -> jax.Array:
    """Destination shard per row; dead rows map to ``num_shards`` so a
    drop-mode scatter discards them."""
    h = _row_hash(data, cols)
    dest = (jnp.right_shift(h, jnp.uint64(33))
            % jnp.uint64(num_shards)).astype(jnp.int32)
    return jnp.where(live, dest, num_shards)


# Rows each traced repartition puts into its send buffer, gathered while
# a step that reports them is traced (``wire_tally``); None otherwise.
_WIRE: contextvars.ContextVar = contextvars.ContextVar(
    "shard_wire", default=None)


@contextlib.contextmanager
def wire_tally(on: bool = True):
    """While a sharded step traces, collect every repartition's live
    send rows (a traced int64 scalar) and send-buffer slots (a static
    int); yields the list (None when ``on`` is false, which leaves the
    traced step as it was)."""
    if not on:
        yield None
        return
    tally: list = []
    token = _WIRE.set(tally)
    try:
        yield tally
    finally:
        _WIRE.reset(token)


def repartition_rows(data: jax.Array, val: Optional[jax.Array],
                     live: jax.Array, key_cols: tuple[int, ...],
                     sr: Semiring, out_cap: int, num_shards: int,
                     backend=None):
    """All-to-all hash repartition on ``key_cols`` (shard-local view;
    must run inside shard_map over the "shards" axis).

    Buckets rows by destination into a padded [S, cap] send buffer,
    swaps buckets with ``jax.lax.all_to_all``, then dedupes the
    received rows — restoring the sorted-arrangement invariant and
    combining any duplicates that now co-locate. Every op it emits
    (destination sort, bucket scatter, all-to-all, dedupe) runs under
    the ``jax.named_scope`` ``repartition``. Returns
    (Relation, overflow)."""
    with jax.named_scope("repartition"):
        cap, arity = data.shape
        if sr.has_value and val is None:
            val = jnp.ones((cap,), sr.dtype)
        # trace-time wire-volume accounting, once per compile (not per
        # execution): the padded buffer IS the wire volume — every launch
        # moves the whole [S, cap, arity] send buffer per shard regardless
        # of live rows, so these per-shard byte/slot counts are exact and
        # static (int32 = 4 bytes; +1 "column" when a val plane ships too)
        trace_count("shard.all_to_all.launches")
        trace_count("shard.all_to_all.slots", num_shards * cap)
        planes = arity + (1 if val is not None else 0)
        trace_count("shard.all_to_all.bytes", num_shards * cap * planes * 4)
        tally = _WIRE.get()
        if tally is not None:
            tally.append((live.sum(dtype=jnp.int64), num_shards * cap))
        dest = shard_of(data, key_cols, live, num_shards)
        order = jnp.argsort(dest)               # stable; dead rows last
        data = data[order]
        dst = dest[order]
        if val is not None:
            val = val[order]
        starts = jnp.searchsorted(dst, jnp.arange(num_shards))
        within = jnp.arange(cap) - starts[jnp.clip(dst, 0, num_shards - 1)]
        within = jnp.maximum(within, 0)         # dead rows: dst==S drops them
        send = jnp.full((num_shards, cap, arity), PAD, jnp.int32)
        send = send.at[dst, within].set(data, mode="drop")
        recv = jax.lax.all_to_all(send, SHARD_AXIS, split_axis=0,
                                  concat_axis=0)
        flat = recv.reshape(num_shards * cap, arity)
        vflat = None
        if val is not None:
            identity = sr.identity if sr.has_value else 0
            sendv = jnp.full((num_shards, cap), identity, val.dtype)
            sendv = sendv.at[dst, within].set(val, mode="drop")
            recvv = jax.lax.all_to_all(sendv, SHARD_AXIS, split_axis=0,
                                       concat_axis=0)
            vflat = recvv.reshape(num_shards * cap)
        return R.dedupe(flat, vflat, sr, out_cap, backend=backend)


def repartition(rel: Relation, key_cols: tuple[int, ...], sr: Semiring,
                num_shards: int, out_cap: Optional[int] = None,
                backend=None):
    """Repartition a (shard-local view of a) Relation on ``key_cols``."""
    return repartition_rows(rel.data, rel.val, live_mask(rel), key_cols,
                            sr, out_cap or rel.capacity, num_shards,
                            backend=backend)


# -- partitioned relop wrappers ----------------------------------------------

class ShardedEvaluator(Evaluator):
    """The IR evaluator with key-partitioned entry points: every binary
    op repartitions its operands on the operation key (so matching rows
    co-locate), then runs the ordinary shard-local op body. Runs inside
    a shard_map trace over the "shards" mesh axis."""

    def __init__(self, cfg: LowerConfig, num_shards: int):
        super().__init__(cfg)
        self.num_shards = num_shards

    def _repart(self, rel: Relation, key_cols: tuple[int, ...]):
        """All-to-all repartition on the operation key — memoized per
        evaluation pass when the arrangement cache is on, so one
        repartition (collective included) serves every rule/subplan
        keyed the same way on the same operand: the shard-local
        arrangement produced by a repartition survives for the rest of
        the pass instead of being rebuilt per op."""
        key_cols = tuple(key_cols)
        if self.cache is None:
            return repartition(rel, key_cols, self.cfg.semiring,
                               self.num_shards, backend=self.cfg.backend)
        return self.cache.memo(
            ("repart", key_cols), (rel.data, rel.val, rel.n),
            lambda: repartition(rel, key_cols, self.cfg.semiring,
                                self.num_shards,
                                backend=self.cfg.backend))

    def _join_op(self, left, right, l_keys, r_keys, l_out, r_out, out_cap):
        left, ov1 = self._repart(left, l_keys)
        right, ov2 = self._repart(right, r_keys)
        data, val, valid, total, ovj = super()._join_op(
            left, right, l_keys, r_keys, l_out, r_out, out_cap)
        return data, val, valid, total, ovj | ov1 | ov2

    def _semijoin_op(self, left, right, l_keys, r_keys):
        left, right, ov = self._co_partition(left, right, l_keys, r_keys)
        out, ov2 = super()._semijoin_op(left, right, l_keys, r_keys)
        return out, ov | ov2

    def _antijoin_op(self, left, right, l_keys, r_keys):
        left, right, ov = self._co_partition(left, right, l_keys, r_keys)
        out, ov2 = super()._antijoin_op(left, right, l_keys, r_keys)
        return out, ov | ov2

    def _co_partition(self, left, right, l_keys, r_keys):
        """Align semijoin/antijoin operands. Zero-key guards need no
        movement, but the 'is right non-empty?' test must be global —
        substitute the psum'd count (membership only compares n > 0)."""
        if len(l_keys) == 0:
            gn = jax.lax.psum(right.n, SHARD_AXIS)
            return left, Relation(right.data, right.val, gn), (
                jnp.zeros((), bool))
        left, ov1 = self._repart(left, l_keys)
        right, ov2 = self._repart(right, r_keys)
        return left, right, ov1 | ov2

    def _reduce_op(self, child, group_cols, agg_specs, out_cap):
        # group-key partition: every group is fully local (an empty
        # group tuple hashes every row to one shard — the global
        # aggregate case, same capacity requirement as single-device)
        child, ov = self._repart(child, group_cols)
        out, ov2 = super()._reduce_op(child, group_cols, agg_specs,
                                      out_cap)
        return out, ov | ov2
    # dedupe/concat hooks stay shard-local on purpose: cross-shard
    # duplicates of projected rows are eliminated at the next
    # repartition or at the head-row re-home in _merge_head — every op
    # that is duplicate-sensitive repartitions first.


# -- sharded fixpoint driver -------------------------------------------------

class ShardedEngine(Engine):
    """Drop-in Engine that hash-partitions every relation across a 1-D
    device mesh and runs the stratum fixpoint under shard_map. Selected
    via ``EngineConfig.shards >= 2`` (see ``repro.engine.make_engine``);
    composes with any ``kernel_backend``."""

    _sanitize_layer = "shard"

    def __init__(self, compiled: I.CompiledProgram,
                 config: EngineConfig | None = None):
        super().__init__(compiled, config)
        self.num_shards = max(int(self.cfg.shards or 1), 1)
        self.mesh = self.cfg.shard_mesh or make_shard_mesh(self.num_shards)
        # send-buffer slots of one execution of an observed step, over
        # all shards, by the step's memo key at its capacities
        self._wire_slots: dict = {}
        if self.mesh.axis_names != (SHARD_AXIS,):
            raise ValueError(
                f"shard mesh must have the single axis {SHARD_AXIS!r}, "
                f"got {self.mesh.axis_names}")
        if self.mesh.devices.size != self.num_shards:
            raise ValueError(
                f"mesh has {self.mesh.devices.size} devices but "
                f"config.shards={self.num_shards}")

    # -- shard_map plumbing ---------------------------------------------------
    def _shmap(self, f, in_specs=_SPEC, out_specs=_SPEC, jit=True):
        g = jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        return jax.jit(g) if (jit and self.cfg.jit) else g

    def _scatter_env(self, rels: dict) -> dict:
        """Host-built (replicated) Relations -> home-partitioned
        ShardedRelations: each shard keeps the rows whose full-row hash
        lands on it. Stable compaction preserves sortedness. The
        replication to every shard and the compaction run under the span
        ``scatter-edb`` (inside ``edb-load`` for the EDBs, inside
        ``init`` for an IDB's ground facts)."""
        if not rels:
            return {}
        O.count(self.cfg.observe, "shard.scatter_env", len(rels))
        identities = {k: self._sr_of(k[0] if isinstance(k, tuple) else k)
                      for k in rels}

        def scatter(reps):
            idx = jax.lax.axis_index(SHARD_AXIS)
            out = {}
            for k, rel in reps.items():
                live = live_mask(rel)
                dest = shard_of(rel.data, tuple(range(rel.arity)), live,
                                self.num_shards)
                keep = live & (dest == idx)
                sr = identities[k]
                d, v, n, _ = R._scatter_compact(
                    rel.data, rel.val, keep, rel.capacity,
                    sr.identity if sr.has_value else 0)
                out[k] = Relation(
                    d, v if rel.val is not None else None, n)
            return _restack(out)

        # one program per set of relation shapes, so a run that scatters
        # what an earlier run did compiles nothing
        step = self._memo_jit(
            ("shard_scatter",) + tuple(
                (k, r.capacity, r.arity, r.val is not None,
                 identities[k].name) for k, r in rels.items()),
            lambda: self._shmap(scatter, in_specs=PartitionSpec(),
                                jit=False))
        with O.span(self.cfg.observe, "scatter-edb", relations=len(rels)):
            return step(rels)

    def _edb_env(self, edbs, edb_caps) -> dict:
        return self._scatter_env(super()._edb_env(edbs, edb_caps))

    def _host_relation(self, rel) -> Relation:
        """Gather a ShardedRelation back to one host-side Relation.
        Home partitioning keeps rows globally distinct, so this is a
        concat of live blocks + one lexicographic sort — byte-identical
        to the single-device arrangement.

        Capacity is preserved: the gathered relation keeps the per-shard
        capacity (growing only if the combined rows need more). It used
        to be recomputed as next-pow2 of the row count, which silently
        shrank a sparsely-populated relation below its stored ``cap`` —
        a scatter/gather round trip could then overflow on the next
        merge (regression-tested in tests/test_sharded.py)."""
        if isinstance(rel, Relation):
            return rel
        O.count(self.cfg.observe, "shard.host_gathers")
        data = np.asarray(rel.data)
        ns = np.asarray(rel.n)
        rows = np.concatenate(
            [data[s, :ns[s]] for s in range(rel.num_shards)], axis=0)
        vals = None
        if rel.val is not None:
            v = np.asarray(rel.val)
            vals = np.concatenate(
                [v[s, :ns[s]] for s in range(rel.num_shards)], axis=0)
        cap = rel.capacity
        if rows.shape[0] > cap:
            cap = pow2_cap(rows.shape[0])
        return from_numpy(rows, cap, val=vals, dedupe=False)

    # -- stratum execution ----------------------------------------------------
    # (the stratum span comes from Engine._run_stratum, which wraps this
    # body for both drivers)
    def _run_stratum_body(self, sp: I.StratumPlan, env_rels, stats,
                          stratum_key, init_state=None, st_span=None):
        F.fault_point("engine.stratum")
        obs = self.cfg.observe
        cfg = self.cfg
        lcfg = LowerConfig(self.intermediate_cap, cfg.semiring,
                           self.backend, cfg.arrangements)
        ev = ShardedEvaluator(lcfg, self.num_shards)
        monoid_names = set(self.monoid)
        idbs = sorted(sp.idbs)
        # the init and host-mode iteration steps also return their
        # repartitions' live send rows when observation is on
        wired = obs is not None
        init_key = ("shard_init", sp.index, wired)
        iter_key = ("shard_iter", sp.index, wired)

        nonrec = [p for p in sp.plans if p.variant == -1]
        rec = [p for p in sp.plans if p.variant >= 0]

        if init_state is not None:
            # seeded incremental continuation: the stored fulls are
            # already home-partitioned ShardedRelations and the seed
            # deltas arrive in stored form too — the seed merge runs
            # shard-local under shard_map through the exact same
            # _stratum_seed body the single-device engine executes
            # (each shard's block is a valid sorted arrangement, so
            # merge_with_delta applies unchanged per shard).
            given = {}
            for name in idbs:
                full, seed = init_state[name]
                if seed is None:
                    seed = self._stored_empty_idb(name)
                given[name] = (full, seed)

            def seed_fn(given_g):
                state, ovf = self._stratum_seed(
                    _unstack(given_g), idbs, ev)
                return _restack(state), ovf[None]

            with O.span(obs, "seed"):
                seed_step = self._memo_jit(
                    ("shard_seed", sp.index),
                    lambda: self._shmap(seed_fn, jit=False))
                state, ovf = seed_step(given)
                ovf = bool(np.asarray(ovf).any())
        else:
            def init_fn(base_g, init_g):
                base, init = _unstack(base_g), _unstack(init_g)
                with wire_tally(wired) as tally:
                    state, ovf = self._stratum_init(
                        base, init, nonrec, idbs, ev, monoid_names)
                return self._with_wire(
                    init_key, (_restack(state), ovf[None]), tally)

            with O.span(obs, "init", nonrec_rules=len(nonrec)):
                init_rels = self._scatter_env(
                    {name: self._ground_relation(sp, name)
                     for name in idbs})
                init_step = self._memo_jit(
                    init_key, lambda: self._shmap(init_fn, jit=False))
                state, ovf = self._read_wire(
                    init_key, init_step(dict(env_rels), init_rels))
                ovf = bool(np.asarray(ovf).any())
        if ovf:
            raise OverflowError_(f"overflow during init of {stratum_key}")

        if not sp.recursive or not rec:
            full_env = dict(env_rels)
            for name in idbs:
                full_env[(name, I.FULL)] = state[name][0]
            stats.iterations[stratum_key] = 0
            if st_span is not None:
                st_span.attrs["iterations"] = 0
            self._sanitize_env(full_env, f"stratum {stratum_key} boundary")
            return full_env

        stratum_iters = 0
        delta_log = []
        if cfg.mode == "device":
            def device_fn(base_g, state_g):
                base, state0 = _unstack(base_g), _unstack(state_g)

                def cond(carry):
                    _, any_delta, ovf, it = carry
                    return any_delta & (it < cfg.max_iters) & (~ovf)

                def body(carry):
                    st, _, ovf, it = carry
                    ns, ov = self._stratum_iter(
                        st, base, rec, idbs, ev, monoid_names)
                    local_delta = sum(
                        ns[name][1].n for name in idbs)
                    any_delta = jax.lax.psum(
                        local_delta, SHARD_AXIS) > 0
                    ovf_g = jax.lax.psum(
                        (ovf | ov).astype(jnp.int32), SHARD_AXIS) > 0
                    return ns, any_delta, ovf_g, it + 1

                carry = (state0, jnp.array(True), jnp.zeros((), bool),
                         jnp.zeros((), jnp.int32))
                st, _, ovf, iters = jax.lax.while_loop(cond, body, carry)
                return _restack(st), ovf[None], iters[None]

            with O.span(obs, "fixpoint-loop", detail="post-hoc"):
                device_step = self._memo_jit(
                    ("shard_device", sp.index),
                    lambda: self._shmap(device_fn, jit=False))
                state, ovf, iters = device_step(dict(env_rels), state)
                ovf = bool(np.asarray(ovf).any())
                stratum_iters = int(np.asarray(iters)[0])
            if ovf:
                raise OverflowError_(f"overflow in stratum {stratum_key}")
        else:
            def step_fn(state_g, base_g):
                state, base = _unstack(state_g), _unstack(base_g)
                with wire_tally(wired) as tally:
                    ns, ovf = self._stratum_iter(
                        state, base, rec, idbs, ev, monoid_names)
                return self._with_wire(
                    iter_key, (_restack(ns), ovf[None]), tally)

            step = self._memo_jit(iter_key,
                                  lambda: self._shmap(step_fn, jit=False))
            # per-iteration deltas ride the loop's existing per-shard
            # count reads (the [S] sum) — no host syncs added
            sizes = {n: int(np.asarray(state[n][1].n).sum())
                     for n in idbs}
            while not all(v == 0 for v in sizes.values()):
                delta_total = sum(sizes.values())
                delta_log.append(delta_total)
                with O.span(obs, "iteration", index=stratum_iters,
                            delta_rows=delta_total,
                            deltas=dict(sizes) if obs else None):
                    state, ovf = self._read_wire(
                        iter_key, step(state, dict(env_rels)))
                    ovf = bool(np.asarray(ovf).any())
                    sizes = {n: int(np.asarray(state[n][1].n).sum())
                             for n in idbs}
                if ovf:
                    raise OverflowError_(
                        f"overflow in stratum {stratum_key} "
                        f"iter {stratum_iters}")
                stratum_iters += 1
                if stratum_iters >= cfg.max_iters:
                    raise RuntimeError(
                        f"no fixpoint after {cfg.max_iters} iterations")

        def final_fn(state_g):
            state = _unstack(state_g)
            out = {}
            ovf = jnp.zeros((), bool)
            for name in idbs:
                full, delta = state[name]
                merged, ov = R.merge(full, delta, self._sr_of(name),
                                     self._idb_cap(name),
                                     backend=self.backend,
                                     incremental=cfg.arrangements)
                ovf |= ov
                out[name] = merged
            return _restack(out), ovf[None]

        with O.span(obs, "final-merge"):
            final_step = self._memo_jit(
                ("shard_final", sp.index),
                lambda: self._shmap(final_fn, jit=False))
            merged, ovf = final_step(state)
            ovf = bool(np.asarray(ovf).any())
        if ovf:
            raise OverflowError_(f"overflow finalizing {stratum_key}")
        full_env = dict(env_rels)
        for name in idbs:
            full_env[(name, I.FULL)] = merged[name]
        stats.iterations[stratum_key] = stratum_iters
        stats.delta_sizes[stratum_key] = delta_log
        if st_span is not None:
            st_span.attrs["iterations"] = stratum_iters
        self._sanitize_env(full_env, f"stratum {stratum_key} boundary")
        return full_env

    # -- wire counts of an observed run ---------------------------------------
    def _with_wire(self, key: tuple, out: tuple, tally) -> tuple:
        """A step's outputs, with its shard's live send rows appended
        when ``tally`` collected them. The slots are static: every shard
        runs the same buffers, so their total over the shards is kept on
        the host, by the step's memo key at its capacities."""
        if tally is None:
            return out
        self._wire_slots[self._at_caps(key)] = self.num_shards * sum(
            s for _, s in tally)
        live = sum((n for n, _ in tally), jnp.zeros((), jnp.int64))
        return out + (live[None],)

    def _read_wire(self, key: tuple, out: tuple) -> tuple:
        """Strip the live send rows off an observed step's outputs and
        add them, summed over the shards, to the observation's
        ``shard.repartition.live_rows``, and the step's slots to
        ``.slots``."""
        if self.cfg.observe is None:
            return out
        O.count(self.cfg.observe, "shard.repartition.live_rows",
                int(np.asarray(out[-1]).sum()))
        O.count(self.cfg.observe, "shard.repartition.slots",
                self._wire_slots[self._at_caps(key)])
        return out[:-1]

    # -- head merge: re-home derived rows before combining --------------------
    def _merge_head(self, rels: list, sr: Semiring, cap: int):
        data = jnp.concatenate([r.data for r in rels], axis=0)
        val = None
        if sr.has_value:
            val = jnp.concatenate(
                [r.val if r.val is not None
                 else jnp.ones((r.capacity,), sr.dtype) for r in rels])
        live = ~jnp.all(data == PAD, axis=1)
        return repartition_rows(
            data, val, live, tuple(range(data.shape[1])), sr, cap,
            self.num_shards, backend=self.backend)

    # -- maintenance driver hooks (incremental.py runs through these) ---------
    def _maintenance_evaluator(self):
        return ShardedEvaluator(
            LowerConfig(self.intermediate_cap, self.cfg.semiring,
                        self.backend, self.cfg.arrangements),
            self.num_shards)

    def run_rule_pass(self, env_rels, roots, restrict=None,
                      memo_key=None, context: str = "") -> dict:
        """Sharded maintenance pass: the shared ``_rule_pass_body``
        runs inside shard_map with the key-partitioned evaluator, so
        every retagged rule occurrence repartitions its operands on the
        operation key exactly like the batch fixpoint, and
        ``_merge_head`` re-homes derived rows before the per-head
        union. Inputs must already be in stored (sharded) form — see
        ``_stored``. ``memo_key`` (structure of the pass) enables the
        same cross-update trace reuse as the single-device driver.
        The fault site shares the single-device driver's name, so one
        fault plan is portable across shard counts."""
        F.fault_point("engine.rule_pass")
        ev = self._maintenance_evaluator()
        restrict = dict(restrict or {})

        def pass_fn(rels_g, restrict_g):
            derived, ovf = self._rule_pass_body(
                _unstack(rels_g), roots, _unstack(restrict_g), ev)
            return _restack(derived), ovf[None]

        if memo_key is None:
            step = self._shmap(pass_fn)
        else:
            step = self._memo_jit(("rule_pass",) + tuple(memo_key),
                                  lambda: self._shmap(pass_fn, jit=False))
        derived, ovf = step(dict(env_rels), restrict)
        if bool(np.asarray(ovf).any()):
            raise OverflowError_(
                self._overflow_msg("incremental rule pass", context))
        return derived

    def _stored(self, rels: dict) -> dict:
        """Scatter host-built Relations to their home shards; entries
        already in sharded form pass through unchanged."""
        host = {k: v for k, v in rels.items()
                if not isinstance(v, ShardedRelation)}
        scattered = self._scatter_env(host) if host else {}
        return {k: scattered.get(k, rels[k]) for k in rels}

    def _stored_empty_idb(self, name: str) -> ShardedRelation:
        e = self._empty_idb(name)
        s = self.num_shards
        return ShardedRelation(
            jnp.tile(e.data[None], (s, 1, 1)),
            jnp.tile(e.val[None], (s, 1)) if e.val is not None else None,
            jnp.zeros((s,), jnp.int32))

    def _difference_stored(self, rel, sub):
        """Shard-local set difference: both operands are home-partitioned
        by full-row hash, so equal rows co-locate and no repartition is
        needed (the DRed candidate-removal step)."""
        def diff_fn(pair_g):
            a, b = _unstack(pair_g)
            out, _ = R.difference(a, b, backend=self.backend)
            return _to_global(out)

        return self._shmap(diff_fn)((rel, sub))

    def _union_stored(self, rels: list, sr: Semiring, cap: int,
                      context: str = ""):
        """Shard-local union of home-partitioned relations (duplicates
        co-locate, so concat + dedupe needs no communication); ``cap``
        is the per-shard capacity, as ``_scatter_env`` gives it."""
        def union_fn(rels_g):
            out, ov = R.concat_all(_unstack(rels_g), sr, cap,
                                   backend=self.backend)
            return _to_global(out), ov[None]

        step = self._memo_jit(
            ("shard_union_stored", sr.name, cap)
            + tuple((r.capacity, r.arity) for r in rels),
            lambda: self._shmap(union_fn, jit=False))
        out, ov = step(list(rels))
        if bool(np.asarray(ov).any()):
            raise OverflowError_(self._overflow_msg(
                "maintenance union", context))
        return out
