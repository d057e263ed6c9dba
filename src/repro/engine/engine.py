"""Semi-naive, stratum-by-stratum fixpoint engine (paper Sec. 2.2, 3).

Two execution modes:

* ``host``   — Python drives the iteration loop; each iteration is one
  jitted, donated step function. Mirrors the per-iteration structure of
  the paper's executor, surfaces per-iteration stats (delta sizes) and
  allows capacity-overflow retry mid-stratum. Default for CPU runs.
* ``device`` — the whole stratum fixpoint is a single
  ``jax.lax.while_loop``; the TPU deployment path (no host syncs; the
  paper's criticism of RecStep's cross-iteration synchronization applies
  to host mode at scale). Used by tests to validate equivalence and by
  the dry-run to lower the engine under a mesh.

Both share one iteration body built from the optimized IR bundle.
Capacity overflow (bounded join outputs; relation.py) raises a retry
from the host with doubled capacities (``auto_grow``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ir as I
from repro.engine import faults as F
from repro.engine import observe as O
from repro.engine import relops as R
from repro.engine.backend import KernelDispatch, resolve_backend
from repro.engine.lower import Env, Evaluator, LowerConfig
from repro.engine.relation import (
    Relation, UNSORTED, empty, from_numpy, live_mask, pow2_cap,
    to_numpy, to_numpy_with_val,
)
from repro.engine.semiring import (
    PRESENCE, Semiring, monoid_for,
)


@dataclass
class EngineConfig:
    idb_cap: int = 1 << 14
    idb_caps: dict = field(default_factory=dict)      # per-IDB override
    intermediate_cap: int = 1 << 15
    max_iters: int = 100_000
    mode: str = "host"            # host | device
    auto_grow: bool = True
    max_grow_retries: int = 8
    semiring: Semiring = PRESENCE  # execution algebra (Sec. 8)
    jit: bool = True
    # physical backend for probe/reduce hot ops (engine/backend.py):
    # "auto" (Pallas on TPU, jnp elsewhere) | "pallas" (TPU only) |
    # "pallas-interpret" | "jnp";
    # a KernelDispatch instance is also accepted. Resolved once at
    # engine construction.
    kernel_backend: str = "auto"
    # arrangement layer (relation.py docstring): share arrangements
    # across rules/subplans per iteration (relops.ArrangementCache),
    # skip no-op arranges via the sort-order witness, and maintain
    # full arrangements incrementally (relops.merge_sorted) instead of
    # concat + re-sort. False restores the seed sort-per-op engine —
    # byte-identical fixpoints either way (tests/test_arrange.py).
    arrangements: bool = True
    # sharded execution (engine/shard.py): number of hash partitions /
    # devices on the 1-D fixpoint mesh. 0 or 1 = single-device Engine;
    # >= 2 selects ShardedEngine via ``repro.engine.make_engine``.
    # ``shard_mesh`` optionally supplies a prebuilt 1-D Mesh whose sole
    # axis is named "shards" (defaults to launch.mesh.make_shard_mesh).
    shards: int = 0
    shard_mesh: object = None
    # runtime arrangement sanitizer (core/analysis/sanitize.py): pull
    # every stored relation to the host at each stratum boundary (and
    # after incremental apply) and validate the relation.py arrangement
    # contract — sort-order witnesses vs actual data, PAD tails,
    # distinctness, shard homing. False disables; True checks every
    # boundary (O(rows) host transfers — debug only); an int N >= 2
    # samples every Nth boundary, cheap enough to leave on in the
    # durable serving path (engine/resilience.py).
    check_invariants: "bool | int" = False
    # observability (engine/observe.py): attach an ``Observation`` to
    # record the span tree of every run/apply (strata, iterations, rule
    # passes, memo-jit and grow events) plus run-scoped metrics. None
    # (the default) short-circuits every hook — byte-identical
    # fixpoints, no host syncs added inside jitted steps either way
    # (tests/test_observe.py pins this).
    observe: Optional["O.Observation"] = None


@dataclass
class EngineStats:
    iterations: dict = field(default_factory=dict)    # stratum -> n_iters
    delta_sizes: dict = field(default_factory=dict)   # stratum -> [sizes]
    wall_s: float = 0.0
    grow_retries: int = 0
    total_facts: dict = field(default_factory=dict)
    # the capacities the run actually completed at (== the config caps
    # unless auto-grow retried; see Engine.effective_caps)
    effective_caps: dict = field(default_factory=dict)

    @property
    def total_iterations(self) -> int:
        return sum(self.iterations.values())


class OverflowError_(RuntimeError):
    pass


class Engine:
    """Executes a CompiledProgram over EDB data."""

    def __init__(self, compiled: I.CompiledProgram,
                 config: EngineConfig | None = None):
        self.compiled = compiled
        self.cfg = config or EngineConfig()
        self.backend: KernelDispatch = resolve_backend(
            self.cfg.kernel_backend)
        self.monoid: dict[str, tuple[Semiring, int]] = {}
        for name, (func, vpos) in compiled.monoid_idbs.items():
            self.monoid[name] = (monoid_for(func), vpos)
        # jitted stratum step functions, memoized across runs/updates
        # (see _memo_jit) — an update stream re-executes the same
        # compiled step instead of re-tracing it per update
        self._jit_memo: dict = {}
        # structural key -> last full (capacity-qualified) key, to spot
        # auto-grow retraces for the observability layer
        self._jit_base_seen: dict = {}
        # effective capacities: attempt-local growth state. run()'s
        # auto-grow doubles THESE (and restores the entry caps on
        # success/failure) — cfg is never mutated, so grown capacity no
        # longer leaks into every later run and memo-jit key. The
        # resilience layer (engine/resilience.py) owns persistent cap
        # changes via set_caps.
        self._intermediate_cap = int(self.cfg.intermediate_cap)
        self._idb_cap_default = int(self.cfg.idb_cap)
        self._idb_caps = dict(self.cfg.idb_caps)
        # stratum-boundary counter for the sanitizer's sampling mode
        self._sanitize_count = 0

    def _memo_jit(self, key: tuple, make):
        """Memoize a jitted stratum function across run()/apply() calls.

        The closures handed in depend only on the stratum plan and the
        engine capacities, so one compiled step serves every batch run
        AND every incremental update at the same capacities — this is
        what makes per-update maintenance latency a steady-state
        execute instead of a fresh trace each time. Capacity changes
        (auto_grow) change the key and re-trace; ``cfg.jit=False``
        bypasses the memo entirely.

        Observability: counts ``memo_jit.hit`` / ``.miss`` / ``.retrace``
        on the attached observation's registry (retrace = a structural
        key already compiled at other capacities — an auto-grow
        recompile)."""
        if not self.cfg.jit:
            return make()
        obs = self.cfg.observe
        base = key
        key = key + (self._intermediate_cap, self._idb_cap_default,
                     tuple(sorted(self._idb_caps.items())))
        fn = self._jit_memo.get(key)
        if fn is None:
            if obs is not None:
                obs.registry.inc("memo_jit.miss")
                if self._jit_base_seen.get(base, key) != key:
                    obs.registry.inc("memo_jit.retrace")
            self._jit_base_seen[base] = key
            fn = jax.jit(make())
            self._jit_memo[key] = fn
        else:
            O.count(obs, "memo_jit.hit")
        return fn

    # -- effective capacities -------------------------------------------------
    @property
    def intermediate_cap(self) -> int:
        return self._intermediate_cap

    def _idb_cap(self, name: str) -> int:
        return int(self._idb_caps.get(name, self._idb_cap_default))

    def effective_caps(self) -> dict:
        """Snapshot of the capacities the engine currently executes at
        (== config caps unless grown by run()'s retry or set_caps)."""
        return {"intermediate_cap": self._intermediate_cap,
                "idb_cap": self._idb_cap_default,
                "idb_caps": dict(self._idb_caps)}

    def set_caps(self, caps: dict) -> None:
        """Install effective capacities (the resilience layer's entry
        point for persistent capacity changes; run() uses it to restore
        its entry caps after an auto-grow attempt)."""
        self._intermediate_cap = int(
            caps.get("intermediate_cap", self._intermediate_cap))
        self._idb_cap_default = int(
            caps.get("idb_cap", self._idb_cap_default))
        if "idb_caps" in caps:
            self._idb_caps = {k: int(v)
                              for k, v in caps["idb_caps"].items()}

    def grow_caps(self, factor: int = 2) -> dict:
        """Multiply every effective capacity; returns the new caps."""
        self._intermediate_cap *= factor
        self._idb_cap_default *= factor
        self._idb_caps = {k: v * factor for k, v in self._idb_caps.items()}
        return self.effective_caps()

    def _overflow_msg(self, what: str, context: str = "") -> str:
        caps = self.effective_caps()
        ctx = f" [{context}]" if context else ""
        msg = (f"overflow in {what}{ctx}: "
               f"intermediate_cap={caps['intermediate_cap']} "
               f"idb_cap={caps['idb_cap']}")
        if caps["idb_caps"]:
            msg += f" idb_caps={caps['idb_caps']}"
        return msg

    # -- helpers -------------------------------------------------------------

    def _sr_of(self, name: str) -> Semiring:
        if name in self.monoid:
            return self.monoid[name][0]
        return self.cfg.semiring

    def _stored_arity(self, name: str) -> int:
        a = self.compiled.arities[name]
        if name in self.monoid:
            a -= 1
        return max(a, 1)

    def _empty_idb(self, name: str) -> Relation:
        sr = self._sr_of(name)
        return empty(self._idb_cap(name), self._stored_arity(name),
                     sr.identity if sr.has_value else None)

    def _split_monoid(self, name: str, rel: Relation) -> Relation:
        """Derived plan outputs carry the lattice value as a data column;
        split it into the val payload (Sec. 9)."""
        if name not in self.monoid:
            return rel
        sr, vpos = self.monoid[name]
        data_cols = [c for c in range(rel.arity) if c != vpos]
        data = rel.data[:, jnp.array(data_cols)]
        val = jnp.where(live_mask(rel), rel.data[:, vpos], sr.identity)
        # a column-subset view loses the sort guarantee: rows sorted by
        # all columns need not stay sorted with vpos removed — mark it
        # so no arrangement fast path can trust this relation
        return Relation(data, val.astype(jnp.int32), rel.n,
                        order=UNSORTED)

    # -- plan evaluation ------------------------------------------------------
    def _merge_head(self, rels: list, sr: Semiring, cap: int):
        """Combine all derived relations for one head IDB into a single
        sorted distinct relation. Overridden by ShardedEngine to first
        repartition rows to the head's home shard (equal rows must
        co-locate before the duplicate-combine)."""
        if len(rels) == 1:
            return R.dedupe(rels[0].data, rels[0].val, sr, cap,
                            backend=self.backend)
        return R.concat_all(rels, sr, cap, backend=self.backend)

    def _rule_phase(self) -> str:
        """How to read per-rule span durations: under jit rule bodies
        execute while *tracing* (once per compilation), so spans measure
        trace/lowering cost + launch-counter attribution; with
        ``jit=False`` they measure real execution."""
        return "trace" if self.cfg.jit else "eval"

    def _eval_plans(self, plans, env: Env, ev: Evaluator):
        """Evaluate plans, concat per head IDB -> derived relations."""
        obs = self.cfg.observe
        by_head: dict[str, list[Relation]] = {}
        for p in plans:
            with O.span(obs, "rule", head=p.head,
                        rule=("nonrec" if p.variant < 0
                              else f"v{p.variant}"),
                        phase=self._rule_phase()):
                rel = ev.eval(p.root, env)
                rel = self._split_monoid(p.head, rel)
            by_head.setdefault(p.head, []).append(rel)
        out: dict[str, Relation] = {}
        for head, rels in by_head.items():
            merged, ov = self._merge_head(
                rels, self._sr_of(head), self._idb_cap(head))
            env.overflow = env.overflow | ov
            out[head] = merged
        return out

    def export_monoid(self, name: str, rel: Relation) -> np.ndarray:
        """Re-attach a monoid IDB's lattice value as a column."""
        data, val = to_numpy_with_val(rel)
        _, vpos = self.monoid[name]
        cols = []
        di = 0
        for c in range(self.compiled.arities[name]):
            if c == vpos:
                cols.append(val)
            else:
                cols.append(data[:, di])
                di += 1
        return np.stack(cols, axis=1) if cols else data

    # -- shared stratum bodies (also run inside shard_map by ShardedEngine) ---
    def _ground_relation(self, sp: I.StratumPlan, name: str) -> Relation:
        """Ground facts for one IDB as a host-built Relation."""
        facts = sp.facts.get(name, [])
        sr = self._sr_of(name)
        if not facts:
            return self._empty_idb(name)
        arr = np.array(facts, dtype=np.int64)
        if name in self.monoid:
            _, vpos = self.monoid[name]
            vals = arr[:, vpos]
            dcols = [c for c in range(arr.shape[1]) if c != vpos]
            arr = arr[:, dcols] if dcols else np.zeros(
                (len(vals), 1), np.int64)
            return from_numpy(
                arr, self._idb_cap(name), val=vals,
                val_identity=sr.identity, dedupe=False)
        if arr.shape[1] == 0:
            arr = np.zeros((arr.shape[0], 1), np.int64)
        return from_numpy(arr, self._idb_cap(name))

    def _stratum_init(self, rels, init_rels, nonrec, idbs, ev,
                      monoid_names):
        """Facts + nonrecursive rules once -> initial (full, delta)."""
        cache = ev.begin_pass()
        env = Env(dict(rels), self.compiled.shared, monoid_names)
        derived = self._eval_plans(nonrec, env, ev)
        state = {}
        for name in idbs:
            full0 = init_rels[name]
            if name in derived:
                sr = self._sr_of(name)
                full0, delta0, ov = R.merge_with_delta(
                    full0, derived[name], sr, self._idb_cap(name),
                    backend=self.backend, cache=cache,
                    incremental=self.cfg.arrangements)
                env.overflow = env.overflow | ov
            else:
                delta0 = full0
            state[name] = (full0, delta0)
        return state, env.overflow

    def _stratum_iter(self, state, base, rec, idbs, ev, monoid_names):
        """One semi-naive iteration -> (new_state, overflow).

        Arrangement lifecycle: one ``ArrangementCache`` spans the whole
        iteration (the merge of full+delta, every rule/subplan arrange,
        and the frontier difference), created here in host mode's
        per-iteration step and inside the while_loop body in device
        mode — under jit either way this is one cache per compiled
        step, so each distinct (relation, key) sorts at most once per
        iteration."""
        cache = ev.begin_pass()
        inc = self.cfg.arrangements
        env_rels = dict(base)
        ovf = jnp.zeros((), bool)
        for name in idbs:
            full, delta = state[name]
            sr = self._sr_of(name)
            full_new, ov = R.merge(full, delta, sr, self._idb_cap(name),
                                   backend=self.backend,
                                   incremental=inc)
            ovf |= ov
            env_rels[(name, I.FULL)] = full
            env_rels[(name, I.FULL_OLD)] = full
            env_rels[(name, I.DELTA)] = delta
            env_rels[(name, I.FULL_NEW)] = full_new
        env = Env(env_rels, self.compiled.shared, monoid_names)
        derived = self._eval_plans(rec, env, ev)
        new_state = {}
        for name in idbs:
            sr = self._sr_of(name)
            full_new = env_rels[(name, I.FULL_NEW)]
            if name in derived:
                nf, nd, ov = R.merge_with_delta(
                    full_new, derived[name], sr, self._idb_cap(name),
                    backend=self.backend, cache=cache,
                    incremental=inc)
                ovf |= ov
            else:
                nf = full_new
                nd = self._empty_idb(name)
            new_state[name] = (nf, nd)
        return new_state, ovf | env.overflow

    def _stratum_seed(self, given, idbs, ev):
        """Seeded semi-naive continuation entry: merge each IDB's seed
        delta into its stored full arrangement -> (full, delta) state.
        Shared per-shard body — ``ShardedEngine`` runs it inside
        shard_map, so a seeded continuation executes identical code on
        one device and on every shard. The stored fulls are still
        sorted arrangements, so the seed merge is the incremental
        ``merge_sorted`` path (no re-sort of the materialized state)."""
        cache = ev.begin_pass()
        state = {}
        ovf = jnp.zeros((), bool)
        for name in idbs:
            full, seed = given[name]
            sr = self._sr_of(name)
            if seed is None:
                state[name] = (full, self._empty_idb(name))
            else:
                nf, nd, ov = R.merge_with_delta(
                    full, seed, sr, self._idb_cap(name),
                    backend=self.backend, cache=cache,
                    incremental=self.cfg.arrangements)
                ovf |= ov
                state[name] = (nf, nd)
        return state, ovf

    def _rule_pass_body(self, rels, roots, restrict, ev):
        """Shared maintenance-pass body (incremental.py): evaluate
        pre-retagged rule roots against the stored relations, union the
        results per head (``_merge_head`` re-homes rows in the sharded
        driver), and optionally restrict a head to candidate rows via
        the evaluator's semijoin hook (which co-partitions under
        sharding). One arrangement scope spans the whole pass, so every
        retagged occurrence shares the stored fulls' arrangements."""
        obs = self.cfg.observe
        ev.begin_pass()
        env = Env(dict(rels), self.compiled.shared, set(self.monoid))
        by_head: dict[str, list[Relation]] = {}
        for head, root in roots:
            with O.span(obs, "rule", head=head, rule="maintenance",
                        phase=self._rule_phase()):
                out = ev.eval(root, env)
                split = self._split_monoid(head, out)
            by_head.setdefault(head, []).append(split)
        derived: dict[str, Relation] = {}
        for head, outs in by_head.items():
            merged, ov = self._merge_head(
                outs, self._sr_of(head), self._idb_cap(head))
            env.overflow = env.overflow | ov
            cand = restrict.get(head)
            if cand is not None:
                cols = tuple(range(merged.arity))
                merged, ov2 = ev._semijoin_op(merged, cand, cols, cols)
                env.overflow = env.overflow | ov2
            derived[head] = merged
        return derived, env.overflow

    # -- maintenance driver hooks (single-device; ShardedEngine overrides) ----
    def _maintenance_evaluator(self) -> Evaluator:
        return Evaluator(LowerConfig(
            self.intermediate_cap, self.cfg.semiring, self.backend,
            self.cfg.arrangements))

    def run_rule_pass(self, env_rels, roots, restrict=None,
                      memo_key=None, context: str = "") -> dict:
        """Driver entry for an incremental maintenance pass: ``roots``
        is a list of (head, retagged IR) pairs; ``env_rels`` maps
        (name, version) to stored relations (including any
        changed-occurrence entries); ``restrict`` optionally maps a
        head to a candidate relation its result is semijoined with.
        Returns head -> stored relation.

        ``memo_key`` must uniquely determine the *structure* of the
        pass (which rules, which retagged occurrences, which restrict
        heads — the callers derive it from the stratum index and the
        changed-relation names); when given, the traced pass is
        memo-jitted so a stream of updates touching the same relations
        re-executes one compiled pass instead of re-tracing.

        ``context`` (stratum key + pass name from the caller) is folded
        into the overflow message alongside the current capacities so a
        maintenance overflow is traceable."""
        F.fault_point("engine.rule_pass")
        restrict = restrict or {}
        ev = self._maintenance_evaluator()

        def pass_fn(rels, rs):
            return self._rule_pass_body(rels, roots, rs, ev)

        if memo_key is None:
            derived, ovf = pass_fn(dict(env_rels), restrict)
        else:
            fn = self._memo_jit(("rule_pass",) + tuple(memo_key),
                                lambda: pass_fn)
            derived, ovf = fn(dict(env_rels), restrict)
        if bool(np.asarray(ovf).any()):
            raise OverflowError_(
                self._overflow_msg("incremental rule pass", context))
        return derived

    def _stored(self, rels: dict) -> dict:
        """Host-built Relations -> this driver's storage form (identity
        here; ShardedEngine scatters each to its home shards)."""
        return rels

    def _stored_empty_idb(self, name: str):
        return self._empty_idb(name)

    def _difference_stored(self, rel, sub):
        """Stored-form set difference (DRed candidate removal)."""
        out, _ = R.difference(rel, sub, backend=self.backend)
        return out

    def _union_stored(self, rels: list, sr: Semiring, cap: int,
                      context: str = ""):
        """Stored-form union (combining maintenance seed sets)."""
        out, ov = R.concat_all(rels, sr, cap, backend=self.backend)
        if bool(np.asarray(ov).any()):
            raise OverflowError_(self._overflow_msg(
                "maintenance seed union", context))
        return out

    # -- runtime invariant sanitizer (core/analysis/sanitize.py) ---------------
    _sanitize_layer = "engine"

    def _sanitize_due(self) -> bool:
        """cfg.check_invariants gate: False disables, True checks every
        stratum boundary, an int N >= 2 samples every Nth boundary
        (the counter spans runs AND incremental applies, so a serving
        loop amortizes the O(rows) host transfers across updates)."""
        ci = self.cfg.check_invariants
        if not ci:
            return False
        self._sanitize_count += 1
        n = 1 if ci is True else int(ci)
        return n <= 1 or self._sanitize_count % n == 0

    def _sanitize_env(self, env, where: str) -> None:
        """Validate every stored arrangement against device data when
        cfg.check_invariants is set (lazy import: sanitize is layered
        above the engine)."""
        if not self._sanitize_due():
            return
        from repro.core.analysis.sanitize import sanitize_env
        sanitize_env(self, env, where, self._sanitize_layer)

    # -- stratum execution ----------------------------------------------------
    def _run_stratum(self, sp: I.StratumPlan, env_rels, stats,
                     stratum_key, init_state=None):
        with O.span(self.cfg.observe, "stratum", key=stratum_key,
                    mode=self.cfg.mode,
                    recursive=bool(sp.recursive)) as st_span:
            return self._run_stratum_body(
                sp, env_rels, stats, stratum_key, init_state, st_span)

    def _run_stratum_body(self, sp: I.StratumPlan, env_rels, stats,
                          stratum_key, init_state=None, st_span=None):
        F.fault_point("engine.stratum")
        base_env_rels = env_rels
        obs = self.cfg.observe
        cfg = self.cfg
        lcfg = LowerConfig(self.intermediate_cap, cfg.semiring,
                           self.backend, cfg.arrangements)
        ev = Evaluator(lcfg)
        monoid_names = set(self.monoid)

        idbs = sorted(sp.idbs)
        # ground facts
        init_rels = {name: self._ground_relation(sp, name)
                     for name in idbs}

        nonrec = [p for p in sp.plans if p.variant == -1]
        rec = [p for p in sp.plans if p.variant >= 0]

        # -- init: facts + nonrecursive rules once
        def init_fn(rels):
            return self._stratum_init(
                rels, init_rels, nonrec, idbs, ev, monoid_names)

        if init_state is not None:
            # incremental continuation: merge seed deltas into given
            # fulls (shared body; ShardedEngine runs it under shard_map).
            # None-seeds are part of the pytree structure, so the memo
            # retraces automatically when a different IDB subset is
            # seeded.
            with O.span(obs, "seed"):
                seed_step = self._memo_jit(
                    ("seed", sp.index),
                    lambda: lambda given: self._stratum_seed(
                        given, idbs, ev))
                state, ovf = seed_step(init_state)
                ovf = bool(ovf)
        else:
            with O.span(obs, "init", nonrec_rules=len(nonrec)):
                init_jit = self._memo_jit(("init", sp.index),
                                          lambda: init_fn)
                state, ovf = init_jit(dict(base_env_rels))
                ovf = bool(ovf)
        if ovf:
            raise OverflowError_(f"overflow during init of {stratum_key}")

        if not sp.recursive or not rec:
            full_env = dict(base_env_rels)
            for name in idbs:
                full_env[(name, I.FULL)] = state[name][0]
            stats.iterations[stratum_key] = 0
            if st_span is not None:
                st_span.attrs["iterations"] = 0
            self._sanitize_env(full_env, f"stratum {stratum_key} boundary")
            return full_env

        # -- one semi-naive iteration
        def iter_fn(state, base):
            new_state, ovf = self._stratum_iter(
                state, base, rec, idbs, ev, monoid_names)
            any_delta = jnp.stack(
                [new_state[n][1].n > 0 for n in idbs]).any()
            return new_state, any_delta, ovf

        stratum_iters = 0
        delta_log = []
        if cfg.mode == "device":
            def cond(carry):
                state, any_delta, ovf, it = carry
                return any_delta & (it < cfg.max_iters) & (~ovf)

            # base env is an argument (not a closure capture) so the
            # memoized compiled loop serves every run/update — same
            # shape as the sharded driver's device_fn
            def run(carry, base):
                def body(c):
                    st, _, ovf, it = c
                    ns, nd, ov = iter_fn(st, base)
                    return ns, nd, ovf | ov, it + 1
                return jax.lax.while_loop(cond, body, carry)

            carry = (state, jnp.array(True), jnp.zeros((), bool),
                     jnp.zeros((), jnp.int32))
            with O.span(obs, "fixpoint-loop", detail="post-hoc"):
                run_step = self._memo_jit(("device", sp.index),
                                          lambda: run)
                state, _, ovf, iters = run_step(carry,
                                                dict(base_env_rels))
                ovf = bool(ovf)
                stratum_iters = int(iters)
            if ovf:
                raise OverflowError_(f"overflow in stratum {stratum_key}")
        else:
            step = self._memo_jit(("iter", sp.index), lambda: iter_fn)
            # per-iteration delta cardinalities come from the SAME
            # ``int(delta.n)`` reads the host loop has always used for
            # termination — observe-on adds no host syncs to the step
            sizes = {n: int(state[n][1].n) for n in idbs}
            while not all(v == 0 for v in sizes.values()):
                delta_total = sum(sizes.values())
                delta_log.append(delta_total)
                with O.span(obs, "iteration", index=stratum_iters,
                            delta_rows=delta_total,
                            deltas=dict(sizes) if obs else None):
                    state, any_delta, ovf = step(state, base_env_rels)
                    ovf = bool(ovf)
                    sizes = {n: int(state[n][1].n) for n in idbs}
                if ovf:
                    raise OverflowError_(
                        f"overflow in stratum {stratum_key} "
                        f"iter {stratum_iters}")
                stratum_iters += 1
                if stratum_iters >= cfg.max_iters:
                    raise RuntimeError(
                        f"no fixpoint after {cfg.max_iters} iterations")

        # final merge (loop exits with delta possibly nonempty in device
        # mode only at max_iters; normally a no-op)
        with O.span(obs, "final-merge"):
            full_env = dict(base_env_rels)
            for name in idbs:
                full, delta = state[name]
                sr = self._sr_of(name)
                merged, ov = R.merge(full, delta, sr,
                                     self._idb_cap(name),
                                     backend=self.backend,
                                     incremental=cfg.arrangements)
                if bool(ov):
                    raise OverflowError_(f"overflow finalizing {name}")
                full_env[(name, I.FULL)] = merged
        stats.iterations[stratum_key] = stratum_iters
        stats.delta_sizes[stratum_key] = delta_log
        if st_span is not None:
            st_span.attrs["iterations"] = stratum_iters
        self._sanitize_env(full_env, f"stratum {stratum_key} boundary")
        return full_env

    # -- public ---------------------------------------------------------------
    def run(self, edbs: dict[str, np.ndarray],
            edb_caps: Optional[dict] = None) -> tuple[dict, EngineStats]:
        """Evaluate the program. Returns ({relation: np.ndarray}, stats).
        Monoid IDBs come back with the value re-attached as a column.

        Capacity-overflow retries grow the *effective* caps (attempt-
        local state; cfg is never mutated) and restore the entry caps
        when run() returns — the capacities the run completed at are
        recorded in ``stats.effective_caps``. Persistent growth is the
        resilience layer's decision (engine/resilience.py adopts
        ``stats.effective_caps`` via ``set_caps`` when it wants the
        grown capacity to stick)."""
        entry_caps = self.effective_caps()
        attempt = 0
        try:
            while True:
                try:
                    out, stats = self._run_once(edbs, edb_caps)
                    stats.grow_retries = attempt
                    stats.effective_caps = self.effective_caps()
                    return out, stats
                except OverflowError_:
                    attempt += 1
                    if not self.cfg.auto_grow or (
                            attempt > self.cfg.max_grow_retries):
                        raise
                    grown = self.grow_caps()
                    obs = self.cfg.observe
                    if obs is not None:
                        obs.registry.inc("engine.grow_retries")
                        obs.event(
                            "grow-retry", attempt=attempt,
                            intermediate_cap=grown["intermediate_cap"],
                            idb_cap=grown["idb_cap"])
        finally:
            self.set_caps(entry_caps)

    def _edb_env(self, edbs, edb_caps) -> dict:
        """Host EDB arrays -> (name, FULL) Relation environment."""
        env_rels: dict[tuple[str, str], Relation] = {}
        for name in self.compiled.edbs:
            arity = max(self.compiled.arities.get(name, 1), 1)
            data = np.asarray(edbs.get(name, np.zeros((0, arity))))
            if data.ndim == 1:
                data = data[:, None]
            if data.shape[1] == 0:
                data = np.zeros((data.shape[0], 1), np.int64)
            if data.shape[1] != arity:
                raise ValueError(
                    f"EDB {name}: expected arity {arity}, "
                    f"got {data.shape[1]}")
            cap = (edb_caps or {}).get(name, pow2_cap(data.shape[0]))
            env_rels[(name, I.FULL)] = from_numpy(data, cap)
        return env_rels

    def _host_relation(self, rel) -> Relation:
        """Bring an environment relation back to a single host-side
        Relation (identity here; ShardedEngine gathers)."""
        return rel

    def _export(self, env_rels, stats) -> dict:
        out: dict[str, np.ndarray] = {}
        for name in self.compiled.arities:
            key = (name, I.FULL)
            if key not in env_rels:
                continue
            rel = self._host_relation(env_rels[key])
            if name in self.monoid:
                out[name] = self.export_monoid(name, rel)
            else:
                out[name] = to_numpy(rel)
            stats.total_facts[name] = out[name].shape[0]
        return out

    def _run_once(self, edbs, edb_caps):
        F.fault_point("engine.run")
        t0 = time.perf_counter()
        stats = EngineStats()
        with O.span(self.cfg.observe, "run",
                    strata=len(self.compiled.strata),
                    mode=self.cfg.mode, shards=self.cfg.shards or 1,
                    backend=type(self.backend).__name__):
            with O.span(self.cfg.observe, "edb-load"):
                env_rels = self._edb_env(edbs, edb_caps)

            for sp in self.compiled.strata:
                env_rels = self._run_stratum(
                    sp, env_rels, stats, f"s{sp.index}")

            with O.span(self.cfg.observe, "export"):
                out = self._export(env_rels, stats)
        stats.wall_s = time.perf_counter() - t0
        self.last_env = env_rels
        return out, stats
