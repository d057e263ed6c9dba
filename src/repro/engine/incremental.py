"""Incremental Datalog maintenance (paper Sec. 9 'Algebraic Semantics')
— the sharded-maintenance contract.

FlowLog supports both batch and incremental execution from the same IR.
This module maintains materialized IDBs under EDB insertions/deletions,
on one device or hash-partitioned across a shard mesh: the engine under
maintenance is whatever ``repro.engine.make_engine`` selects from the
config (``shards >= 2`` -> ``ShardedEngine``), and every maintenance
pass executes the same per-shard code the batch fixpoint runs.

Maintenance algorithm
=====================

* **Stratum pruning** — only strata downstream of a changed relation are
  touched (dependency closure over the stratified program). The pruning
  and retag logic here is pure IR manipulation, independent of where
  rows live; the data passes all go through driver hooks.
* **Insertions** — seeded semi-naive continuation: every derivation
  using at least one inserted tuple is produced by re-evaluating each
  rule with one changed-relation occurrence retagged to scan only the
  inserted rows (``retag_scans``); the resulting seed delta then drives
  the normal semi-naive loop from the existing fixpoint
  (``Engine._stratum_seed``). Sound and complete for set semantics.
* **Deletions** — delete/re-derive (DRed, simplified): over-approximate
  deletable facts with the same seed trick against the *old* state,
  remove them, then re-derive survivors from the reduced state and
  continue to fixpoint. Monoid (MIN/MAX) IDBs fall back to stratum
  recompute on deletion — lattice values cannot be 'un-improved'
  without support counting (documented limitation); the recompute runs
  through the same driver (``_run_stratum``), so it too executes
  sharded when the engine is sharded.

Sharded-maintenance contract
============================

What stays **shard-local** (no communication): the seed merge into the
stored fulls (``merge_with_delta`` per shard block — every block is a
valid sorted arrangement), the semi-naive frontier differences, the
DRed candidate removal (``_difference_stored``) and seed-set unions
(``_union_stored``) — all of these key rows on every stored column, and
home partitioning co-locates equal rows by full-row hash.

What **repartitions** (all-to-all on the operation key): the joins /
semijoins / reduces inside a retagged rule pass, exactly as in the
batch fixpoint (``ShardedEvaluator``); derived head rows are re-homed
by full output row before the per-head union (``_merge_head``). The
DRed candidate/re-derive loop and the ``any_delta`` fixpoint test
aggregate across shards with a one-scalar psum.

What stays **host-side**: the EDB multiset mirror (``self.edbs``), the
IR retagging, the DRed candidate frontier sets (small, bounded by the
over-deletion), and the stratum-pruning closure. Stored fulls stay
``ShardedRelation``s across the whole update stream — state is gathered
to one host only in numpy export (``snapshot``/``to_numpy``) and when
diffing IDB snapshots to feed downstream strata.

Equivalence discipline: sharded maintenance is byte-identical to
single-device maintenance — same post-update fixpoints, same iteration
counts — at any shard count, on either kernel backend, for narrow and
wide (multi-word key) programs alike (tests/test_update_streams.py
pins this against from-scratch batch recompute after every update of a
randomized stream).

The maintained state IS an arrangement (relation.py docstring): the
stored fulls stay sorted across updates, so a seeded continuation
reuses the final arrangement of the previous run directly — the seed
merge is the incremental ``relops.merge_sorted`` path (O(n + |seed|),
no re-sort of the materialized view), and each seed pass opens one
``ArrangementCache`` so every retagged rule occurrence shares the
stored relations' per-key arrangements.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core import ir as I
from repro.engine import make_engine
from repro.engine import faults as F
from repro.engine import observe as O
from repro.engine.engine import EngineConfig, EngineStats
from repro.engine.relation import (
    Relation, from_numpy, pow2_cap, to_numpy,
)

CHANGED = "changed"


def _row_tuples(rows) -> list[tuple]:
    """Update-batch rows -> list of tuples; tolerates empty batches
    (a zero-row array cannot be reshaped with -1)."""
    rows = np.asarray(rows)
    if rows.size == 0:
        return []
    return [tuple(r) for r in rows.reshape(len(rows), -1)]


def _unique_rules(plans: list[I.RulePlan]) -> list[I.RulePlan]:
    """One representative plan per source rule (variants collapse)."""
    seen: set[tuple[str, str]] = set()
    out = []
    for p in plans:
        key = (p.head, p.source)
        if key not in seen:
            seen.add(key)
            out.append(p)
    return out


def _retag_all_full(root: I.IR) -> I.IR:
    return I.retag_scans(root, lambda rel, idx: I.FULL)


def _count_occurrences(root: I.IR, rel: str) -> int:
    return sum(1 for n in I.iter_nodes(root)
               if isinstance(n, I.Scan) and n.rel == rel)


def _retag_one_changed(root: I.IR, rel: str, occ: int) -> I.IR:
    def version_of(r, idx):
        if r == rel and idx == occ:
            return CHANGED
        return I.FULL
    return I.retag_scans(root, version_of)


class IncrementalEngine:
    """Materialized-view maintenance over a CompiledProgram, single-
    device or sharded (``config.shards``)."""

    def __init__(self, compiled: I.CompiledProgram,
                 config: EngineConfig | None = None):
        self.compiled = compiled
        self.engine = make_engine(compiled, config)
        self.edbs: dict[str, set[tuple]] = {}
        self._env: dict[tuple[str, str], Relation] = {}
        self._stats = EngineStats()
        # relation -> strata indexes that (transitively) depend on it
        self._downstream = self._dependency_closure()

    # -- dependency analysis --------------------------------------------------
    def _dependency_closure(self) -> dict[str, set[int]]:
        produces: dict[int, set[str]] = {}
        consumes: dict[int, set[str]] = {}
        for sp in self.compiled.strata:
            produces[sp.index] = set(sp.idbs)
            cons = set()
            for p in sp.plans:
                for n in I.iter_nodes(p.root):
                    if isinstance(n, I.Scan):
                        cons.add(n.rel)
                for n in self._shared_scans(p.root):
                    cons.add(n)
            consumes[sp.index] = cons
        self._consumes = consumes
        # relations consumed in a NEGATED position (under an Antijoin's
        # right subtree) per stratum: seeded maintenance is monotone,
        # but a change to a negated relation acts inverted on the head
        # (deleting a negated fact can ADD head facts, inserting one
        # can RETRACT them), so such strata fall back to recompute
        self._neg_consumes = {
            sp.index: set().union(*(self._negated_scans(p.root)
                                    for p in sp.plans), set())
            for sp in self.compiled.strata}
        downstream: dict[str, set[int]] = {}

        def affected(rels: set[str]) -> set[int]:
            hit: set[int] = set()
            live = set(rels)
            for sp in self.compiled.strata:
                if consumes[sp.index] & live:
                    hit.add(sp.index)
                    live |= produces[sp.index]
            return hit

        for name in set(self.compiled.arities):
            downstream[name] = affected({name})
        return downstream

    def _negated_scans(self, root: I.IR) -> set[str]:
        """Relations scanned under any Antijoin's negated (right) side,
        expanding shared subplans."""

        def scans_under(node) -> set[str]:
            s: set[str] = set()
            for m in I.iter_nodes(node):
                if isinstance(m, I.Scan):
                    s.add(m.rel)
                elif isinstance(m, I.SharedRef):
                    s |= scans_under(self.compiled.shared[m.ref])
            return s

        out: set[str] = set()
        for n in I.iter_nodes(root):
            if isinstance(n, I.Antijoin):
                out |= scans_under(n.right)
            elif isinstance(n, I.SharedRef):
                out |= self._negated_scans(self.compiled.shared[n.ref])
        return out

    def _shared_scans(self, root: I.IR) -> set[str]:
        out: set[str] = set()
        for n in I.iter_nodes(root):
            if isinstance(n, I.SharedRef):
                sub = self.compiled.shared[n.ref]
                for m in I.iter_nodes(sub):
                    if isinstance(m, I.Scan):
                        out.add(m.rel)
                out |= self._shared_scans(sub)
        return out

    # -- public ----------------------------------------------------------------
    def initialize(self, edbs: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        self.edbs = {k: set(_row_tuples(v)) for k, v in edbs.items()}
        out, stats = self.engine.run(edbs)
        if stats.grow_retries:
            # run() restores its entry caps on return, but the stored
            # fulls were materialized at the grown caps — keep
            # maintenance executing at the caps that worked
            self.engine.set_caps(stats.effective_caps)
        self._env = self.engine.last_env
        self._stats = stats
        return out

    def apply(self, inserts: Optional[dict[str, np.ndarray]] = None,
              deletes: Optional[dict[str, np.ndarray]] = None
              ) -> dict[str, np.ndarray]:
        F.fault_point("incremental.apply")
        inserts = inserts or {}
        deletes = deletes or {}
        changed = set(inserts) | set(deletes)
        for name in changed:
            if name not in self.compiled.edbs:
                raise ValueError(f"{name} is not an EDB")

        obs = self.engine.cfg.observe
        # an update that changes nothing still spans its mirror and its
        # snapshot, host time a profiler's record should name
        with O.span(obs, "apply") as ap_span:
            with O.span(obs, "mirror") as mirror_span:
                ins, dels = self._mirror(inserts, deletes)
                real_ins = {k: np.array(sorted(v)) for k, v in ins.items()}
                real_del = {k: np.array(sorted(v)) for k, v in dels.items()}
            changed = set(real_ins) | set(real_del)
            if ap_span is not None:
                ap_span.attrs.update(
                    changed=",".join(sorted(changed)),
                    insert_rows=sum(len(v) for v in real_ins.values()),
                    delete_rows=sum(len(v) for v in real_del.values()))
            idb_delta_rows = (self._maintain(real_ins, real_del)
                              if changed else None)
            with O.span(obs, "snapshot") as snap_span:
                out = self.snapshot()
        if obs is not None and changed:
            # per-update maintenance latency: the apply less its mirror
            # update and its final export, so this is maintenance cost,
            # not numpy export cost; and IDB-level churn per update
            obs.registry.observe(
                "update.latency_s",
                ap_span.dur - mirror_span.dur - snap_span.dur)
            obs.registry.observe("update.delta_rows", idb_delta_rows)
        return out

    def _mirror(self, inserts: dict, deletes: dict
                ) -> tuple[dict[str, set], dict[str, set]]:
        """Apply an update batch to the host EDB sets; returns the rows
        that really were inserted and deleted, per relation that
        changed."""
        real_ins: dict[str, set] = {}
        real_del: dict[str, set] = {}
        for name, rows in inserts.items():
            have = self.edbs.setdefault(name, set())
            new = {r for r in _row_tuples(rows) if r not in have}
            if new:
                have |= new
                real_ins[name] = new
        for name, rows in deletes.items():
            have = self.edbs.get(name, set())
            old = {r for r in _row_tuples(rows) if r in have}
            if old:
                have -= old
                real_del[name] = old
        return real_ins, real_del

    def _maintain(self, real_ins: dict[str, np.ndarray],
                  real_del: dict[str, np.ndarray]) -> int:
        """Bring the stored EDBs and every affected stratum up to the
        changed EDB sets; returns the IDB rows added and removed."""
        obs = self.engine.cfg.observe
        changed = set(real_ins) | set(real_del)
        idb_delta_rows = 0
        affected: set[int] = set()
        for name in changed:
            affected |= self._downstream.get(name, set())

        # refresh EDB relations in env (stored form: the sharded
        # driver scatters each to its home shards)
        for name in changed:
            self._refresh_edb(name)

        # change sets grow as strata update (IDB-level diffs feed
        # downstream)
        ins_changes: dict[str, np.ndarray] = dict(real_ins)
        del_changes: dict[str, np.ndarray] = dict(real_del)
        for sp in self.compiled.strata:
            if sp.index not in affected:
                continue
            consumed = self._consumes[sp.index]
            my_ins = {k: v for k, v in ins_changes.items()
                      if k in consumed}
            my_del = {k: v for k, v in del_changes.items()
                      if k in consumed}
            if not my_ins and not my_del:
                continue
            with O.span(obs, "idb-diff", key=f"s{sp.index}",
                        side="before"):
                old_snap = {n: self._snapshot_idb(n) for n in sp.idbs}
            monoid_hit = any(n in self.compiled.monoid_idbs
                             for n in sp.idbs)
            # stratified aggregates (Reduce) are order-sensitive in
            # their inputs: seeds over changed subsets would aggregate
            # partial groups. Non-recursive agg strata are one pass —
            # recompute. Exception: a Reduce feeding a MIN/MAX monoid
            # IDB is seed-safe (a partial-subset MIN monoid-merges to
            # the true MIN).
            agg_hit = any(
                isinstance(n, I.Reduce)
                for p in sp.plans
                if p.head not in self.compiled.monoid_idbs
                for n in I.iter_nodes(p.root))
            # a change to a relation this stratum NEGATES is inverted
            # and non-monotone on the head (delete of a negated fact
            # adds head facts; insert retracts them) — seeds cannot
            # express either, so recompute (still through the driver:
            # sharded engines recompute shard-local)
            neg_hit = bool((set(my_ins) | set(my_del))
                           & self._neg_consumes[sp.index])
            if agg_hit or neg_hit or (my_del and monoid_hit):
                strategy = "recompute"
            elif my_del:
                strategy = "dred"
            else:
                strategy = "seed-insert"
            with O.span(obs, "maintain-stratum",
                        key=f"s{sp.index}", strategy=strategy):
                F.fault_point("incremental.maintain")
                O.count(obs, f"incremental.{strategy}")
                if strategy == "recompute":
                    self._recompute_stratum(sp)
                elif strategy == "dred":
                    self._dred_stratum(sp, my_ins, my_del)
                else:
                    self._insert_stratum(sp, my_ins)
            # IDB-level diffs for downstream strata
            with O.span(obs, "idb-diff", key=f"s{sp.index}",
                        side="after"):
                for n in sp.idbs:
                    new_snap = self._snapshot_idb(n)
                    old_set = set(map(tuple, old_snap[n]))
                    new_set = set(map(tuple, new_snap))
                    added = sorted(new_set - old_set)
                    removed = sorted(old_set - new_set)
                    idb_delta_rows += len(added) + len(removed)
                    if added:
                        ins_changes[n] = np.array(added)
                    if removed:
                        del_changes[n] = np.array(removed)
        # maintained arrangements must satisfy the same contract a
        # batch run would leave behind (core/analysis/sanitize.py); the
        # recompute/fixpoint paths were checked per-stratum already —
        # this covers the seed-merge and DRed update paths
        if self.engine._sanitize_due():
            from repro.core.analysis.sanitize import sanitize_env
            sanitize_env(self.engine, self._env, "incremental apply",
                         "incremental")
        return idb_delta_rows

    def _rows(self, rel) -> np.ndarray:
        """Stored relation -> host rows (the one gather point)."""
        return to_numpy(self.engine._host_relation(rel))

    def _snapshot_idb(self, name: str) -> np.ndarray:
        rel = self._env.get((name, I.FULL))
        if rel is None:
            return np.zeros((0, max(self.compiled.arities[name], 1)))
        if name in self.engine.monoid:
            return self.engine.export_monoid(
                name, self.engine._host_relation(rel))
        return self._rows(rel)

    def _rel_from_rows(self, name: str, rows: np.ndarray) -> Relation:
        """Rows (with monoid value column re-attached, if any) -> Relation
        in stored layout (host-side; callers scatter via ``_stored``)."""
        rows = np.asarray(rows).reshape(len(rows), -1)
        cap = pow2_cap(len(rows))
        if name in self.engine.monoid:
            sr, vpos = self.engine.monoid[name]
            vals = rows[:, vpos]
            dcols = [c for c in range(rows.shape[1]) if c != vpos]
            data = rows[:, dcols] if dcols else np.zeros(
                (len(vals), 1), np.int64)
            return from_numpy(data, cap, val=vals, val_identity=sr.identity,
                              dedupe=False)
        return from_numpy(rows, cap)

    def _stored_from_rows(self, rows_by_name: dict[str, np.ndarray]) -> dict:
        return self.engine._stored(
            {name: self._rel_from_rows(name, rows)
             for name, rows in rows_by_name.items()})

    def _edb_rows(self, name: str) -> np.ndarray:
        """Current mirror rows for one EDB (sorted; empty-safe)."""
        rows = self.edbs.get(name, set())
        if rows:
            return np.array(sorted(rows))
        return np.zeros((0, max(self.compiled.arities[name], 1)))

    def _refresh_edb(self, name: str) -> None:
        """Mirror -> stored EDB relation in the env (the sharded driver
        scatters to home shards)."""
        with O.span(self.engine.cfg.observe, "refresh-edb", relation=name):
            rows = self._edb_rows(name)
            self._env[(name, I.FULL)] = self.engine._stored(
                {name: from_numpy(rows, pow2_cap(len(rows)))})[name]

    # -- recompute rungs (engine/resilience.py degradation ladder) -------------
    def apply_base(self, inserts: Optional[dict] = None,
                   deletes: Optional[dict] = None) -> set:
        """Apply an update batch to the base EDB state only — the host
        multiset mirror plus the stored EDB relations — WITHOUT
        maintaining any IDB. Returns the set of EDB names actually
        changed. Idempotent: re-applying rows already present (or
        deleting rows already absent) is a no-op, so the resilience
        ladder can re-base after a partially-failed maintenance pass
        and recompute from a consistent EDB state."""
        inserts = inserts or {}
        deletes = deletes or {}
        for name in set(inserts) | set(deletes):
            if name not in self.compiled.edbs:
                raise ValueError(f"{name} is not an EDB")
        ins, dels = self._mirror(inserts, deletes)
        changed = set(ins) | set(dels)
        for name in changed:
            self._refresh_edb(name)
        return changed

    def recompute_strata(self, changed: Optional[set] = None) -> None:
        """Recompute strata from the current EDB state through the
        driver (``_run_stratum`` — sharded engines recompute
        shard-local): every stratum when ``changed`` is None, else the
        dependency closure downstream of the changed relations, in
        stratum order so each recomputed IDB feeds later strata."""
        if changed is None:
            affected = {sp.index for sp in self.compiled.strata}
        else:
            affected = set()
            for name in changed:
                affected |= self._downstream.get(name, set())
        for sp in self.compiled.strata:
            if sp.index in affected:
                self._recompute_stratum(sp)

    def reinitialize(self) -> dict[str, np.ndarray]:
        """Full batch recompute from the current EDB mirror (the last
        resilience rung): re-runs the whole program and replaces the
        maintained state wholesale."""
        edbs = {name: self._edb_rows(name) for name in self.edbs}
        out, stats = self.engine.run(edbs)
        if stats.grow_retries:
            self.engine.set_caps(stats.effective_caps)
        self._env = self.engine.last_env
        self._stats = stats
        return out

    def snapshot(self) -> dict[str, np.ndarray]:
        out = {}
        for name in self.compiled.arities:
            key = (name, I.FULL)
            if key in self._env:
                out[name] = self._snapshot_idb(name)
        return out

    # -- internals --------------------------------------------------------------
    def _recompute_stratum(self, sp: I.StratumPlan) -> None:
        stats = EngineStats()
        env = {k: v for k, v in self._env.items()
               if k[0] not in sp.idbs}
        self._env = self.engine._run_stratum(env_rels=env, sp=sp,
                                             stats=stats,
                                             stratum_key=f"inc_s{sp.index}")
        self._stats.iterations[f"inc_s{sp.index}"] = (
            stats.iterations.get(f"inc_s{sp.index}", 0))

    def _seed_roots(self, sp: I.StratumPlan,
                    changed_names) -> list[tuple[str, I.IR]]:
        """Retag logic (driver-agnostic pure IR work): every rule with
        one changed-relation occurrence scanning only the changed rows."""
        roots: list[tuple[str, I.IR]] = []
        for p in _unique_rules(sp.plans):
            plain = _retag_all_full(p.root)
            for rel_name in sorted(changed_names):
                occs = _count_occurrences(plain, rel_name)
                for occ in range(occs):
                    roots.append(
                        (p.head, _retag_one_changed(plain, rel_name, occ)))
        return roots

    def _seed(self, sp: I.StratumPlan, changed_rows: dict,
              env_rels, restrict=None) -> dict:
        """Evaluate every rule with one changed-occurrence scan; union
        by head (driver pass: runs under shard_map when sharded).
        ``changed_rows`` must already be in stored form. Changed IDB
        inputs from lower strata are handled by passing their full
        (already updated) relations — the seed only needs the changed
        occurrences because lower strata were updated first."""
        roots = self._seed_roots(sp, set(changed_rows))
        if not roots:
            return {}
        rels = dict(env_rels)
        for name, rel in changed_rows.items():
            rels[(name, CHANGED)] = rel
        # the pass structure is fully determined by (stratum, changed
        # names, restrict heads), so an update stream touching the same
        # relations re-executes one compiled pass
        memo_key = (sp.index, "seed", tuple(sorted(changed_rows)),
                    tuple(sorted(restrict)) if restrict else ())
        with O.span(self.engine.cfg.observe, "seed-pass",
                    stratum=f"s{sp.index}",
                    changed=",".join(sorted(changed_rows))):
            return self.engine.run_rule_pass(
                rels, roots, restrict=restrict, memo_key=memo_key,
                context=(f"stratum=s{sp.index} pass=seed "
                         f"changed={','.join(sorted(changed_rows))}"))

    def _insert_stratum(self, sp: I.StratumPlan,
                        inserts: dict[str, np.ndarray]) -> None:
        changed_rel = self._stored_from_rows(inserts)
        seeds = self._seed(sp, changed_rel, self._env)
        self._continue_fixpoint(sp, seeds)

    def _dred_stratum(self, sp, inserts, deletes) -> None:
        # 1. over-delete to FIXPOINT: candidates derivable from deleted
        #    tuples against the OLD state, propagated through stratum IDB
        #    occurrences until no new candidates (classic DRed phase 1).
        #    The env still holds old IDB fulls; changed EDB fulls are
        #    already new, so reconstruct the old EDB view for the seeds.
        del_rel = self._stored_from_rows(deletes)
        old_env = dict(self._env)
        for name, rows in deletes.items():
            # old view = new ∪ deleted (works for EDBs and lower IDBs)
            if name in self.engine.monoid:
                cur = self.engine.export_monoid(
                    name, self.engine._host_relation(
                        self._env[(name, I.FULL)]))
            else:
                cur = self._rows(self._env[(name, I.FULL)])
            allrows = np.concatenate([cur, rows]) if len(cur) else rows
            old_env[(name, I.FULL)] = self._stored_from_rows(
                {name: allrows})[name]

        # the "only facts that actually exist can be deleted" filter is
        # a semijoin against the current fulls, evaluated inside the
        # pass (shard-local under sharding) — only the small candidate
        # set ever reaches the host
        obs = self.engine.cfg.observe
        exists = {n: self._env[(n, I.FULL)] for n in sp.idbs}
        candidates: dict[str, set[tuple]] = {n: set() for n in sp.idbs}
        rounds = 0
        with O.span(obs, "dred-candidates") as cand_span:
            frontier = del_rel
            while frontier:
                rounds += 1
                step = self._seed(sp, frontier, old_env, restrict=exists)
                new_rows: dict[str, np.ndarray] = {}
                for head, rel in step.items():
                    rows = set(map(tuple, self._rows(rel)))
                    new = rows - candidates[head]
                    if new:
                        candidates[head] |= new
                        new_rows[head] = np.array(sorted(new))
                frontier = self._stored_from_rows(new_rows)
            if cand_span is not None:
                cand_span.attrs["rounds"] = rounds
                cand_span.attrs["candidate_rows"] = sum(
                    len(v) for v in candidates.values())
        O.count(obs, "incremental.dred_rounds", rounds)

        candidates_rel = self._stored_from_rows(
            {name: np.array(sorted(rows))
             for name, rows in candidates.items() if rows})

        # 2. remove candidates from stored fulls (shard-local: both
        #    sides are home-partitioned by full row)
        with O.span(obs, "dred-remove"):
            for name, cand in candidates_rel.items():
                self._env[(name, I.FULL)] = (
                    self.engine._difference_stored(
                        self._env[(name, I.FULL)], cand))

        # 3. re-derive: run rules against the reduced state; anything still
        #    derivable (incl. candidates with alternate support) comes back
        #    through the standard fixpoint continuation.
        plain_roots = [(p.head, _retag_all_full(p.root))
                       for p in _unique_rules(sp.plans)]
        with O.span(obs, "dred-rederive"):
            rederive = self.engine.run_rule_pass(
                dict(self._env), plain_roots, restrict=candidates_rel,
                memo_key=(sp.index, "rederive",
                          tuple(sorted(candidates_rel))),
                context=f"stratum=s{sp.index} pass=dred-rederive")
        # 4. insertions seeded on the post-deletion state
        if inserts:
            ins_rel = self._stored_from_rows(inserts)
            ins_seeds = self._seed(sp, ins_rel, self._env)
            for head, rel in ins_seeds.items():
                if head in rederive:
                    rederive[head] = self.engine._union_stored(
                        [rederive[head], rel], self.engine._sr_of(head),
                        self.engine._idb_cap(head),
                        context=(f"stratum=s{sp.index} "
                                 f"pass=dred-insert-union head={head}"))
                else:
                    rederive[head] = rel
        self._continue_fixpoint(sp, rederive)

    def _continue_fixpoint(self, sp: I.StratumPlan,
                           seeds: dict[str, Relation]) -> None:
        """Merge seeds into fulls, then run the stratum's semi-naive loop
        from (full, seed-delta) to fixpoint — through the driver, so a
        sharded engine continues shard-local from its stored state."""
        stats = EngineStats()
        env = dict(self._env)
        self._env = self.engine._run_stratum(
            sp=sp, env_rels={k: v for k, v in env.items()
                             if k[0] not in sp.idbs},
            stats=stats, stratum_key=f"inc_s{sp.index}",
            init_state={
                name: (env.get((name, I.FULL),
                               self.engine._stored_empty_idb(name)),
                       seeds.get(name))
                for name in sorted(sp.idbs)})
        self._stats.iterations[f"inc_s{sp.index}"] = (
            stats.iterations.get(f"inc_s{sp.index}", 0))
