"""Fault-tolerance suite (engine/resilience.py + engine/faults.py).

Covers: deterministic fault injection, the crash-replay differential
across backends and shard counts (reusing the randomized stream
harness from test_update_streams.py), named-site crash windows the
acceptance pins explicitly (crash between log-append and apply; crash
mid-checkpoint), snapshot mismatch refusal and shard re-homing, WAL
torn-tail tolerance and compaction, the graceful degradation ladder
with its ``resilience.*`` metrics, and the attempt-local auto-grow
capacities.

Sharded cases skip on a single device; run the full matrix with
``make test-resilience`` (8 forced host devices, also the CI
``sharded`` job).
"""
from benchmarks.hostdevices import force_host_device_count

force_host_device_count()  # must precede the first jax device init

import numpy as np
import pytest

import jax

from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig
from repro.engine import faults as F
from repro.engine.engine import OverflowError_
from repro.engine.faults import FaultPlan, FaultSpec, SimulatedCrash
from repro.engine.incremental import IncrementalEngine
from repro.engine.observe import Observation
from repro.engine.resilience import (
    DurableIncrementalEngine, ResilienceConfig, SnapshotMismatch,
    UpdateLog, config_fingerprint, program_hash, restore_snapshot,
    save_snapshot,
)

from test_update_streams import (
    _cfg, _edbs, _need, _run_crash_replay_stream, _source,
)

TC_SRC = """
.input edge
.output tc
tc(x,y) :- edge(x,y).
tc(x,z) :- tc(x,y), edge(y,z).
"""

PATH_SRC = """
.input arc
.output path
path(x,y) :- arc(x,y).
path(x,z) :- path(x,y), arc(y,z).
"""


def _edges(seed=0, n=18, dom=11):
    return np.random.default_rng(seed).integers(0, dom, size=(n, 2))


def _tc(config=None):
    return compile_program(TC_SRC), (config or _cfg())


# -- fault injection ----------------------------------------------------------

def test_fault_plan_deterministic():
    """Seeded plans are reproducible; firing is a pure function of the
    hit-count sequence."""
    a = FaultPlan.seeded(5, ("x", "y", "z"), n_faults=4, max_hit=6)
    b = FaultPlan.seeded(5, ("x", "y", "z"), n_faults=4, max_hit=6)
    assert a.specs == b.specs
    for plan in (a, b):
        for _ in range(20):
            for site in ("x", "y", "z"):
                try:
                    plan.fire(site)
                except Exception:
                    pass
    assert a.fired == b.fired and a.counts == b.counts


def test_fault_spec_windows_and_kinds():
    plan = FaultPlan([
        FaultSpec("a", kind="io", hit=2),            # exactly hit 2
        FaultSpec("b.*", kind="overflow", hit=1, last=2),
        FaultSpec("c", kind="crash", hit=3, last=-1),  # forever from 3
    ])
    with F.install(plan):
        F.fault_point("a")                           # hit 1: silent
        with pytest.raises(F.FaultError):
            F.fault_point("a")                       # hit 2: io
        F.fault_point("a")                           # hit 3: silent again
        with pytest.raises(OverflowError_):
            F.fault_point("b.one")                   # prefix match
        with pytest.raises(OverflowError_):
            F.fault_point("b.one")
        F.fault_point("b.one")                       # window closed
        F.fault_point("c")
        F.fault_point("c")
        for _ in range(3):
            with pytest.raises(SimulatedCrash):
                F.fault_point("c")
    F.fault_point("a")  # no plan installed: always a no-op
    assert [kind for (_, _, kind) in plan.fired] == [
        "io", "overflow", "overflow", "crash", "crash", "crash"]


def test_fault_point_is_noop_without_plan():
    assert F.active() is None
    F.fault_point("engine.rule_pass")


# -- crash-replay differential matrix (acceptance: jnp+pallas, 1+8 shard) ----
# Marked slow: several minutes of repeated restarts. Always run by
# `make test-resilience` (no marker filter; CI sharded job) and the
# nightly full tier; excluded only from the fast push tier.

@pytest.mark.slow
def test_crash_replay_pallas():
    crashes = _run_crash_replay_stream(
        "TC", backend="pallas-interpret", n_steps=5, seed=33, n_crashes=3)
    assert crashes >= 1


@pytest.mark.slow
@pytest.mark.parametrize("shards", (2, 8))
def test_crash_replay_sharded(shards):
    crashes = _run_crash_replay_stream(
        "TC", shards=shards, n_steps=5, seed=35, n_crashes=3)
    assert crashes >= 1


@pytest.mark.slow
def test_crash_replay_wide_program():
    """Multi-rule wide program under a deterministic mid-stream crash
    (a seeded plan can draw hit counts this short stream never
    reaches, so pin the schedule instead)."""
    plan = FaultPlan([
        FaultSpec("resilience.after_log", kind="crash", hit=2),
        FaultSpec("checkpoint.commit", kind="crash", hit=2),
    ])
    crashes = _run_crash_replay_stream(
        "WideReach2", n_steps=5, seed=37, plan=plan)
    assert crashes >= 2


@pytest.mark.slow
@pytest.mark.parametrize("site", (
    "resilience.after_log",   # acceptance: between log-append and apply
    "checkpoint.commit",      # acceptance: mid-checkpoint
    "wal.before_append",
    "incremental.maintain",
))
def test_crash_replay_named_site(site, tmp_path):
    """Every named crash window, injected deterministically at an
    early hit, is absorbed byte-identically. (hit=2 because not every
    apply enters the maintain-stratum loop — some stream steps filter
    to mirror no-ops — and incremental.maintain must still fire.)"""
    plan = FaultPlan([FaultSpec(site, kind="crash", hit=2)])
    _run_crash_replay_stream("TC", n_steps=6, seed=39,
                             state_dir=tmp_path, plan=plan)
    assert plan.fired, f"site {site} never fired"


# -- durable snapshots: replay, mismatch refusal, re-homing -------------------

def test_recover_replays_wal_tail(tmp_path):
    """Updates applied after the last snapshot live only in the WAL;
    recovery must replay exactly those."""
    cp, cfg = _tc()
    dur = DurableIncrementalEngine(
        cp, cfg, directory=tmp_path,
        resilience=ResilienceConfig(snapshot_every=0))  # never re-snapshot
    dur.initialize({"edge": _edges()})
    out = dur.apply(inserts={"edge": [[0, 9], [9, 7]]})
    out = dur.apply(deletes={"edge": [_edges()[0].tolist()]})
    dur.close()
    cold = DurableIncrementalEngine(cp, _cfg(), directory=tmp_path)
    rec = cold.recover()
    assert cold.applied_seq == 2
    for name in out:
        np.testing.assert_array_equal(out[name], rec[name])


def test_restore_refuses_program_mismatch(tmp_path):
    cp, cfg = _tc()
    inc = IncrementalEngine(cp, cfg)
    inc.initialize({"edge": _edges()})
    save_snapshot(inc, tmp_path, seq=0)
    other = IncrementalEngine(compile_program(PATH_SRC), _cfg())
    with pytest.raises(SnapshotMismatch, match="program"):
        restore_snapshot(other, tmp_path)
    assert program_hash(cp) != program_hash(other.compiled)


def test_restore_refuses_semiring_mismatch(tmp_path):
    from repro.engine.semiring import COUNTING
    cp, cfg = _tc()
    inc = IncrementalEngine(cp, cfg)
    inc.initialize({"edge": _edges()})
    save_snapshot(inc, tmp_path, seq=0)
    other = IncrementalEngine(cp, _cfg(semiring=COUNTING))
    assert config_fingerprint(other.engine.cfg) != config_fingerprint(cfg)
    with pytest.raises(SnapshotMismatch, match="config fingerprint"):
        restore_snapshot(other, tmp_path)


def test_restore_refuses_schema_mismatch(tmp_path):
    import json
    cp, cfg = _tc()
    inc = IncrementalEngine(cp, cfg)
    inc.initialize({"edge": _edges()})
    save_snapshot(inc, tmp_path, seq=0)
    man_path = tmp_path / "step_00000000" / "manifest.json"
    man = json.loads(man_path.read_text())
    man["extra"]["schema_version"] = 999
    man_path.write_text(json.dumps(man))
    with pytest.raises(SnapshotMismatch, match="schema_version"):
        restore_snapshot(inc, tmp_path)


@pytest.mark.parametrize("src_shards,dst_shards", ((0, 2), (2, 0), (2, 8)))
def test_restore_rehomes_across_shard_counts(src_shards, dst_shards,
                                             tmp_path):
    """A snapshot taken at one shard count restores onto another: rows
    are gathered to host form at save and re-homed through the target
    driver's scatter — byte-identical snapshots either way."""
    _need(max(src_shards, dst_shards))
    cp = compile_program(_source("TC"))
    edbs = _edbs("TC")
    src = IncrementalEngine(cp, _cfg(shards=src_shards))
    out = src.initialize({k: v.copy() for k, v in edbs.items()})
    save_snapshot(src, tmp_path, seq=0)

    obs = Observation()
    dst = IncrementalEngine(cp, _cfg(shards=dst_shards, observe=obs))
    seq = restore_snapshot(dst, tmp_path)
    assert seq == 0
    assert obs.registry.get("resilience.restore.rehomed") == 1
    for name, rows in dst.snapshot().items():
        np.testing.assert_array_equal(rows, out[name])
    assert dst.edbs == src.edbs
    # the restored state must keep maintaining correctly
    a = src.apply(inserts={"edge": [[0, 23], [23, 5]]})
    b = dst.apply(inserts={"edge": [[0, 23], [23, 5]]})
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
    assert src._stats.iterations == dst._stats.iterations


# -- write-ahead log ----------------------------------------------------------

def test_wal_roundtrip_and_compaction(tmp_path):
    log = UpdateLog(tmp_path / "u.log")
    log.append(1, {"edge": np.array([[1, 2]])}, None)
    log.append(2, None, {"edge": [[3, 4]]})
    log.append(3, {"edge": [[5, 6]]}, {"edge": []})
    assert [r["seq"] for r in log.records()] == [1, 2, 3]
    assert [r["seq"] for r in log.records(after_seq=1)] == [2, 3]
    assert log.records()[0]["ins"] == {"edge": [[1, 2]]}
    log.compact(2)
    assert [r["seq"] for r in log.records()] == [3]
    log.append(4, {"edge": [[7, 8]]}, None)   # append survives compact
    assert [r["seq"] for r in log.records()] == [3, 4]
    log.close()


def test_wal_torn_tail_ignored(tmp_path):
    """A crash mid-write leaves a partial last line; replay stops at
    the last complete record instead of failing."""
    log = UpdateLog(tmp_path / "u.log")
    log.append(1, {"edge": [[1, 2]]}, None)
    log.append(2, {"edge": [[3, 4]]}, None)
    log.close()
    with open(tmp_path / "u.log", "a", encoding="utf-8") as fh:
        fh.write('{"seq": 3, "ins": {"edge": [[5,')   # torn
    assert [r["seq"] for r in log.records()] == [1, 2]


def test_wal_io_fault_surfaces(tmp_path):
    log = UpdateLog(tmp_path / "u.log")
    with F.install(FaultPlan([FaultSpec("wal.write", kind="io")])):
        with pytest.raises(F.FaultError):
            log.append(1, {"edge": [[1, 2]]}, None)
    log.append(1, {"edge": [[1, 2]]}, None)    # retry succeeds
    assert [r["seq"] for r in log.records()] == [1]
    log.close()


# -- graceful degradation ladder ----------------------------------------------

def _ladder_engine(tmp_path, obs, retries=2):
    cp = compile_program(TC_SRC)
    dur = DurableIncrementalEngine(
        cp, _cfg(observe=obs), directory=tmp_path,
        resilience=ResilienceConfig(max_capacity_retries=retries))
    dur.initialize({"edge": _edges()})
    return cp, dur


def _batch_reference(cp, dur):
    eng = Engine(cp, _cfg())
    out, _ = eng.run({name: (np.array(sorted(rows)) if rows
                             else np.zeros((0, 2), int))
                      for name, rows in dur.inc.edbs.items()})
    return out


def test_ladder_capacity_backoff_recovers(tmp_path):
    """Transient overflow (two failing passes, then clean) is absorbed
    by rung 1: grow-and-retry, no recompute."""
    obs = Observation()
    cp, dur = _ladder_engine(tmp_path, obs)
    plan = FaultPlan([FaultSpec("engine.rule_pass", kind="overflow",
                                hit=1, last=2)])
    with F.install(plan):
        out = dur.apply(inserts={"edge": [[0, 10], [10, 4]]})
    reg = obs.registry
    assert reg.get("resilience.ladder.capacity_backoff") == 2
    assert reg.get("resilience.ladder.capacity_recovered") == 1
    assert reg.get("resilience.ladder.stratum_recompute") == 0
    ref = _batch_reference(cp, dur)
    assert set(map(tuple, out["tc"])) == set(map(tuple, ref["tc"]))


def test_ladder_exhausted_growth_falls_back_to_recompute(tmp_path):
    """Acceptance: a fault plan that exhausts grow retries completes
    via the stratum-recompute rung instead of raising, and the
    resilience.* metrics report each escalation rung."""
    obs = Observation()
    cp, dur = _ladder_engine(tmp_path, obs, retries=2)
    plan = FaultPlan([FaultSpec("engine.rule_pass", kind="overflow",
                                hit=1, last=-1)])   # every pass, forever
    with F.install(plan):
        out = dur.apply(inserts={"edge": [[0, 10], [10, 4]]})
    reg = obs.registry
    assert reg.get("resilience.ladder.capacity_backoff") == 2
    assert reg.get("resilience.ladder.stratum_recompute") == 1
    assert reg.get("resilience.ladder.full_recompute") == 0
    ref = _batch_reference(cp, dur)
    assert set(map(tuple, out["tc"])) == set(map(tuple, ref["tc"]))
    # the ladder left consistent state: further clean applies work
    out2 = dur.apply(inserts={"edge": [[4, 0]]})
    ref2 = _batch_reference(cp, dur)
    assert set(map(tuple, out2["tc"])) == set(map(tuple, ref2["tc"]))


def test_ladder_escalates_to_full_recompute(tmp_path):
    """If the stratum recompute ALSO overflows, the last rung re-runs
    the whole program. Window arithmetic: rung 1 makes retries+1
    apply attempts (one stratum hit each), rung 2 one recompute hit —
    keep the fault live through all of those, then let rung 3 pass."""
    obs = Observation()
    cp, dur = _ladder_engine(tmp_path, obs, retries=2)
    plan = FaultPlan([FaultSpec("engine.stratum", kind="overflow",
                                hit=1, last=4)])
    with F.install(plan):
        out = dur.apply(inserts={"edge": [[0, 10], [10, 4]]})
    reg = obs.registry
    assert reg.get("resilience.ladder.stratum_recompute") == 1
    assert reg.get("resilience.ladder.full_recompute") == 1
    ref = _batch_reference(cp, dur)
    assert set(map(tuple, out["tc"])) == set(map(tuple, ref["tc"]))


# -- attempt-local auto-grow capacities (satellite: engine.run) ---------------

def test_auto_grow_does_not_mutate_config():
    """run()'s overflow retry grows attempt-local caps, records the
    effective caps in stats, and restores the entry caps — cfg is
    never touched and later memo-jit keys see the original caps."""
    cp = compile_program(TC_SRC)
    cfg = EngineConfig(idb_cap=16, intermediate_cap=16,
                       max_grow_retries=8)
    eng = Engine(cp, cfg)
    edges = _edges(seed=3, n=40, dom=14)
    out, stats = eng.run({"edge": edges})
    assert stats.grow_retries > 0
    assert cfg.idb_cap == 16 and cfg.intermediate_cap == 16
    assert cfg.idb_caps == {}
    assert eng.effective_caps() == {
        "intermediate_cap": 16, "idb_cap": 16, "idb_caps": {}}
    assert stats.effective_caps["idb_cap"] == 16 << stats.grow_retries
    # the grown run is still correct
    eng2 = Engine(cp, EngineConfig())
    ref, _ = eng2.run({"edge": edges})
    assert set(map(tuple, out["tc"])) == set(map(tuple, ref["tc"]))


def test_overflow_message_is_traceable():
    """Maintenance overflows name the stratum, the pass, and the
    capacities (satellite: no more bare 'overflow in incremental rule
    pass')."""
    cp = compile_program(TC_SRC)
    inc = IncrementalEngine(cp, EngineConfig(
        idb_cap=32, intermediate_cap=1 << 12))
    inc.initialize({"edge": np.array([[0, 1]])})
    big = [[i, i + 1] for i in range(40)]
    with pytest.raises(OverflowError_) as exc:
        inc.apply(inserts={"edge": big})
    msg = str(exc.value)
    assert "stratum=s" in msg and "pass=" in msg
    assert "idb_cap=32" in msg and "intermediate_cap=" in msg


# -- sanitizer sampling rides the durable path --------------------------------

def test_durable_apply_with_sampled_sanitizer(tmp_path):
    """check_invariants=N composes with the durable serving path."""
    cp, _ = _tc()
    dur = DurableIncrementalEngine(
        cp, _cfg(check_invariants=2), directory=tmp_path)
    dur.initialize({"edge": _edges()})
    out = dur.apply(inserts={"edge": [[0, 10], [10, 4]]})
    dur.close()
    cold = DurableIncrementalEngine(
        cp, _cfg(check_invariants=2), directory=tmp_path)
    rec = cold.recover()
    for name in out:
        np.testing.assert_array_equal(out[name], rec[name])
