"""Randomized update-stream differential harness (incremental.py: the
sharded-maintenance contract).

Property: after EVERY step of a randomized update stream (interleaved
inserts/deletes of random EDB row batches, including empty batches,
duplicate re-inserts, and delete-then-reinsert of the same rows), the
incremental engine's maintained state is byte-identical to a
from-scratch batch recompute of the current EDB state — for either
kernel backend, and for the sharded driver at every shard count (which
must additionally match the single-device incremental engine's
iteration counts).

Streams are generated from fixed seeds; every divergence assertion
embeds the (program, backend, shards, seed, step) tuple so a failure
reproduces with ``_run_stream(program, seed=..., n_steps=...)``.

Engines are cached per (program, backend, shards) and re-initialized
per test: the engine memo-jits its stratum and maintenance passes
(``Engine._memo_jit``), so a stream re-executes compiled steps instead
of re-tracing per update — both the production serving model and what
keeps >= 200 differential steps inside the fast-tier budget.

Sharded cases skip on a single device; run them standalone (or via
``make test-sharded`` / the CI ``sharded`` job) with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from benchmarks.hostdevices import force_host_device_count

force_host_device_count()  # must precede the first jax device init

import numpy as np
import pytest

import jax

from benchmarks.programs import CC, equivalence_datasets
from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig
from repro.engine.incremental import IncrementalEngine

# (program, backend, steps, seed) — the single-device differential
# plan; streams total >= 200 steps and run in the fast tier
STREAM_PLAN = (
    ("TC", "jnp", 70, 101),
    ("Negation", "jnp", 25, 102),
    ("WideReach2", "jnp", 45, 103),
    ("TC", "pallas-interpret", 40, 104),
    ("WideReach2", "pallas-interpret", 25, 105),
)
# test ids name the kernels ("pallas"), not their interpret mode
STREAM_IDS = ["-".join(map(str, p)).replace("pallas-interpret", "pallas")
              for p in STREAM_PLAN]

_SABOTAGE_ROW_VALUE = 1_000_003  # far outside every corpus domain


def _cfg(**kw):
    d = dict(idb_cap=1 << 10, intermediate_cap=1 << 12,
             kernel_backend="jnp")
    d.update(kw)
    return EngineConfig(**d)


def _need(shards: int):
    if shards > len(jax.devices()):
        pytest.skip(f"needs {shards} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count)")


_datasets = equivalence_datasets
_ENGINES: dict = {}


def _source(program: str) -> str:
    return CC if program == "CC" else _datasets()[program][0]


def _edbs(program: str) -> dict:
    if program == "CC":
        rng = np.random.default_rng(3)
        return {"edge": rng.integers(0, 24, size=(40, 2))}
    return {k: np.asarray(v) for k, v in _datasets()[program][1].items()}


def _inc(program: str, backend: str = "jnp",
         shards: int = 0) -> IncrementalEngine:
    """Cached IncrementalEngine; shards=1 forces the sharded driver on
    a 1-device mesh (make_engine would pick the single-device Engine)."""
    key = ("inc", program, backend, shards)
    if key not in _ENGINES:
        cp = compile_program(_source(program))
        inc = IncrementalEngine(
            cp, _cfg(kernel_backend=backend, shards=shards))
        if shards == 1:
            from repro.engine.shard import ShardedEngine
            inc.engine = ShardedEngine(
                cp, _cfg(kernel_backend=backend, shards=1))
        _ENGINES[key] = inc
    return _ENGINES[key]


def _batch(program: str, backend: str = "jnp") -> Engine:
    key = ("batch", program, backend)
    if key not in _ENGINES:
        _ENGINES[key] = Engine(compile_program(_source(program)),
                               _cfg(kernel_backend=backend))
    return _ENGINES[key]


# -- stream generation -------------------------------------------------------

def gen_stream(seed: int, edbs: dict, n_steps: int) -> list:
    """Fixed-seed random update stream: list of (inserts, deletes)
    dicts. Covers random insert batches, deletes of current rows,
    mixed steps, duplicate re-inserts of present rows, empty batches,
    and delete-then-reinsert of the same rows (the reinsert lands on
    the following step)."""
    rng = np.random.default_rng(seed)
    mirror = {k: set(map(tuple, np.asarray(v).reshape(len(v), -1)))
              for k, v in edbs.items()}
    arity = {k: np.asarray(v).reshape(len(v), -1).shape[1]
             for k, v in edbs.items()}
    dom = {k: int(np.asarray(v).max(initial=0)) + 2 for k, v in edbs.items()}
    names = sorted(edbs)
    kinds = ["ins", "del", "mixed", "dup", "empty", "delreins"]
    steps = []
    pending: dict[str, np.ndarray] = {}
    for _ in range(n_steps):
        ins: dict[str, np.ndarray] = dict(pending)
        dele: dict[str, np.ndarray] = {}
        pending = {}
        kind = kinds[int(rng.integers(len(kinds)))]
        name = names[int(rng.integers(len(names)))]
        a = arity[name]

        def _sample_current(k: int) -> np.ndarray:
            cur = sorted(mirror[name])
            if not cur or not k:
                return np.zeros((0, a), int)
            idx = rng.permutation(len(cur))[:k]
            return np.array([cur[j] for j in idx])

        if kind in ("ins", "mixed"):
            k = int(rng.integers(0, 5))  # 0 = empty insert batch
            batch = rng.integers(0, dom[name], size=(k, a))
            prev = ins.get(name, np.zeros((0, a), int))
            ins[name] = np.concatenate([prev, batch]).astype(int)
        if kind in ("del", "mixed"):
            dele[name] = _sample_current(int(rng.integers(0, 4)))
        if kind == "dup":  # re-insert rows that are already present
            ins[name] = _sample_current(int(rng.integers(1, 4)))
        if kind == "empty":
            ins.setdefault(name, np.zeros((0, a), int))
            dele[name] = np.zeros((0, a), int)
        if kind == "delreins":  # delete now, re-insert next step
            rows = _sample_current(int(rng.integers(1, 3)))
            if len(rows):
                dele[name] = rows
                pending[name] = rows
        # mirror follows apply() semantics: inserts land, then deletes
        for n_, r in ins.items():
            mirror[n_] |= set(map(tuple, np.asarray(r).reshape(-1, arity[n_])))
        for n_, r in dele.items():
            mirror[n_] -= set(map(tuple, np.asarray(r).reshape(-1, arity[n_])))
        steps.append((ins, dele))
    return steps


# -- the differential harness ------------------------------------------------

def _current_edbs(inc: IncrementalEngine) -> dict:
    out = {}
    for name, rows in inc.edbs.items():
        a = max(inc.compiled.arities[name], 1)
        out[name] = (np.array(sorted(rows))
                     if rows else np.zeros((0, a), int))
    return out


def _assert_states_equal(a: dict, b: dict, ctx: str):
    assert a.keys() == b.keys(), f"relation sets differ: {ctx}"
    for name in sorted(a):
        np.testing.assert_array_equal(
            a[name], b[name],
            err_msg=f"update-stream divergence: rel={name} {ctx}")
        assert a[name].dtype == b[name].dtype, f"dtype drift: rel={name} {ctx}"


def _run_stream(program: str, backend: str = "jnp", n_steps: int = 20,
                seed: int = 0, sabotage_at: int | None = None) -> int:
    """Drive one randomized stream, pinning the incremental state
    against a from-scratch batch recompute after every step. Returns
    the number of differential steps executed. ``sabotage_at`` injects
    a divergence (corrupts the EDB mirror so the batch reference
    disagrees with the maintained state) to prove the harness fails
    loudly; the corruption is repaired afterwards so the cached engine
    stays consistent for later tests."""
    edbs = _edbs(program)
    inc = _inc(program, backend)
    inc.initialize({k: v.copy() for k, v in edbs.items()})
    batch = _batch(program, backend)
    steps = gen_stream(seed, edbs, n_steps)
    sab_name = sorted(inc.edbs)[0]
    sab_row = (_SABOTAGE_ROW_VALUE,) * max(
        inc.compiled.arities[sab_name], 1)
    executed = 0
    try:
        for i, (ins, dele) in enumerate(steps):
            if sabotage_at == i:
                inc.edbs[sab_name].add(sab_row)
            out = inc.apply(
                inserts={k: v.copy() for k, v in ins.items()},
                deletes={k: v.copy() for k, v in dele.items()})
            ref, _ = batch.run(_current_edbs(inc))
            _assert_states_equal(
                out, ref,
                f"program={program} backend={backend} shards=0 "
                f"seed={seed} step={i} (reproduce: _run_stream("
                f"{program!r}, backend={backend!r}, n_steps={n_steps}, "
                f"seed={seed}))")
            executed += 1
    finally:
        inc.edbs[sab_name].discard(sab_row)
    return executed


@pytest.mark.parametrize("program,backend,n_steps,seed", STREAM_PLAN,
                         ids=STREAM_IDS)
def test_update_stream_matches_batch(program, backend, n_steps, seed):
    """>= 200 randomized differential steps across the plan: every
    step's post-update state byte-matches a from-scratch recompute."""
    executed = _run_stream(program, backend=backend, n_steps=n_steps,
                           seed=seed)
    assert executed == n_steps


def test_stream_plan_covers_200_steps():
    """The plan itself guarantees the >= 200-step budget (this pins the
    budget even if individual cases are edited)."""
    assert sum(p[2] for p in STREAM_PLAN) >= 200


def test_device_mode_update_stream():
    """Maintenance composes with device mode (the whole-stratum
    while_loop continuation from a seeded state): still byte-identical
    to batch recompute after every step."""
    edbs = _edbs("TC")
    cp = compile_program(_source("TC"))
    inc = IncrementalEngine(cp, _cfg(mode="device"))
    inc.initialize({k: v.copy() for k, v in edbs.items()})
    batch = _batch("TC")
    for i, (ins, dele) in enumerate(gen_stream(21, edbs, 5)):
        out = inc.apply(inserts=ins, deletes=dele)
        ref, _ = batch.run(_current_edbs(inc))
        _assert_states_equal(out, ref,
                             f"program=TC mode=device seed=21 step={i}")


def test_divergence_fails_loudly():
    """An injected divergence (EDB mirror corrupted mid-stream) must
    trip the differential assertion with the reproducing seed in the
    message — the harness is sensitive, not vacuous."""
    with pytest.raises(AssertionError) as exc:
        _run_stream("TC", n_steps=6, seed=7, sabotage_at=3)
    msg = str(exc.value)
    assert "seed=7" in msg and "step=3" in msg and "divergence" in msg


# -- sharded maintenance: byte-identical to single-device, per step ----------

def _run_sharded_stream(program: str, shards: int, backend: str = "jnp",
                        n_steps: int = 6, seed: int = 11) -> None:
    """Same stream through the single-device and sharded incremental
    engines: snapshots AND iteration counts must match after every
    step, and the final state must match batch recompute."""
    _need(shards)
    edbs = _edbs(program)
    ref = _inc(program, backend)
    sh = _inc(program, backend, shards=shards)
    o_ref = ref.initialize({k: v.copy() for k, v in edbs.items()})
    o_sh = sh.initialize({k: v.copy() for k, v in edbs.items()})
    ctx0 = (f"program={program} backend={backend} shards={shards} "
            f"seed={seed}")
    _assert_states_equal(o_ref, o_sh, ctx0 + " step=init")
    for i, (ins, dele) in enumerate(gen_stream(seed, edbs, n_steps)):
        a = ref.apply(inserts={k: v.copy() for k, v in ins.items()},
                      deletes={k: v.copy() for k, v in dele.items()})
        b = sh.apply(inserts={k: v.copy() for k, v in ins.items()},
                     deletes={k: v.copy() for k, v in dele.items()})
        ctx = f"{ctx0} step={i}"
        _assert_states_equal(a, b, ctx)
        assert ref._stats.iterations == sh._stats.iterations, (
            f"iteration-count divergence: {ctx}: "
            f"{ref._stats.iterations} != {sh._stats.iterations}")
    batch, _ = _batch(program, backend).run(_current_edbs(sh))
    _assert_states_equal(b, batch, ctx0 + " step=final-vs-batch")


# -- crash-replay differential (engine/resilience.py) ------------------------
#
# Property: a durable engine driven through the SAME stream while a
# seeded fault plan injects crashes at random fault sites — each crash
# followed by a cold restart (recover = snapshot restore + log replay,
# then client re-submission of the in-flight batch) — must be
# byte-identical to the uninterrupted run after every step: same
# snapshots AND same maintenance iteration counts.

CRASH_SITES = (
    "resilience.after_log",    # logged but not applied
    "wal.before_append",       # batch never became durable
    "incremental.apply",       # died entering maintenance
    "incremental.maintain",    # died mid-apply, partial in-memory state
    "checkpoint.commit",       # died mid-snapshot (tmp left behind)
    "checkpoint.retention",    # snapshot published, cleanup lost
)


def _reference_trail(program: str, backend: str, edbs: dict,
                     steps: list) -> tuple[list, list]:
    """Per-step snapshots + iteration dicts of the uninterrupted run
    (no fault plan active: the reference must never see a fault)."""
    ref = _inc(program, backend)
    outs = [ref.initialize({k: v.copy() for k, v in edbs.items()})]
    iters = [dict(ref._stats.iterations)]
    for ins, dele in steps:
        outs.append(ref.apply(
            inserts={k: v.copy() for k, v in ins.items()},
            deletes={k: v.copy() for k, v in dele.items()}))
        iters.append(dict(ref._stats.iterations))
    return outs, iters


def _run_crash_replay_stream(program: str = "TC", backend: str = "jnp",
                             shards: int = 0, n_steps: int = 8,
                             seed: int = 31, n_crashes: int = 4,
                             state_dir=None, plan=None) -> int:
    """Drive one crash-replay differential stream; returns the number
    of crashes absorbed. ``plan`` overrides the seeded random plan with
    an explicit fault schedule (the named-site tests use this)."""
    import tempfile

    from repro.engine import faults
    from repro.engine.faults import FaultPlan, SimulatedCrash
    from repro.engine.resilience import (
        DurableIncrementalEngine, ResilienceConfig,
    )

    if shards:
        _need(shards)
    edbs = _edbs(program)
    steps = gen_stream(seed, edbs, n_steps)
    ref_outs, ref_iters = _reference_trail(program, backend, edbs, steps)

    cp = compile_program(_source(program))
    rcfg = ResilienceConfig(snapshot_every=3)
    if plan is None:
        plan = FaultPlan.seeded(seed, CRASH_SITES, n_faults=n_crashes,
                                max_hit=max(2, n_steps))
    tmp_ctx = (tempfile.TemporaryDirectory() if state_dir is None
               else None)
    d = tmp_ctx.name if tmp_ctx else state_dir
    crashes = 0
    box = {}

    def fresh():
        return DurableIncrementalEngine(
            cp, _cfg(kernel_backend=backend, shards=shards),
            directory=d, resilience=rcfg)

    def restart():
        nonlocal crashes
        while True:                 # recovery itself may crash again
            try:
                box["dur"].close()
                box["dur"] = fresh()
                if box["dur"].recoverable():
                    box["dur"].recover()
                else:               # died before snapshot 0 landed
                    box["dur"].initialize(
                        {k: v.copy() for k, v in edbs.items()})
                return
            except SimulatedCrash:
                crashes += 1

    def until_done(op):
        nonlocal crashes
        while True:
            try:
                return op()
            except SimulatedCrash:
                crashes += 1
                restart()           # then re-submit the in-flight op

    try:
        box["dur"] = fresh()
        with faults.install(plan):
            until_done(lambda: box["dur"].initialize(
                {k: v.copy() for k, v in edbs.items()}))
            for i, (ins, dele) in enumerate(steps):
                out = until_done(lambda: box["dur"].apply(
                    inserts={k: v.copy() for k, v in ins.items()},
                    deletes={k: v.copy() for k, v in dele.items()}))
                ctx = (f"crash-replay program={program} "
                       f"backend={backend} shards={shards} seed={seed} "
                       f"step={i} fired={plan.fired}")
                _assert_states_equal(out, ref_outs[i + 1], ctx)
                assert (box["dur"].inc._stats.iterations
                        == ref_iters[i + 1]), (
                    f"iteration-count divergence: {ctx}: "
                    f"{box['dur'].inc._stats.iterations} != "
                    f"{ref_iters[i + 1]}")
        # clean cold restart after the stream: recovered state must
        # still equal the uninterrupted final state
        box["dur"].close()
        cold = fresh()
        final = cold.recover()
        _assert_states_equal(
            final, ref_outs[-1],
            f"crash-replay cold-restart program={program} "
            f"backend={backend} shards={shards} seed={seed}")
        assert cold.inc._stats.iterations == ref_iters[-1]
        cold.close()
    finally:
        if tmp_ctx:
            tmp_ctx.cleanup()
    return crashes


def test_crash_replay_matches_uninterrupted(tmp_path):
    """Seeded random crashes at every fault-site class: restore +
    replay is byte-identical (facts + iteration counts) to the
    uninterrupted run, after every step and after a cold restart."""
    crashes = _run_crash_replay_stream(
        "TC", n_steps=8, seed=31, state_dir=tmp_path)
    assert crashes >= 1, "fault plan must actually crash the stream"


@pytest.mark.parametrize("shards", (1, 2, 4, 8))
def test_sharded_update_stream(shards):
    """Seeded continuations and DRed deletions execute shard-local:
    byte-identical snapshots and iteration counts at every shard
    count, driven by a mixed insert/delete stream."""
    _run_sharded_stream("TC", shards)


@pytest.mark.parametrize("shards", (2, 8))
def test_sharded_update_stream_wide(shards):
    """Wide (multi-word key) programs maintain shard-locally too."""
    _run_sharded_stream("WideReach2", shards, n_steps=5, seed=12)


def test_sharded_update_stream_pallas():
    """sharded x pallas x incremental composes (interpret mode on CPU)."""
    _run_sharded_stream("TC", 2, backend="pallas-interpret", n_steps=4, seed=13)


def test_sharded_monoid_recompute_fallback():
    """MIN-monoid deletions fall back to stratum recompute — routed
    through the sharded driver, still byte-identical."""
    _run_sharded_stream("CC", 2, n_steps=5, seed=14)


def test_sharded_negation_stream():
    """Stratified negation (antijoin + psum'd ground guard) under
    sharded maintenance."""
    _run_sharded_stream("Negation", 2, n_steps=5, seed=15)
