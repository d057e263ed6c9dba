"""The sharded engine's tracing on four virtual CPU devices: the
``repartition`` scope in the compiled step, the wire counters of an
observed run, the spans of the sharded host loop, and an unobserved
run left exactly as it was.

The devices are forced before JAX starts, so the checks run in a
process of their own (``XLA_FLAGS``), which prints one JSON line."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec
from benchmarks.programs import REACH
from repro.core.optimizer import compile_program
from repro.engine import Engine, EngineConfig, Observation, make_engine
from repro.engine import shard as SH
from repro.engine.relation import PAD
from repro.engine.semiring import PRESENCE
from repro.launch.mesh import SHARD_AXIS, make_shard_mesh

out = {{"devices": len(jax.devices())}}

# one repartition: live rows and slots against a numpy count
S, cap = 4, 16
rng = np.random.default_rng(1)
data = rng.integers(0, 50, (S, cap, 2)).astype(np.int32)
live = rng.random((S, cap)) < 0.6
data[~live] = int(PAD)


traced_slots = []


def one(d, l):
    with SH.wire_tally() as tally:
        rel, _ = SH.repartition_rows(d[0], None, l[0], (0,), PRESENCE,
                                     2 * cap, S)
    (sent, slots), = tally
    traced_slots.append(slots)
    return sent[None], rel.n[None]


mesh = make_shard_mesh(S)
sent, n = jax.jit(jax.shard_map(
    one, mesh=mesh, in_specs=PartitionSpec(SHARD_AXIS),
    out_specs=PartitionSpec(SHARD_AXIS), check_vma=False))(data, live)
out["one"] = {{"sent": np.asarray(sent).tolist(),
              "traced_slots": traced_slots,
              "numpy_live": live.sum(axis=1).tolist(),
              "slots": S * cap,
              "received": int(np.asarray(n).sum()),
              "distinct": len({{tuple(r) for r in data[live].tolist()}})}}

# whole runs of REACH: observed, unobserved, single device
edges = rng.integers(0, 60, (240, 2))
edbs = {{"edge": edges, "source": np.array([[0]])}}
cp = compile_program(REACH)
steps, reads = [], [0]


class Counting:
    def __getattr__(self, k):
        return getattr(np, k)

    def asarray(self, *a, **k):
        reads[0] += 1
        return np.asarray(*a, **k)


real_memo = SH.ShardedEngine._memo_jit


def memo(self, key, make):
    fn = real_memo(self, key, make)
    if key[0] != "shard_iter":
        return fn

    def step(*args):
        res = fn(*args)
        steps.append([key[-1], len(res)])
        if "step_hlo_has_scope" not in out:
            out["step_hlo_has_scope"] = (
                "repartition" in fn.lower(*args).compile().as_text())
        return res
    return step


SH.ShardedEngine._memo_jit = memo
SH.np = Counting()


def cfg(**kw):
    return EngineConfig(idb_cap=64, intermediate_cap=512,
                        kernel_backend="jnp", **kw)


compiles = [0]


def on_event(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(on_event)
runs = {{}}
engine = make_engine(cp, cfg(shards=4))
for name, c, eng in [("single", cfg(), None), ("off", None, engine),
                     ("again", None, engine),
                     ("on", cfg(shards=4, observe=Observation("x")), None)]:
    reads[0] = 0
    compiles[0] = 0
    steps.clear()
    res, st = (eng or make_engine(cp, c)).run(dict(edbs))
    runs[name] = {{"reach": res["reach"].tolist(),
                  "iterations": st.iterations, "reads": reads[0],
                  "steps": list(steps), "compiles": compiles[0]}}
    if c is not None and c.observe is not None:
        reg = c.observe.registry
        runs[name]["live_rows"] = reg.get("shard.repartition.live_rows")
        runs[name]["slots"] = reg.get("shard.repartition.slots")
        runs[name]["spans"] = c.observe.roots[0].tree_lines()
out["runs"] = runs
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def got():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT.format(root=str(ROOT))],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


def test_compiled_step_carries_the_repartition_scope(got):
    assert got["step_hlo_has_scope"] is True


def test_wire_counts_of_one_repartition(got):
    one = got["one"]
    assert one["sent"] == one["numpy_live"]
    # the slots are static: one [S, cap] buffer, counted while tracing
    assert one["traced_slots"] == [one["slots"]]
    assert one["received"] == one["distinct"]


def test_observed_run_counts_its_wire(got):
    on = got["runs"]["on"]
    assert 0 < on["live_rows"] <= on["slots"]
    # the steps' slots: a whole number of [S, cap] send buffers
    assert on["slots"] % 4 == 0


def test_sharded_host_loop_opens_the_spans(got):
    lines = got["runs"]["on"]["spans"]
    names = [ln.split(" [")[0] for ln in lines]
    stripped = [n.strip() for n in names]
    for span in ("edb-load", "iteration", "final-merge", "export"):
        assert span in stripped
    # scatter-edb nests in edb-load
    i = stripped.index("edb-load")
    assert stripped[i + 1] == "scatter-edb"
    assert len(names[i + 1]) - len(stripped[i + 1]) > (
        len(names[i]) - len(stripped[i]))


def test_unobserved_run_is_unchanged(got):
    runs = got["runs"]
    assert runs["off"]["reach"] == runs["single"]["reach"] == \
        runs["on"]["reach"]
    assert runs["off"]["iterations"] == runs["single"]["iterations"] == \
        runs["on"]["iterations"]
    iters = sum(runs["off"]["iterations"].values())
    # unobserved: the step returns (state, overflow) and no wire counts
    assert runs["off"]["steps"] == [[False, 2]] * iters
    assert runs["on"]["steps"] == [[True, 3]] * iters
    # observed: one more read per step (init and each iteration); so
    # without an observation, no read is added
    assert runs["on"]["reads"] == runs["off"]["reads"] + 1 + iters


def test_a_repeated_sharded_run_compiles_nothing(got):
    """Every program of a sharded run, the EDB scatter included, is
    kept across runs of one engine, so a timed run compiles none."""
    runs = got["runs"]
    assert runs["off"]["compiles"] > 0
    assert runs["again"]["compiles"] == 0
    assert runs["again"]["reach"] == runs["off"]["reach"]
