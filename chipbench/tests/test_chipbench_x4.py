"""The four-chip cell ``reach-s16.batch.x4`` driven whole through the
harness on four virtual CPU devices, at Graph500 scale 7 with the
portable kernels: correct, equal to the one-chip cell on the same seed,
and within the configuration's per-shard capacities.

The devices are forced before JAX starts, so the run is made in a
process of its own (``XLA_FLAGS``); it prints one JSON line."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from chipbench import bench, control

SEED = 2**31 + 1515

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
import numpy as np
jax.config.update("jax_enable_compilation_cache", False)
from chipbench import bench, check
from chipbench import run as R
from chipbench.tests.conftest import shrink
from repro.engine.engine import Engine
from repro.engine.relation import pow2_cap

outputs, engines = {{}}, []
real_compare, real_run = check.compare, Engine.run


def compare(got, want):
    outputs[cell.name] = [np.asarray(g).tolist() for g in got]
    return real_compare(got, want)


def run(self, edbs, edb_caps=None):
    out, stats = real_run(self, edbs, edb_caps)
    engines.append([type(self).__name__, getattr(self, "num_shards", 1),
                    stats.grow_retries])
    return out, stats


check.compare, Engine.run = compare, run
result = {{"devices": len(jax.devices())}}
for name in ("reach-s16.batch.x4", "reach-s16.batch"):
    cell = shrink(bench.resolve(name))
    if cell.chips == 4:
        # the configuration's own rule, per shard, at this scale
        graph = cell.generator.generate(cell.config, {seed})
        rows = len(graph.relation(cell.config["edbs"]["edge"]))
        cell.config["caps"] = {{
            "idb_cap": pow2_cap(graph.vertices // 4),
            "intermediate_cap": pow2_cap(2 * rows // 4)}}
    engines.clear()
    out = R.run_cell(cell, {seed}, 0.5, False, require_chip=False,
                     start=time.perf_counter())
    diagnostics = out.pop("_diagnostics")
    result[name] = {{"out": out, "engines": list(engines),
                    "caps": cell.config["caps"],
                    "compiles_in_window": diagnostics["compiles_in_window"]}}
result["outputs_equal"] = (outputs["reach-s16.batch.x4"][0]
                           == outputs["reach-s16.batch"][0])
print(json.dumps(result))
"""


@pytest.fixture(scope="module")
def runs():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(bench.ROOT),
                                             seed=SEED)],
        cwd=bench.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_runs_sharded_over_four_devices(runs):
    assert runs["devices"] == 4
    assert bench.resolve("reach-s16.batch.x4").chips == 4
    engines = runs["reach-s16.batch.x4"]["engines"]
    assert engines and all(e[:2] == ["ShardedEngine", 4] for e in engines)


def test_the_cell_is_correct(runs):
    out = runs["reach-s16.batch.x4"]["out"]
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["checks"] == {"mismatched_rows": {"value": 0, "limit": 0}}


def test_four_chips_give_the_one_chip_answer(runs):
    assert runs["reach-s16.batch"]["out"]["correct"] is True
    assert runs["outputs_equal"] is True


def test_per_shard_caps_grow_nothing(runs):
    x4 = runs["reach-s16.batch.x4"]
    # scale 7: 128 vertices; a quarter each, so 32 -> 64 rows a shard
    assert x4["caps"]["idb_cap"] == 64
    assert [e[2] for e in x4["engines"]] == [0] * len(x4["engines"])


@pytest.mark.parametrize("name", ["reach-s16.batch.x4", "reach-s16.batch"])
def test_nothing_compiles_in_the_window(runs, name):
    assert runs[name]["out"]["attempted"] >= 1
    assert runs[name]["compiles_in_window"] == 0


def test_control_is_not_correct(tiny):
    got = control.readings(tiny("reach-s16.batch.x4"), SEED, 10)
    assert got["correct"] is False and got["mismatched_rows"] > 0
