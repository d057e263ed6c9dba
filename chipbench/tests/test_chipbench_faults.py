"""``correct`` on whole runs driven on the CPU at a small size: true for
the program as it is, false for the control and for each fault the
timed path can have, planted underneath the harness."""
from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import control
from chipbench import drive as D
from chipbench import run as R
from repro.engine.engine import Engine
from repro.engine.incremental import IncrementalEngine
from repro.engine.relation import Relation

SEED = 2**31 + 99
BATCH = ["reach-s16.batch", "cc-s14.batch"]
CELLS = BATCH + ["reach-s16.insert"]


def drive(cell, seconds=0.5):
    return R.run_cell(cell, SEED, seconds, False, require_chip=False,
                      start=time.perf_counter())


def cell_for_faults(tiny, mp, name):
    """A cell in which every planted fault shows whatever the CPU's
    speed: an update cell at scale 8 with batches of 64 edges and a
    window of exactly 12 of them, so that some batches change the view
    (at scale 7 most batches of 8 edges leave it as it was)."""
    cell = tiny(name)
    if cell.traffic["driver"] != "updates":
        return cell

    def twelve_steps(self, step, capture=None, traced=1):
        self.start = time.perf_counter()
        for _ in range(12):
            t0 = time.perf_counter()
            rows = step()
            self.samples.append((t0, time.perf_counter(), rows))
        self.end = time.perf_counter()
    mp.setattr(D.Window, "run", twelve_steps)
    return tiny(name, scale=8, batch_edges=64)


def _state_unchanged_fixpoint(mp):
    """Each semi-naive iteration returns its state unchanged, with an
    empty frontier: the fixpoint stops after its first rules."""
    def step(self, state, base, rec, idbs, ev, monoid_names):
        import jax.numpy as jnp
        return ({n: (full, Relation(d.data, d.val, d.n * 0, d.order))
                 for n, (full, d) in state.items()},
                jnp.zeros((), bool))
    mp.setattr(Engine, "_stratum_iter", step)


def _half_the_edges(mp):
    real = Engine._edb_env
    mp.setattr(Engine, "_edb_env", lambda self, edbs, caps: real(
        self, {k: (v[::2] if k == "edge" else v) for k, v in edbs.items()},
        caps))


def _alter(out: dict) -> dict:
    name = "cc" if "cc" in out else "reach"
    rows = np.array(out[name], copy=True)
    rows[0, -1] += 1
    return {**out, name: rows}


def _answer_altered_fixpoint(mp):
    real = Engine._export
    mp.setattr(Engine, "_export",
               lambda self, env, stats: _alter(real(self, env, stats)))


def _state_unchanged_update(mp):
    mp.setattr(IncrementalEngine, "_insert_stratum",
               lambda self, sp, my_ins: None)


def _half_the_batch(mp):
    real = IncrementalEngine.apply
    mp.setattr(IncrementalEngine, "apply", lambda self, inserts=None,
               deletes=None: real(self, inserts={
                   k: v[: len(v) // 2] for k, v in (inserts or {}).items()},
                   deletes=deletes))


def _answer_altered_update(mp):
    real = IncrementalEngine.apply
    mp.setattr(IncrementalEngine, "apply", lambda self, **kw: _alter(
        real(self, **kw)))


FAULTS = {
    "fixpoints": [_state_unchanged_fixpoint, _half_the_edges,
                  _answer_altered_fixpoint],
    "updates": [_state_unchanged_update, _half_the_batch,
                _answer_altered_update],
}


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny, name):
    out = drive(tiny(name))
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["checks"] == {"mismatched_rows": {"value": 0, "limit": 0}}
    assert list(out)[-2:] == ["checks", "_diagnostics"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", range(3))
def test_fault_is_not_correct(tiny, monkeypatch, name, fault):
    cell = cell_for_faults(tiny, monkeypatch, name)
    FAULTS[cell.traffic["driver"]][fault](monkeypatch)
    out = drive(cell)
    assert out["correct"] is False
    assert out["checks"]["mismatched_rows"]["value"] > 0
    assert out["failed"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny, name):
    got = control.readings(tiny(name), SEED, 10)
    assert got["correct"] is False
    assert got["mismatched_rows"] > 0 and got["compared"] == 10
