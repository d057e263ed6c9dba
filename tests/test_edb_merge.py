"""The stored EDB under maintenance (incremental.py ``_refresh_edb``).

An apply that only inserts into an EDB merges the new rows into the
stored arrangement on the device (``Engine._union_stored``); an apply
that deletes from it, and ``apply_base``, rebuild it from the host
mirror (``_rebase_edb``). Property: either way the stored EDB is
byte-identical to the rebuild — the mirror's sorted rows at
``pow2_cap`` of their count, in the driver's stored form — so every
downstream compiled pass sees the same shapes and arrays. Pinned on
the jnp and Pallas (interpret) backends, single-device and on 2
shards, across a power-of-two capacity boundary; plus the
``incremental.edb_merge`` / ``.edb_rebuild`` counters, steady-state
memo hits, and the resilience ladder's rollback after a merge.

Sharded cases skip on a single device, as in test_update_streams.py.
"""
from benchmarks.hostdevices import force_host_device_count

force_host_device_count()  # must precede the first jax device init

import numpy as np
import pytest

import jax

from repro.core.optimizer import compile_program
from repro.engine import Engine
from repro.engine import faults as F
from repro.engine.faults import FaultPlan, FaultSpec
from repro.engine.incremental import IncrementalEngine
from repro.engine.observe import Observation
from repro.engine.relation import from_numpy, pow2_cap
from repro.engine.resilience import (
    DurableIncrementalEngine, ResilienceConfig,
)

from test_update_streams import _cfg, _current_edbs, _need

REACH = """
.input edge
.input source
.output reach
reach(x) :- source(x).
reach(y) :- reach(x), edge(x, y).
"""

TC = """
.input edge
.output tc
tc(x,y) :- edge(x,y).
tc(x,z) :- tc(x,y), edge(y,z).
"""

# (backend, shards): the single-device and sharded drivers on both
# kernel backends
DRIVERS = (("jnp", 0), ("pallas-interpret", 0), ("jnp", 2),
           ("pallas-interpret", 2))
DRIVER_IDS = [f"{b.replace('pallas-interpret', 'pallas')}-shards{s}"
              for b, s in DRIVERS]


def _engine(src: str, backend: str = "jnp", shards: int = 0,
            **kw) -> IncrementalEngine:
    if shards:
        _need(shards)
    return IncrementalEngine(compile_program(src),
                             _cfg(kernel_backend=backend, shards=shards,
                                  **kw))


def _rebuilt(inc: IncrementalEngine, name: str):
    """The stored EDB as a rebuild from the mirror gives it."""
    rows = _current_edbs(inc)[name]
    return inc.engine._stored(
        {name: from_numpy(rows, pow2_cap(len(rows)))})[name]


def _assert_same_stored(got, want, ctx: str):
    assert type(got) is type(want), ctx
    assert getattr(got, "order", None) == getattr(want, "order", None), ctx
    assert got.val is None and want.val is None, ctx
    for leaf in ("data", "n"):
        a, b = getattr(got, leaf), getattr(want, leaf)
        assert jax.typeof(a) == jax.typeof(b), f"{leaf} {ctx}"
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{leaf} {ctx}")


def _fresh_edges(rng, have: set, k: int, dom: int) -> np.ndarray:
    out: list[tuple] = []
    while len(out) < k:
        row = tuple(int(v) for v in rng.integers(0, dom, size=2))
        if row not in have and row not in out:
            out.append(row)
    return np.array(out)


@pytest.mark.parametrize("backend,shards", DRIVERS, ids=DRIVER_IDS)
def test_insert_merge_is_byte_identical_to_rebuild(backend, shards):
    """Insert-only applies leave the stored EDB byte-identical to the
    rebuild after every apply — data, live count, capacity, dtype —
    including the apply whose rows cross 16 -> 32 capacity, and the
    view matches batch recompute."""
    rng = np.random.default_rng(41)
    inc = _engine(REACH, backend, shards)
    edges = _fresh_edges(rng, set(), 11, 12)
    inc.initialize({"edge": edges, "source": np.array([[0]])})
    batch = Engine(compile_program(REACH), _cfg(kernel_backend=backend))
    caps = []
    for step in range(4):
        new = _fresh_edges(rng, inc.edbs["edge"], 2, 12)
        # a present row and a repeated new row: the mirror drops both
        ins = np.concatenate([new, new[:1], edges[:1]])
        out = inc.apply(inserts={"edge": ins})
        ctx = f"backend={backend} shards={shards} step={step}"
        stored = inc._env[("edge", "full")]
        _assert_same_stored(stored, _rebuilt(inc, "edge"), ctx)
        caps.append(stored.capacity)
        ref, _ = batch.run(_current_edbs(inc))
        np.testing.assert_array_equal(out["reach"], ref["reach"],
                                      err_msg=ctx)
    assert caps[0] == 16 and caps[-1] == 32, caps


def _counts(obs: Observation) -> tuple[int, int]:
    reg = obs.registry
    return (reg.get("incremental.edb_merge"),
            reg.get("incremental.edb_rebuild"))


def test_refresh_counters_follow_the_path():
    """An insert-only apply counts one merge per changed EDB and no
    rebuild; an EDB with deletes, and apply_base, count rebuilds."""
    obs = Observation()
    inc = _engine(REACH, observe=obs)
    rng = np.random.default_rng(5)
    inc.initialize({"edge": _fresh_edges(rng, set(), 20, 16),
                    "source": np.array([[0]])})
    assert _counts(obs) == (0, 0)
    inc.apply(inserts={"edge": _fresh_edges(rng, inc.edbs["edge"], 3, 16)})
    assert _counts(obs) == (1, 0)
    inc.apply(inserts={"edge": _fresh_edges(rng, inc.edbs["edge"], 3, 16),
                       "source": np.array([[3]])})
    assert _counts(obs) == (3, 0)
    gone = np.array(sorted(inc.edbs["edge"])[:2])
    inc.apply(inserts={"source": np.array([[5]])},
              deletes={"edge": gone})
    assert _counts(obs) == (4, 1)
    # inserted and deleted in one apply: the rebuild re-syncs it
    inc.apply(inserts={"edge": _fresh_edges(rng, inc.edbs["edge"], 1, 16)},
              deletes={"edge": np.array(sorted(inc.edbs["edge"])[:1])})
    assert _counts(obs) == (4, 2)
    inc.apply_base(inserts={"edge": _fresh_edges(
        rng, inc.edbs["edge"], 2, 16)})
    assert _counts(obs) == (4, 3)
    for name in ("edge", "source"):
        _assert_same_stored(inc._env[(name, "full")], _rebuilt(inc, name),
                            f"rel={name}")


def test_steady_insert_applies_add_no_memo_miss():
    """After warm-up, insert applies of a fixed batch size execute
    compiled passes only: the union is memo-jitted on its shapes."""
    obs = Observation()
    inc = _engine(REACH, observe=obs)
    rng = np.random.default_rng(6)
    inc.initialize({"edge": _fresh_edges(rng, set(), 40, 30),
                    "source": np.array([[0]])})

    def insert():
        inc.apply(inserts={"edge": _fresh_edges(
            rng, inc.edbs["edge"], 4, 30)})

    for _ in range(2):
        insert()
    misses = obs.registry.get("memo_jit.miss")
    for _ in range(3):
        insert()
    assert inc._env[("edge", "full")].capacity == 64
    assert obs.registry.get("memo_jit.miss") == misses
    assert obs.registry.get("incremental.edb_merge") == 5


@pytest.mark.parametrize("how", ("planted-fault", "tiny-cap"))
def test_ladder_rolls_back_a_merged_edb(how, tmp_path):
    """An overflow in the seed pass after the EDB merge is absorbed by
    rung 1: the rollback restores the stored EDB with the mirror, the
    retry merges again, and the result matches batch recompute with
    the stored EDB equal to the rebuild."""
    obs = Observation()
    caps = ({} if how == "planted-fault"
            else {"idb_cap": 16, "intermediate_cap": 16})
    cp = compile_program(TC)
    dur = DurableIncrementalEngine(
        cp, _cfg(observe=obs, **caps), directory=tmp_path,
        resilience=ResilienceConfig(max_capacity_retries=4))
    dur.initialize({"edge": np.array([[0, 1], [5, 6]])})
    chain = np.array([[i, i + 1] for i in range(1, 5)])
    plan = FaultPlan([FaultSpec("engine.rule_pass", kind="overflow",
                                hit=1)] if how == "planted-fault" else [])
    with F.install(plan):
        out = dur.apply(inserts={"edge": chain})
    reg = obs.registry
    assert reg.get("resilience.ladder.capacity_backoff") >= 1
    assert reg.get("resilience.ladder.capacity_recovered") == 1
    assert reg.get("resilience.ladder.stratum_recompute") == 0
    tries = reg.get("resilience.ladder.capacity_backoff") + 1
    assert reg.get("incremental.edb_merge") == tries
    assert reg.get("incremental.edb_rebuild") == 0
    inc = dur.inc
    _assert_same_stored(inc._env[("edge", "full")], _rebuilt(inc, "edge"),
                        how)
    ref, _ = Engine(cp, _cfg()).run(_current_edbs(inc))
    np.testing.assert_array_equal(out["tc"], ref["tc"])
    dur.close()
