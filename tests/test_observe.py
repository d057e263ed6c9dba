"""Observability layer (engine/observe.py): span-tree shape, registry
scoping, Chrome-trace schema, the relation.COUNTERS shim, and the
zero-overhead contract — observe-on vs observe-off byte-identical
fixpoints and iteration counts across jnp/pallas/sharded/incremental
configurations."""
from benchmarks.hostdevices import force_host_device_count

force_host_device_count()  # must precede the first jax device init

import json

import numpy as np
import pytest

import jax

from benchmarks.programs import equivalence_datasets
from repro.core.optimizer import compile_program
from repro.engine import (
    Engine, EngineConfig, Observation, make_engine, validate_chrome_trace,
)
from repro.engine import observe as O
from repro.engine import relation as RL

TWO_STRATA = """
.input edge
.input source
.output reach
reach(x) :- source(x).
reach(y) :- reach(x), edge(x, y).
.output unreached
unreached(x) :- edge(x, _), !reach(x).
"""


def _cfg(**kw):
    d = dict(idb_cap=1 << 10, intermediate_cap=1 << 12,
             kernel_backend="jnp")
    d.update(kw)
    return EngineConfig(**d)


def _edbs(rng):
    return {"edge": rng.integers(0, 30, size=(50, 2)),
            "source": np.array([[0]])}


# -- span tree shape ----------------------------------------------------------

def test_span_tree_two_strata(rng):
    obs = Observation("t")
    edbs = _edbs(rng)
    cfg = _cfg(observe=obs)
    out, stats = Engine(compile_program(TWO_STRATA), cfg).run(edbs)

    runs = obs.find("run")
    assert len(runs) == 1
    strata = obs.find("stratum")
    assert [s.attrs["key"] for s in strata] == ["s0", "s1"]

    # recursive stratum: one iteration span per loop pass, each carrying
    # the existing termination-read delta cardinality
    rec = strata[0]
    iters = rec.find("iteration")
    assert rec.attrs["iterations"] == stats.iterations["s0"]
    assert len(iters) == stats.iterations["s0"]
    assert [s.attrs["delta_rows"] for s in iters] == \
        stats.delta_sizes["s0"][:len(iters)]
    assert iters[-1].attrs["delta_rows"] >= 1
    # per-IDB breakdown rides on each iteration span
    assert set(iters[0].attrs["deltas"]) == {"reach"}

    # nonrecursive stratum closes with zero loop iterations
    assert strata[1].attrs["iterations"] == 0

    # rule passes are children of their stratum, tagged with the head
    heads = {s.attrs["head"] for s in rec.find("rule")}
    assert heads == {"reach"}

    # spans nest: every child's window is inside its parent's
    def check_nesting(sp):
        for c in sp.children:
            assert c.t0 >= sp.t0 - 1e-9
            assert c.t1 <= sp.t1 + 1e-9
            check_nesting(c)
    for r in obs.roots:
        check_nesting(r)


def test_compile_spans_via_ambient(rng):
    obs = Observation("compile")
    with obs.activate():
        compile_program(TWO_STRATA)
    assert len(obs.find("compile")) == 1
    # one compile-rule span per lowered rule variant: reach nonrec,
    # reach delta-variant, unreached nonrec
    rules = obs.find("compile-rule")
    assert len(rules) == 3
    stages = {sp.attrs["stage"] for sp in obs.find("pass")}
    assert {"plan", "fusion", "sharing"} <= stages
    # no ambient observation -> compile stays span-free and works
    before = len(obs.roots)
    compile_program(TWO_STRATA)
    assert len(obs.roots) == before


def test_ambient_span_noop_without_activation():
    with O.ambient_span("x", a=1) as sp:
        assert sp is None


# -- metrics registry ---------------------------------------------------------

def test_registry_scope_windows_nest_and_accumulate():
    reg = O.MetricsRegistry()
    reg.inc("a.x", 5)
    with reg.scope("a.") as outer:
        reg.inc("a.x", 2)
        with reg.scope("a.") as inner:
            reg.inc("a.x", 3)
            reg.inc("a.y")
        reg.inc("b.z")  # outside the prefix
    assert inner == {"a.x": 3, "a.y": 1}
    assert outer == {"a.x": 5, "a.y": 1}
    # the registry keeps totals: scopes are windows, not resets
    assert reg.get("a.x") == 10
    assert reg.get("b.z") == 1


def test_registry_histograms_and_gauges():
    reg = O.MetricsRegistry()
    assert reg.percentiles("missing") is None
    for v in range(1, 101):
        reg.observe("lat", v / 100)
    p = reg.percentiles("lat")
    assert p["count"] == 100 and p["min"] == 0.01 and p["max"] == 1.0
    assert abs(p["p50"] - 0.5) < 0.02 and abs(p["p99"] - 0.99) < 0.02
    reg.gauge("g", 2.5)
    assert reg.get_gauge("g") == 2.5
    snap = reg.snapshot()
    assert snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["lat"]["count"] == 100


def test_relation_counters_shim_backed_by_registry():
    """The legacy COUNTERS mapping and the registry are the same store:
    writes through either side are visible on the other."""
    RL.reset_counters()
    base = O.REGISTRY.get("arrange.sorts")
    assert base == 0 and RL.COUNTERS["sorts"] == 0
    RL.COUNTERS["sorts"] += 3
    assert O.REGISTRY.get("arrange.sorts") == 3
    O.REGISTRY.inc("arrange.sorts")
    assert RL.COUNTERS["sorts"] == 4
    assert set(RL.COUNTERS) == {"sorts", "merge_sorted", "cache_hits",
                                "cache_misses", "cache_fastpath"}
    assert len(RL.COUNTERS) == 5
    RL.reset_counters()
    assert RL.COUNTERS["sorts"] == 0


# -- exporters ----------------------------------------------------------------

def test_chrome_trace_schema(rng, tmp_path):
    obs = Observation("t")
    Engine(compile_program(TWO_STRATA), _cfg(observe=obs)).run(_edbs(rng))
    trace = obs.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    assert trace["otherData"]["schema_version"] == O.SCHEMA_VERSION
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"run", "stratum", "iteration", "rule"} <= names
    for e in trace["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0

    # round-trips through JSON on disk and revalidates
    path = tmp_path / "trace.json"
    obs.save_chrome_trace(path)
    assert validate_chrome_trace(json.loads(path.read_text())) == []

    # the validator actually rejects malformed traces
    assert validate_chrome_trace({}) != []
    bad = {"traceEvents": [{"ph": "X", "ts": 0, "pid": 1, "tid": 1}]}
    assert any("name" in e for e in validate_chrome_trace(bad))


def test_report_and_dict_exports(rng):
    obs = Observation("t")
    Engine(compile_program(TWO_STRATA), _cfg(observe=obs)).run(_edbs(rng))
    rep = obs.fixpoint_report()
    assert "s0" in rep and "reach" in rep
    d = obs.to_dict()
    assert d["schema_version"] == O.SCHEMA_VERSION
    assert [s["stratum"] for s in d["strata"]] == ["s0", "s1"]
    traj = d["strata"][0]["delta_trajectory"]
    assert len(traj) == d["strata"][0]["iterations"]
    assert all(isinstance(x, int) and x > 0 for x in traj)
    assert d["rules"] and abs(
        sum(r["share"] for r in d["rules"]) - 1.0) < 0.05
    json.dumps(d)  # stable = plain-JSON serializable


# -- zero-overhead contract: observe on/off byte-identical --------------------

def _run_pair(src, edbs, **cfg_kw):
    compiled = compile_program(src)
    obs = Observation("diff")
    out_on, st_on = make_engine(
        compiled, _cfg(observe=obs, **cfg_kw)).run(dict(edbs))
    out_off, st_off = make_engine(
        compiled, _cfg(**cfg_kw)).run(dict(edbs))
    assert out_on.keys() == out_off.keys()
    for name in out_on:
        np.testing.assert_array_equal(out_on[name], out_off[name])
    assert st_on.iterations == st_off.iterations
    return obs


@pytest.mark.parametrize("program", ["TC", "SG", "Negation", "Sum"])
def test_observe_off_identical_jnp(program):
    src, edbs = equivalence_datasets()[program]
    obs = _run_pair(src, edbs)
    assert obs.find("run")


def test_observe_off_identical_pallas():
    src, edbs = equivalence_datasets()["TC"]
    _run_pair(src, edbs, kernel_backend="pallas-interpret")


def test_observe_off_identical_device_mode():
    src, edbs = equivalence_datasets()["TC"]
    obs = _run_pair(src, edbs, mode="device")
    # device mode hides iterations inside lax.while_loop: the stratum
    # span records the post-hoc count, no per-iteration spans exist
    st = obs.find("stratum")[0]
    assert st.attrs["iterations"] >= 1
    assert not st.find("iteration")
    assert obs.find("fixpoint-loop")


def test_observe_off_identical_sharded():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    src, edbs = equivalence_datasets()["TC"]
    obs = _run_pair(src, edbs, shards=2)
    # sharded iteration spans carry mesh-summed delta cardinalities
    iters = obs.find("iteration")
    assert iters and all(s.attrs["delta_rows"] > 0 for s in iters)
    assert O.REGISTRY.get("shard.all_to_all.launches") > 0


def test_observe_off_identical_incremental(rng):
    src, edbs = equivalence_datasets()["TC"]
    compiled = compile_program(src)
    obs = Observation("inc")
    inc_on = make_engine(compiled, _cfg(observe=obs), incremental=True)
    inc_off = make_engine(compiled, _cfg(), incremental=True)
    inc_on.initialize(dict(edbs))
    inc_off.initialize(dict(edbs))
    for step in range(3):
        ins = {"edge": rng.integers(0, 16, size=(2, 2))}
        dele = {"edge": np.array(sorted(map(tuple, inc_on.edbs["edge"])))
                [step:step + 1]}
        out_on = inc_on.apply(inserts=dict(ins), deletes=dict(dele))
        out_off = inc_off.apply(inserts=dict(ins), deletes=dict(dele))
        assert out_on.keys() == out_off.keys()
        for name in out_on:
            np.testing.assert_array_equal(out_on[name], out_off[name])
    # per-update metrics landed in the observation registry
    lat = obs.registry.percentiles("update.latency_s")
    assert lat and lat["count"] == 3
    assert obs.registry.percentiles("update.delta_rows")["count"] == 3
    applies = obs.find("apply")
    assert len(applies) == 3
    strategies = {s.attrs["strategy"]
                  for a in applies for s in a.find("maintain-stratum")}
    assert strategies <= {"seed-insert", "dred", "recompute"}
    assert strategies
