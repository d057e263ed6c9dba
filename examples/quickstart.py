"""Quickstart: write a Datalog program, run it batch, then update it
incrementally — the FlowLog workflow (paper Sec. 1-3).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.optimizer import CompileOptions, compile_program
from repro.engine import Engine, EngineConfig
from repro.engine.incremental import IncrementalEngine

PROGRAM = """
// multi-hop reachability with an excluded-node filter (negation)
.input edge
.input source
.input blocked
.output reach
reach(x) :- source(x).
reach(y) :- reach(x), edge(x, y), !blocked(y).

// connected components via recursive MIN aggregation (paper Sec. 9)
.output cc
cc(x, MIN(x)) :- edge(x, _).
cc(y, MIN(y)) :- edge(_, y).
cc(x, MIN(i)) :- edge(y, x), cc(y, i).
cc(x, MIN(i)) :- edge(x, y), cc(y, i).
"""


def main():
    rng = np.random.default_rng(0)
    edges = rng.integers(0, 50, size=(120, 2))

    # -- 1. compile: front-end -> structural optimizer -> fused IR
    compiled = compile_program(PROGRAM, CompileOptions())
    print("=== optimized IR (first stratum) ===")
    print(compiled.strata[1].plans[0].root.pretty()
          if len(compiled.strata) > 1 else
          compiled.strata[0].plans[0].root.pretty())

    # -- 2. batch evaluation
    engine = Engine(compiled, EngineConfig(
        idb_cap=1 << 12, intermediate_cap=1 << 14))
    out, stats = engine.run({
        "edge": edges,
        "source": np.array([[0]]),
        "blocked": np.array([[13]]),
    })
    print(f"\nreach: {out['reach'].shape[0]} nodes, "
          f"cc: {out['cc'].shape[0]} labeled, "
          f"iterations: {stats.iterations}, wall: {stats.wall_s:.3f}s")

    # -- 3. incremental maintenance (insert + delete)
    inc = IncrementalEngine(compiled, EngineConfig(
        idb_cap=1 << 12, intermediate_cap=1 << 14))
    inc.initialize({"edge": edges, "source": np.array([[0]]),
                    "blocked": np.array([[13]])})
    upd = inc.apply(inserts={"edge": np.array([[0, 49], [49, 13]])},
                    deletes={"edge": edges[:2]})
    print(f"after update: reach={upd['reach'].shape[0]} "
          f"cc={upd['cc'].shape[0]}")
    assert set(upd) >= {"reach", "cc"}
    print("quickstart OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
