"""Andersen points-to analysis (the paper's flagship domain) with the
optimizer ablation: plan+sip vs the DDlog-style no-opt baseline.

    PYTHONPATH=src python examples/program_analysis.py
"""
import time

import numpy as np

from repro.core.optimizer import CompileOptions, compile_program
from repro.engine import Engine, EngineConfig

ANDERSEN = """
.input addr      // p = &x
.input assign    // p = q
.input load      // p = *q
.input store     // *p = q
.output pt
pt(p, x) :- addr(p, x).
pt(p, x) :- assign(p, q), pt(q, x).
pt(p, x) :- load(p, q), pt(q, r), pt(r, x).
pt(r, x) :- store(p, q), pt(p, r), pt(q, x).
"""


def synthesize_program(n_vars=120, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "addr": rng.integers(0, n_vars, size=(n_vars // 2, 2)),
        "assign": rng.integers(0, n_vars, size=(n_vars, 2)),
        "load": rng.integers(0, n_vars, size=(n_vars // 3, 2)),
        "store": rng.integers(0, n_vars, size=(n_vars // 3, 2)),
    }


def main():
    edbs = synthesize_program()
    results = {}
    for label, opts in [
        ("flowlog (plan+sip)", CompileOptions()),
        ("no-opt (DDlog-like)", CompileOptions(
            use_planner=False, use_sip=False, use_fusion=False,
            use_sharing=False)),
    ]:
        cp = compile_program(ANDERSEN, opts)
        eng = Engine(cp, EngineConfig(idb_cap=1 << 15,
                                      intermediate_cap=1 << 17))
        t0 = time.perf_counter()
        out, stats = eng.run(edbs)
        wall = time.perf_counter() - t0
        results[label] = (wall, out["pt"].shape[0], stats)
        print(f"{label:22s} {wall:7.2f}s  pt={out['pt'].shape[0]:7d} "
              f"iters={stats.total_iterations}")
    facts = {r[1] for r in results.values()}
    assert len(facts) == 1, "optimizations must not change semantics"
    print("program_analysis OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
