"""Wide-relation (multi-word row key) benchmarks.

    PYTHONPATH=src python -m benchmarks.run --only wide     # make bench-wide

Two questions, one table:

* **Narrow-path overhead (the headline row).** Every <= 3-column key
  squeezes onto the legacy single-word probe seam, so the multi-word
  refactor must cost narrow programs ~nothing. Measured steady-state
  (jitted, post-compile, best of N) on arrangement-shaped data:

    - ``legacy_us``    — the pre-refactor formulation
                         (``pack_columns`` + ``KernelDispatch.probe``);
    - ``fastpath_us``  — the new code path
                         (``pack_key_words`` + the W = 1 squeeze) —
                         lowers to equivalent XLA, so
                         ``overhead_pct`` is measurement noise around 0;
    - ``multiword_us`` — the same keys forced through the 2-word path
                         (``relation.force_multiword()``): the word-loop
                         cost narrow programs would pay WITHOUT the fast
                         path, i.e. what the squeeze saves.

* **Wide fixpoints per backend.** The newly supported 4-6 column
  programs end-to-end under both kernel backends. On CPU these
  end-to-end times are compile-dominated (each run re-jits) and the
  pallas rows run in interpret mode — a correctness/lowering proxy, not
  a TPU speedup;
  the check that matters is identical facts + iterations per pair.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

REPEATS = 3


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _best(fn) -> float:
    fn()  # warm-up / compile
    return min(_timed(fn) for _ in range(REPEATS))


def _bench_narrow_probe_overhead() -> dict:
    import jax

    from repro.engine import relops as R
    from repro.engine.backend import JNP
    from repro.engine.relation import (
        force_multiword, from_numpy, live_mask, pack_columns,
        pack_key_words,
    )

    rng = np.random.default_rng(0)
    n = 1 << 14
    build = R.arrange(from_numpy(
        rng.integers(0, 1 << 20, size=(n, 2)), n), (0,))
    probe = R.arrange(from_numpy(
        rng.integers(0, 1 << 20, size=(n, 2)), n), (0,))

    def legacy(b, p):
        bk = pack_columns(b.data, (0,), live_mask(b))
        pk = pack_columns(p.data, (0,), live_mask(p))
        return JNP.probe(bk, pk)

    def fastpath(b, p):
        bw = pack_key_words(b.data, (0,), live_mask(b))
        pw = pack_key_words(p.data, (0,), live_mask(p))
        return R._probe_ranks(JNP, bw, pw)

    # distinct underlying function: jax.jit wrappers of the SAME
    # function share a trace cache, so jitting ``fastpath`` twice would
    # silently reuse whichever trace (forced or not) ran first
    def fastpath_forced(b, p):
        return fastpath(b, p)

    fns = {"legacy": jax.jit(legacy), "fastpath": jax.jit(fastpath)}
    jax.block_until_ready(fns["fastpath"](build, probe))
    with force_multiword():
        # the flag is trace-time: tracing inside the context bakes the
        # 2-word keys and the multi-word probe into this variant
        fns["multiword"] = jax.jit(fastpath_forced)
        jax.block_until_ready(fns["multiword"](build, probe))

    def once(f):
        t0 = time.perf_counter()
        jax.block_until_ready(f(build, probe))
        return (time.perf_counter() - t0) * 1e6

    samples = {k: [] for k in fns}
    keys = list(fns)
    for f in fns.values():
        jax.block_until_ready(f(build, probe))   # warm-up / compile
    for i in range(60):
        # interleaved AND rotated rounds, median estimator: per-call
        # times on this shared CPU spread 3-5x between min and max, so
        # a fixed order or a min-of-few estimator reports phantom
        # overheads either way
        for k in keys[i % len(keys):] + keys[:i % len(keys)]:
            samples[k].append(once(fns[k]))
    med = {k: statistics.median(v) for k, v in samples.items()}
    legacy_us, fast_us, multi_us = (
        med["legacy"], med["fastpath"], med["multiword"])
    return {
        "table": "wide", "name": "narrow_probe_overhead",
        "rows": n,
        "legacy_us": round(legacy_us, 1),
        "fastpath_us": round(fast_us, 1),
        "overhead_pct": round((fast_us / legacy_us - 1) * 100, 1),
        "multiword_us": round(multi_us, 1),
        "word_loop_pct": round((multi_us / legacy_us - 1) * 100, 1),
        "note": ("steady-state jitted probe on sorted 2-column "
                 "arrangements; fastpath vs legacy lower to equivalent XLA "
                 "(overhead_pct ~ 0 = noise), multiword forces 2-word "
                 "keys — the cost the W=1 squeeze avoids"),
    }


def bench() -> list[dict]:
    from benchmarks.programs import WIDE_PROGRAMS, equivalence_datasets
    from repro.core.optimizer import compile_program
    from repro.engine import Engine, EngineConfig

    rows: list[dict] = [_bench_narrow_probe_overhead()]

    def run(src, edbs, backend="jnp"):
        eng = Engine(compile_program(src),
                     EngineConfig(idb_cap=1 << 12,
                                  intermediate_cap=1 << 14,
                                  kernel_backend=backend))
        out, stats = eng.run({k: np.asarray(v) for k, v in edbs.items()})
        return out, stats

    datasets = equivalence_datasets()
    for name in WIDE_PROGRAMS:
        src, edbs = datasets[name]
        per_backend = {}
        for backend in ("jnp", "pallas-interpret"):
            res = {}
            t = _best(lambda: res.update(
                zip(("out", "stats"), run(src, edbs, backend))))
            out, stats = res["out"], res["stats"]
            per_backend[backend] = (t, out, stats)
            rows.append({
                "table": "wide", "program": name, "backend": backend,
                "median_s": round(t, 4),
                "facts": {k: int(v.shape[0]) for k, v in out.items()},
                "iterations": stats.total_iterations,
            })
        (_, oj, sj), (_, op_, sp) = (per_backend["jnp"],
                                     per_backend["pallas-interpret"])
        assert all(np.array_equal(oj[k], op_[k]) for k in oj)
        assert sj.iterations == sp.iterations
    return rows
