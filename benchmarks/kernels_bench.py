"""Kernel micro-benchmarks (XLA reference path wall-times on this CPU;
relative scaling only — Pallas kernels target TPU and are validated in
interpret mode) plus end-to-end fixpoint benchmarks per kernel backend:
the same Datalog programs run under ``kernel_backend="jnp"`` and
``"pallas-interpret"`` so the dispatch layer's effect is measured
through the whole semi-naive loop, not per kernel. The pallas rows time
interpret mode — a correctness/lowering proxy, not the TPU speedup."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _time(fn, *args, repeats=5, **kw):
    fn(*args, **kw)[0].block_until_ready() if isinstance(
        fn(*args, **kw), tuple) else fn(*args, **kw).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.tree.map(
            lambda x: x.block_until_ready() if hasattr(
                x, "block_until_ready") else x, out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench() -> list[dict]:
    rng = np.random.default_rng(0)
    rows = []

    segs = jnp.asarray(np.sort(rng.integers(0, 4096, 65536)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(65536, 64)), jnp.float32)
    f = jax.jit(lambda v, s: ops.segment_reduce(v, s, 4096, "sum"))
    rows.append({"table": "kernels", "name": "segment_reduce_64k_x64",
                 "us_per_call": round(_time(f, vals, segs), 1)})

    build = jnp.asarray(np.sort(rng.integers(0, 1 << 40, 1 << 16)))
    probe = jnp.asarray(np.sort(rng.integers(0, 1 << 40, 1 << 16)))
    f = jax.jit(lambda b, p: ops.merge_probe_counts(b, p))
    rows.append({"table": "kernels", "name": "merge_probe_64k",
                 "us_per_call": round(_time(f, build, probe), 1)})

    x = jnp.asarray(rng.normal(size=(4096, 39)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(39, 10)), jnp.float32)
    f = jax.jit(ops.fm_interaction)
    rows.append({"table": "kernels", "name": "fm_interaction_4k",
                 "us_per_call": round(_time(f, x, v), 1)})

    q = jnp.asarray(rng.normal(size=(1, 8, 512, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 8, 512, 64)), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v))
    rows.append({"table": "kernels", "name": "attention_512_xla",
                 "us_per_call": round(_time(f, q, k, k), 1)})
    return rows


def bench_fixpoint_backends(repeats: int = 3) -> list[dict]:
    """End-to-end fixpoint wall time per kernel backend (ISSUE 1): one
    row per (program, backend), identical inputs, jnp vs pallas.
    TC/Reach hammer the join probe every iteration, Degree the segment
    reduce."""
    from benchmarks.programs import DEGREE, REACH, TC
    from repro.core.optimizer import compile_program
    from repro.engine import Engine, EngineConfig

    rng = np.random.default_rng(0)
    progs = {
        "TC": (TC, {"edge": rng.integers(0, 64, size=(220, 2))}),
        "Reach": (REACH, {"edge": rng.integers(0, 400, size=(1600, 2)),
                          "source": np.array([[0]])}),
        "Degree": (DEGREE,
                   {"edge": rng.integers(0, 256, size=(2000, 2))}),
    }
    rows = []
    for pname, (src, edbs) in progs.items():
        compiled = compile_program(src)
        for backend in ("jnp", "pallas-interpret"):
            eng = Engine(compiled, EngineConfig(
                idb_cap=1 << 13, intermediate_cap=1 << 15,
                kernel_backend=backend))
            best, iters = float("inf"), 0
            for _ in range(repeats):
                out, stats = eng.run({k: v.copy()
                                      for k, v in edbs.items()})
                best = min(best, stats.wall_s)
                iters = stats.total_iterations
            rows.append({
                "table": "backends", "program": pname,
                "backend": eng.backend.name, "wall_s": round(best, 4),
                "us_per_call": round(best * 1e6, 1), "iters": iters,
                "facts": int(sum(stats.total_facts.values()))})
    return rows
