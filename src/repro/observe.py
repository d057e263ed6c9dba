"""``python -m repro.observe`` — fixpoint profiler.

Runs a demo Datalog fixpoint with the engine observability layer
(``repro.engine.observe``) attached and prints the fixpoint report
(per-stratum iteration/delta table, per-rule time share, metrics).
With ``--trace DIR`` the run is recorded by ``jax.profiler`` into DIR:
the engine's spans (``flowlog/run``, ``flowlog/stratum``,
``flowlog/iteration``, ...) sit on the host plane of that record and
the device's ops on theirs, on one clock, for TensorBoard's profiler
or Perfetto. Wired as ``make trace-smoke``: the CI bench-smoke job runs
the demo with ``--trace`` and fails unless the record holds the
engine's spans.

Usage::

    python -m repro.observe                          # demo TC, print report
    python -m repro.observe --demo monitor           # 2-stratum demo
    python -m repro.observe --trace /tmp/trace       # + profiler record
    python -m repro.observe --updates 20             # + incremental stream
    python -m repro.observe --json                   # stable dict

Demo programs are built in (no dataset files needed); ``--mode device``
shows the post-hoc summary path (iterations inside ``lax.while_loop``
are opaque to the host, so per-iteration delta cardinalities are only
available in host mode — see the ``repro.engine.observe`` docstring).
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


# -- built-in demo programs (scaled by --size) --------------------------------

def _demo_tc(size: int):
    src = """
    .input edge
    .output tc
    tc(x,y) :- edge(x,y).
    tc(x,z) :- tc(x,y), edge(y,z).
    """
    rng = np.random.default_rng(0)
    edges = rng.integers(0, size, size=(size * 2, 2))
    return src, {"edge": edges}


def _demo_monitor(size: int):
    # 2 strata: recursive reachability + monoid shortest hop count,
    # then a stratified negation view — exercises stratum spans,
    # monoid merge, and antijoin in one trace.
    src = """
    .input link
    .input monitor
    .output reaches
    reaches(x) :- monitor(x).
    reaches(y) :- reaches(x), link(x, y).
    .output pathlen
    pathlen(x, MIN(0)) :- monitor(x).
    pathlen(y, MIN(d + 1)) :- pathlen(x, d), link(x, y).
    .output dark
    dark(x) :- link(x, _), !reaches(x).
    """
    rng = np.random.default_rng(0)
    links = rng.integers(0, size, size=(size * 3, 2))
    return src, {"link": links, "monitor": np.array([[0]])}


DEMOS = {"tc": _demo_tc, "monitor": _demo_monitor}


def read_spans(logdir) -> list[tuple[str, float, float]]:
    """The engine's spans in the profiler record under ``logdir``:
    ``(name, start_ns, end_ns)`` with the ``flowlog/`` prefix taken
    off, in order of start."""
    from pathlib import Path

    from jax.profiler import ProfileData
    from repro.engine.observe import PREFIX

    spans = []
    for path in sorted(Path(logdir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return sorted(spans, key=lambda s: s[1])


def _run_demo(args) -> None:
    from repro.core.optimizer import compile_program
    from repro.engine import EngineConfig, make_engine
    from repro.engine import observe as O

    src, edbs = DEMOS[args.demo](args.size)
    obs = O.Observation(f"demo:{args.demo}")
    with obs.activate():
        compiled = compile_program(src)
    cfg = EngineConfig(
        idb_cap=1 << 13, intermediate_cap=1 << 15,
        mode=args.mode, kernel_backend=args.backend, shards=args.shards,
        observe=obs)

    if args.updates:
        inc = make_engine(compiled, cfg, incremental=True)
        inc.initialize(edbs)
        rng = np.random.default_rng(1)
        name, rows = next(iter(edbs.items()))
        hi = int(rows.max()) + 1
        for _ in range(args.updates):
            ins = rng.integers(0, hi, size=(3, rows.shape[1]))
            cur = np.array(sorted(map(tuple, inc.edbs[name])))
            dele = cur[rng.permutation(len(cur))[:2]]
            inc.apply(inserts={name: ins}, deletes={name: dele})
    else:
        make_engine(compiled, cfg).run(edbs)

    if args.json:
        print(json.dumps(obs.to_dict(), indent=2, default=str))
    else:
        print(obs.fixpoint_report())


def _traced_demo(args) -> int:
    """The demo under ``jax.profiler.trace``; fails unless the record
    holds the fixpoint's lifecycle (host mode exposes per-iteration
    spans; device mode only the stratum summary)."""
    import jax

    with jax.profiler.trace(args.trace):
        _run_demo(args)
    names = {name for name, _, _ in read_spans(args.trace)}
    need = {"run", "stratum"}
    if args.mode == "host":
        need |= {"iteration"}
    missing = sorted(need - names)
    if missing:
        print(f"trace INVALID: no {', '.join(missing)} span(s) in the "
              f"profiler record under {args.trace}")
        return 1
    print(f"trace: {args.trace} (profiler record; engine spans: "
          f"{', '.join(sorted(names))})")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.observe",
        description="Fixpoint profiler: run a demo with tracing on, "
                    "print the report, record a profiler trace")
    ap.add_argument("--demo", choices=sorted(DEMOS), default="tc")
    ap.add_argument("--size", type=int, default=64,
                    help="demo graph node count (default 64)")
    ap.add_argument("--mode", choices=("host", "device"), default="host")
    ap.add_argument("--backend", choices=("jnp", "pallas", "pallas-interpret"),
                    default="jnp")
    ap.add_argument("--shards", type=int, default=0)
    ap.add_argument("--updates", type=int, default=0,
                    help="also run N incremental update batches and "
                         "report per-update latency")
    ap.add_argument("--trace", metavar="DIR",
                    help="record the run with jax.profiler into DIR")
    ap.add_argument("--json", action="store_true",
                    help="print the stable dict instead of the report")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        return _traced_demo(args)
    _run_demo(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
