"""``python -m repro.observe`` — fixpoint profiler / trace exporter.

Runs a demo Datalog fixpoint with the engine observability layer
(``repro.engine.observe``) attached, prints the fixpoint report
(per-stratum iteration/delta table, per-rule time share, metrics), and
optionally exports a Chrome ``trace_event`` JSON loadable in Perfetto /
``chrome://tracing``. Wired as ``make trace-smoke``: the CI bench-smoke
job runs the demo, exports a trace, and validates its schema.

Usage::

    python -m repro.observe                          # demo TC, print report
    python -m repro.observe --demo monitor           # 2-stratum demo
    python -m repro.observe --trace /tmp/trace.json  # export Chrome trace
    python -m repro.observe --updates 20             # + incremental stream
    python -m repro.observe --check /tmp/trace.json  # validate a trace file
    python -m repro.observe --json                   # stable dict (bench form)

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


def _run_demo(args) -> int:
    # engine imports deferred so --check works without touching jax
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

    if args.trace:
        from repro.engine.observe import validate_chrome_trace
        obs.save_chrome_trace(args.trace)
        trace = obs.to_chrome_trace()
        errs = validate_chrome_trace(trace)
        # beyond the schema: the fixpoint lifecycle must actually be in
        # the trace (host mode exposes per-iteration spans; device mode
        # only the stratum summary)
        names = {e["name"] for e in trace["traceEvents"]}
        need = {"run", "stratum"}
        if args.mode == "host":
            need |= {"iteration", "rule"}
        errs += [f"missing {m!r} span(s)" for m in sorted(need - names)]
        if errs:
            print(f"trace INVALID ({len(errs)} violation(s)):")
            for e in errs:
                print(f"  {e}")
            return 1
        print(f"trace: {args.trace} "
              f"({len(trace['traceEvents'])} events, schema ok, "
              f"spans: {', '.join(sorted(need))})")
    return 0


def _check(path: str) -> int:
    from repro.engine.observe import validate_chrome_trace
    with open(path) as f:
        trace = json.load(f)
    errs = validate_chrome_trace(trace)
    if errs:
        print(f"{path}: INVALID ({len(errs)} violation(s))")
        for e in errs:
            print(f"  {e}")
        return 1
    print(f"{path}: valid Chrome trace "
          f"({len(trace['traceEvents'])} events)")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.observe",
        description="Fixpoint profiler: run a demo with tracing on, "
                    "print the report, export/validate Chrome traces")
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
    ap.add_argument("--trace", metavar="PATH",
                    help="export Chrome trace_event JSON here")
    ap.add_argument("--json", action="store_true",
                    help="print the stable dict (bench row form) "
                         "instead of the report")
    ap.add_argument("--check", metavar="PATH",
                    help="validate an existing trace file and exit")
    args = ap.parse_args(argv)

    if args.check:
        return _check(args.check)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    return _run_demo(args)


if __name__ == "__main__":
    sys.exit(main())
