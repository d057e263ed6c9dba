"""End-to-end incremental Datalog serving — the paper's 'kind' of
deployment (DDlog's use case, Sec. 9): materialize views over a live
fact stream, answer after every update batch, track latency.

    PYTHONPATH=src python examples/incremental_serving.py [--updates 30]

``--shards N`` serves the same stream from a hash-partitioned mesh
(incremental maintenance runs shard-local; see engine/incremental.py's
sharded-maintenance contract). On CPU, force host devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/incremental_serving.py --shards 8

``--durable DIR`` serves through the fault-tolerance layer
(engine/resilience.py): every batch is write-ahead logged before it is
applied and the state snapshots periodically, so the server survives
process death. The demo proves it: mid-stream it injects a simulated
crash (engine/faults.py) plus a transient capacity overflow, restarts
from snapshot + log replay, and prints the ``resilience.*`` counters —
crashes absorbed, updates replayed, and which degradation-ladder rungs
(capacity backoff / stratum recompute / full recompute) fired.
"""
import argparse
import contextlib
import tempfile
import time

import numpy as np

from repro.core.optimizer import compile_program
from repro.engine import EngineConfig, Observation, make_engine
from repro.engine import faults as F

# network reachability monitoring: link updates stream in; the view is
# which hosts can reach the monitoring target, avoiding quarantined ones
PROGRAM = """
.input link
.input monitor
.input quarantined
.output reaches
reaches(x) :- monitor(x).
reaches(y) :- reaches(x), link(x, y), !quarantined(y).
.output pathlen
pathlen(x, MIN(0)) :- monitor(x).
pathlen(y, MIN(d + 1)) :- pathlen(x, d), link(x, y), !quarantined(y).
"""


@contextlib.contextmanager
def _noop():
    yield


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=30)
    ap.add_argument("--hosts", type=int, default=200)
    ap.add_argument("--shards", type=int, default=0,
                    help="serve from an N-shard mesh (needs N devices)")
    ap.add_argument("--durable", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="serve through the durable resilience layer "
                         "(WAL + snapshots in DIR, default a tempdir), "
                         "with a mid-stream crash/recover demo")
    args = ap.parse_args()

    rng = np.random.default_rng(1)
    links = rng.integers(0, args.hosts, size=(args.hosts * 4, 2))

    # the engine's own metrics layer measures each apply() from the
    # inside: maintenance latency (excluding snapshot export) and the
    # IDB rows actually changed per batch — engine/observe.py
    obs = Observation("serving")
    cfg = EngineConfig(idb_cap=1 << 12, intermediate_cap=1 << 14,
                       shards=args.shards, observe=obs)
    cp = compile_program(PROGRAM)
    tmp = None
    plan = None
    if args.durable is not None:
        from repro.engine.resilience import (
            DurableIncrementalEngine, ResilienceConfig,
        )
        state_dir = args.durable
        if not state_dir:
            tmp = tempfile.TemporaryDirectory()
            state_dir = tmp.name
        rcfg = ResilienceConfig(snapshot_every=10)

        def fresh():
            return DurableIncrementalEngine(
                cp, cfg, directory=state_dir, resilience=rcfg)
        dur = fresh()
        inc = dur.inc
        # the demo's fault schedule: one crash between WAL append and
        # apply, plus a transient overflow the ladder must absorb
        plan = F.FaultPlan([
            F.FaultSpec("resilience.after_log", kind="crash",
                        hit=max(2, args.updates // 2)),
            F.FaultSpec("engine.rule_pass", kind="overflow",
                        hit=30, last=31),
        ])
    else:
        dur = None
        inc = make_engine(cp, cfg, incremental=True)

    t0 = time.perf_counter()
    edbs = {
        "link": links,
        "monitor": np.array([[0]]),
        "quarantined": np.array([[7], [23]]),
    }
    out = (dur or inc).initialize(edbs)
    print(f"initialized: {out['reaches'].shape[0]} reachable hosts "
          f"({time.perf_counter() - t0:.2f}s)"
          + (f" [durable, state in {state_dir}]" if dur else ""))

    crashes = 0
    with (F.install(plan) if plan else _noop()):
        for step in range(args.updates):
            ins = rng.integers(0, args.hosts, size=(3, 2))
            cur = np.array(sorted(inc.edbs["link"]))
            dele = cur[rng.permutation(len(cur))[:2]]
            batch = dict(inserts={"link": ins}, deletes={"link": dele})
            if dur is None:
                out = inc.apply(**batch)
                continue
            while True:
                try:
                    out = dur.apply(**batch)
                    break
                except F.SimulatedCrash:
                    crashes += 1
                    dur.close()
                    dur = fresh()
                    inc = dur.inc
                    dur.recover()   # snapshot + WAL replay
                    print(f"  step {step}: simulated crash — recovered "
                          f"at seq {dur.applied_seq}, re-submitting")

    lat = obs.registry.percentiles("update.latency_s")
    dlt = obs.registry.percentiles("update.delta_rows")
    strategies = {
        k.split(".", 1)[1]: v
        for k, v in obs.registry.counters_snapshot(
            "incremental.").items()
        if k.split(".", 1)[1] in ("seed-insert", "dred", "recompute")}
    print(f"{lat['count']} update batches: "
          f"maintenance p50={lat['p50'] * 1e3:.0f}ms "
          f"p99={lat['p99'] * 1e3:.0f}ms max={lat['max'] * 1e3:.0f}ms, "
          f"delta rows p50={dlt['p50']:.0f} max={dlt['max']:.0f}")
    print(f"strategies: {strategies}, "
          f"view={out['reaches'].shape[0]} hosts, "
          f"max hop count={out['pathlen'][:, 1].max()}")
    if dur is not None:
        res = obs.registry.counters_snapshot("resilience.")
        ladder = {k.rsplit(".", 1)[1]: v for k, v in res.items()
                  if k.startswith("resilience.ladder.")}
        print(f"resilience: {crashes} crash(es) absorbed, "
              f"{res.get('resilience.replayed_updates', 0)} update(s) "
              f"replayed from the WAL, "
              f"{res.get('resilience.snapshots', 0)} snapshot(s), "
              f"ladder rungs fired: {ladder or 'none'}")
        dur.checkpoint()
        dur.close()
        if tmp is not None:
            tmp.cleanup()
    print("incremental_serving OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
