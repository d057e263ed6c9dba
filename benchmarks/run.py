"""Benchmark runner — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,robustness]

Prints ``name,us_per_call,derived`` CSV rows (derived = JSON blob of the
table-specific fields) and writes results/bench.json.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

ALL_TABLES = ("table1", "seminaive", "robustness", "specialization",
              "incremental", "kernels", "backends", "sharding", "wide",
              "arrange", "observe", "resilience", "roofline")

# the cheap tables --smoke runs by default (CI bitrot guard: the bench
# harness executes end-to-end on every push, in seconds; resilience
# rides along so the crash-replay differential runs on every push)
SMOKE_TABLES = ("arrange", "incremental", "robustness", "observe",
                "resilience")


def collect(only=None, smoke: bool = False) -> list[dict]:
    only = set(only or (SMOKE_TABLES if smoke else ALL_TABLES))
    rows: list[dict] = []
    if "table1" in only:
        from benchmarks.paper_programs import bench
        rows += bench()
    if "seminaive" in only:
        from benchmarks.paper_programs import bench_seminaive_vs_naive
        rows += bench_seminaive_vs_naive()
    if "robustness" in only:
        from benchmarks.robustness import bench, summarize
        r = bench(smoke=smoke)
        rows += r + summarize(r)
    if "specialization" in only:
        from benchmarks.specialization import bench
        rows += bench()
    if "incremental" in only:
        from benchmarks.incremental import bench
        rows += bench(smoke=smoke)
    if "kernels" in only:
        from benchmarks.kernels_bench import bench
        rows += bench()
    if "backends" in only:
        from benchmarks.kernels_bench import bench_fixpoint_backends
        rows += bench_fixpoint_backends()
    if "sharding" in only:
        from benchmarks.sharding import bench as bench_sharding
        rows += bench_sharding()
    if "wide" in only:
        from benchmarks.wide import bench as bench_wide
        rows += bench_wide()
    if "arrange" in only:
        from benchmarks.arrange import bench as bench_arrange
        rows += bench_arrange(smoke=smoke)
    if "observe" in only:
        from benchmarks.observe import bench as bench_observe
        rows += bench_observe(smoke=smoke)
    if "resilience" in only:
        from benchmarks.resilience import bench as bench_resilience
        rows += bench_resilience(smoke=smoke)
    if "roofline" in only:
        from benchmarks.roofline import rows as roof_rows
        try:
            rows += roof_rows()
        except Exception as e:  # noqa: BLE001
            rows.append({"table": "roofline", "error": repr(e)})
    # every row is stamped with the observability export schema version
    # (repro.engine.observe.SCHEMA_VERSION) so report tooling can branch
    # on row shape across commits
    from repro.engine.observe import SCHEMA_VERSION
    for r in rows:
        r.setdefault("schema_version", SCHEMA_VERSION)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list of {ALL_TABLES}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny datasets, single repeat, cheap tables "
                         f"only (default {SMOKE_TABLES}) — the CI "
                         "push-tier bitrot guard for the bench harness")
    ap.add_argument("--out", default=None,
                    help="output json (default results/bench.json; "
                         "--smoke defaults to results/bench-smoke.json "
                         "so tiny rows never clobber real results)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = args.only.split(",") if args.only else None
    if args.out is None:
        args.out = ("results/bench-smoke.json" if args.smoke
                    else "results/bench.json")

    rows = collect(only, smoke=args.smoke)
    print("name,us_per_call,derived")
    for r in rows:
        name = "/".join(str(r.get(k)) for k in
                        ("table", "program", "arch", "name", "rule",
                         "shape", "setting", "order", "update_size",
                         "kind", "backend", "shards")
                        if r.get(k) is not None)
        us = r.get("us_per_call")
        if us is None:
            for k in ("flowlog_s", "incremental_s", "presence_s",
                      "median_s"):
                if r.get(k) is not None:
                    us = round(r[k] * 1e6, 1)
                    break
        derived = {k: v for k, v in r.items() if k != "table"}
        print(f"{name},{us},{json.dumps(derived)}")
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    # merge-update: a partial run (--only X) replaces only its own
    # tables' rows, preserving everything previously recorded
    kept = []
    if out.exists():
        ran = {r.get("table") for r in rows}
        try:
            kept = [r for r in json.loads(out.read_text())
                    if r.get("table") not in ran]
        except (ValueError, AttributeError):
            kept = []
    out.write_text(json.dumps(kept + rows, indent=1))
    print(f"\n# wrote {len(rows)} rows to {out} "
          f"({len(kept)} rows of other tables kept)")


if __name__ == "__main__":
    main()
