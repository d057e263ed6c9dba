"""Run one cell of BENCHMARK.json once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Set-up (counted in ``setup_s``) builds the cell's data from the seed,
loads the compiled programs from the persistent compilation cache and
warms up the cell's own shapes. The window then drives the cell's
traffic for ``--seconds``. After it closes, the device's peak memory is
read, the program's state is freed, and every output of the window is
compared with the configuration's reference. With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
profiler records the window's first operations (the driver's
``TRACED_STEPS``) and the result carries the per-layer metrics read
from that recording. The last line of standard output is the result; the
numbers compared, each with its limit, are the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, the
run exits 1 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):    # the program under test, and chipbench
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import bench as B  # noqa: E402
from chipbench import check as C  # noqa: E402
from chipbench import drive as D  # noqa: E402
from chipbench import trace as T  # noqa: E402


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What one run measured; the metric readers take it. A reader that
    needs more of the traffic than the window's samples reads it from
    ``driver``, the cell's driver object."""
    cell: B.Cell
    device_kind: str
    chips: int
    setup_s: float
    window: D.Window
    compiles_in_window: int
    trace: "T.Trace | None"
    driver: object


def devices_for(cell: B.Cell, require_chip: bool) -> list:
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {devices[0].platform!r}")
    if require_chip and len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, "
                     f"JAX sees {len(devices)}")
    return devices[:cell.chips]


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)


def run_cell(cell: B.Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, start: float | None = None) -> dict:
    """One run of ``cell``: its result object. ``require_chip=False``
    lets a test drive the whole run on the CPU."""
    start = PROCESS_START if start is None else start
    devices = devices_for(cell, require_chip)
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # keep every program, however quick to compile: the engine makes
    # small programs per row count, which every seed repeats, so that
    # only a checkout's first run compiles and none compiles in a window
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = D.CompileCounter()

    t_data = time.perf_counter()
    driver = cell.driver.Driver(cell, seed)
    t_engine = time.perf_counter()
    driver.setup(counter)
    window = D.Window(seconds)
    setup_s = time.perf_counter() - start
    setup_parts = {"imports_and_devices_s": t_data - start,
                   "data_s": t_engine - t_data,
                   "engine_and_warmup_s": start + setup_s - t_engine}
    c0, s0 = counter.count, counter.seconds
    capture = T.Capture() if trace else None
    try:
        window.run(driver.step, capture, cell.driver.TRACED_STEPS)
        recorded = capture.read() if capture else None
    finally:
        if capture:
            capture.discard()
    compiles = counter.count - c0
    compile_s_in_window = counter.seconds - s0
    memory = peak_bytes(devices)

    record = Run(cell=cell, device_kind=devices[0].device_kind,
                 chips=len(devices), setup_s=setup_s, window=window,
                 compiles_in_window=compiles,
                 trace=recorded, driver=driver)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        try:
            value = cell.readers[m["name"]].read(record)
        except T.NothingToRead as e:
            print(f"chipbench: {m['name']} left out: {e}", file=sys.stderr)
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    driver.release()
    gc.collect()
    result = C.compare(driver.outputs, driver.expected())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": C.correct(result),
           "attempted": len(window.samples),
           "failed": result["failed"],
           "metrics": metrics, "device": device}
    if record.trace is not None:
        cut = record.trace.cut_at()
        if cut is not None:
            lo, hi = record.trace.window_span()
            print(f"chipbench: the profiler's record ends "
                  f"{(cut - lo) / 1e9:.3f} s into the {(hi - lo) / 1e9:.3f} s "
                  f"traced (its buffer of device events was full): busy_s, "
                  f"window_s and the breakdown cover what it holds",
                  file=sys.stderr)
        busy = [T.busy_ns(record.trace, d.id) / 1e9 for d in devices]
        lo, hi = record.trace.window()
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = T.breakdown(record.trace, devices[0].id)
    out["checks"] = C.checks(result)
    out["_diagnostics"] = {"compiles_in_window": compiles,
                           "compile_s_in_window": compile_s_in_window,
                           "compile_s_total": counter.seconds,
                           "outputs_compared": result["compared"],
                           "window_s": window.length,
                           "setup_s": setup_s, **setup_parts}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no repro package under {ROOT / 'src'}; run "
              f"from a checkout of the repository", file=sys.stderr)
        return 1
    cell = B.resolve(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 1
    diag = out.pop("_diagnostics")
    print("chipbench: " + " ".join(f"{k}={v}" for k, v in diag.items()),
          file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
