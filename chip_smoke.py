#!/usr/bin/env python3
"""Smoke test of the FlowLog-JAX engine on a TPU.

    python chip_smoke.py              # one chip: phases (a)-(c) below
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

Drives the engine through the entry points a user calls
(``compile_program`` -> ``make_engine`` -> ``run`` / ``initialize`` /
``apply``) with ``kernel_backend="auto"``, which on a TPU resolves to the
compiled Pallas kernels, and checks every answer against a plain
reference. The data is a seeded Graph500 Kronecker graph (initiator
0.57/0.19/0.19/0.05, edge factor 16: the generator behind LDBC
Graphalytics' ``graph500-22``), cut to a smaller scale (``--scale``).

One chip:
  (a) batch fixpoints of ``benchmarks/programs.CC`` and ``REACH`` in
      ``mode="host"`` and ``mode="device"`` against
      ``scipy.sparse.csgraph`` (weak components with their minimum
      vertex id; breadth-first order from the source);
  (b) ``make_engine(..., incremental=True)`` on ``REACH`` under insert
      and delete batches, checked after every batch;
  (c) one direct call of each engine kernel at engine sizes against
      numpy: probe, probe_multi (W=2), merge_ranks, and segment_reduce
      sum/min/max on int32, resident and tiled.
Four chips (``--chips 4``): ``ShardedEngine`` with ``shards=4`` on the
same CC and Reach inputs, compared byte for byte (facts and iterations)
with the single-device ``Engine`` and with scipy, plus the bytes of
engine state each device holds.

Each phase prints cold seconds (with compilation), warm seconds (a
second run on the same engine; none for ``--chips 4``), iterations,
fact counts, ``grow_retries``, the device's ``peak_bytes_in_use`` and,
beside each time, the seconds that call spent compiling. The cold
fixpoint runs of (a), with the cold half of (b), and those of
``--chips 4`` go in parallel threads so that their compiles overlap;
their seconds overlap too. These are smoke numbers, not benchmark
results. Any mismatch or error exits non-zero; without a TPU the script
exits non-zero before running anything. The last line of a passing run
is the JSON object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

# Graph500 Kronecker initiator and edge factor (Graph500 specification,
# section 3; LDBC Graphalytics graph500-22 uses scale 22)
KRONECKER = (0.57, 0.19, 0.19, 0.05)
EDGE_FACTOR = 16
SOURCE_SCALE = 22
# the run must end within 1200 s with a cold compile cache. On one v5e,
# scale 15 took 708 s; scale 16 was still in phase (a) at 1000 s, most
# of it compiling, then 157 s per warm CC run
DEFAULT_SCALE = 15
BATCH_EDGES = 1000
BATCHES = 4          # incremental batches, inserts and deletes alternating


def log(msg: str) -> None:
    print(msg, flush=True)


# -- data and references -------------------------------------------------------

def kronecker_edges(scale: int, seed: int) -> np.ndarray:
    """Graph500 Kronecker edge list [16 * 2**scale, 2] (int64 ids below
    2**scale), with the specification's vertex and edge permutations.
    Duplicate edges and self loops are kept, as the generator emits
    them."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, EDGE_FACTOR << scale
    a, b, c, _ = KRONECKER
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << bit
        dst |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    order = rng.permutation(m)
    return np.stack([perm[src[order]], perm[dst[order]]], axis=1)


def _csr(edges: np.ndarray, n: int):
    from scipy.sparse import csr_matrix
    return csr_matrix((np.ones(len(edges), np.int8),
                       (edges[:, 0], edges[:, 1])), shape=(n, n))


def ref_cc(edges: np.ndarray, n: int) -> np.ndarray:
    """cc(x, m): every vertex on an edge with the least vertex id of its
    weakly connected component."""
    from scipy.sparse.csgraph import connected_components
    _, label = connected_components(_csr(edges, n), directed=True,
                                    connection="weak")
    least = np.full(label.max() + 1, n, np.int64)
    np.minimum.at(least, label, np.arange(n))
    verts = np.unique(edges)
    return np.stack([verts, least[label[verts]]], axis=1)


def ref_reach(edges: np.ndarray, n: int, source: int) -> np.ndarray:
    """reach(y): vertices in breadth-first order from ``source``."""
    from scipy.sparse.csgraph import breadth_first_order
    order = breadth_first_order(_csr(edges, n), source, directed=True,
                                return_predecessors=False)
    return np.sort(order).astype(np.int64)[:, None]


def sorted_rows(a: np.ndarray) -> np.ndarray:
    a = np.asarray(a, np.int64)
    if a.ndim == 1:
        a = a[:, None]
    return a[np.lexsort(a.T[::-1])] if len(a) else a


def check(what: str, got: np.ndarray, want: np.ndarray) -> None:
    got, want = sorted_rows(got), sorted_rows(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        raise AssertionError(
            f"{what}: engine {got.shape} != reference {want.shape}")


# -- phases --------------------------------------------------------------------

class Workload:
    """The seeded graph, the programs and the presized capacities."""

    def __init__(self, scale: int, seed: int):
        from benchmarks.programs import CC, REACH
        from repro.core.optimizer import compile_program
        from repro.engine.relation import pow2_cap

        t0 = time.perf_counter()
        self.n = 1 << scale
        self.edges = kronecker_edges(scale, seed)
        outdeg = np.bincount(self.edges[:, 0], minlength=self.n)
        self.source = int(np.random.default_rng(seed + 1).choice(
            np.flatnonzero(outdeg)))
        self.programs = {"CC": compile_program(CC),
                         "Reach": compile_program(REACH)}
        # presized so no run overflows (grow_retries stays 0): every
        # IDB holds at most one row per vertex, and no join or dedupe
        # input exceeds two rows per edge
        self.caps = dict(idb_cap=pow2_cap(self.n),
                         intermediate_cap=pow2_cap(2 * len(self.edges)))
        self.gen_s = time.perf_counter() - t0

    def edbs(self, program: str, edges=None) -> dict:
        edges = self.edges if edges is None else edges
        if program == "CC":
            return {"edge": edges}
        return {"edge": edges, "source": np.array([[self.source]])}

    def reference(self, program: str, edges=None) -> np.ndarray:
        edges = self.edges if edges is None else edges
        if program == "CC":
            return ref_cc(edges, self.n)
        return ref_reach(edges, self.n, self.source)

    def config(self, backend, **kw):
        from repro.engine import EngineConfig
        return EngineConfig(kernel_backend=backend, **self.caps, **kw)


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


class Clock:
    """Wall seconds, and the seconds XLA spent compiling, summed from
    JAX's backend-compile monitoring event: on a TPU, compiling the
    engine's sorts is a large share of a cold run. JAX compiles in the
    thread that calls, so compile seconds are also kept per thread and
    a timed call counts only its own, even beside parallel jobs."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self._by_thread: dict[int, float] = {}
        self._lock = threading.Lock()    # compiles report from threads
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            tid = threading.get_ident()
            with self._lock:
                self.compile_s += secs
                self._by_thread[tid] = self._by_thread.get(tid, 0.0) + secs

    def _own(self) -> float:
        with self._lock:
            return self._by_thread.get(threading.get_ident(), 0.0)

    def timed(self, fn):
        """(fn(), wall seconds, seconds this thread spent compiling)."""
        t0, c0 = time.perf_counter(), self._own()
        out = fn()
        return out, time.perf_counter() - t0, self._own() - c0

    def cold_runs(self, jobs: dict) -> tuple[dict, float]:
        """Run each job (key -> no-argument callable) once, all in
        parallel threads, so that their XLA compiles overlap: compiled
        one after another they would be most of a cold run. Returns
        ({key: (result, wall seconds, compile seconds)}, compile seconds
        of all the jobs)."""
        from concurrent.futures import ThreadPoolExecutor
        c0 = self.compile_s
        with ThreadPoolExecutor(len(jobs)) as pool:
            futures = {k: pool.submit(self.timed, fn)
                       for k, fn in jobs.items()}
            done = {k: f.result() for k, f in futures.items()}
        return done, self.compile_s - c0


def phase_batch(w: Workload, device, backend, clock: Clock,
                also=None) -> None:
    """(a) CC and Reach fixpoints, host and device mode: a cold run of
    all four in parallel (with ``also``, a no-argument callable such as
    the cold half of phase (b)), then a warm run of each."""
    from repro.engine import make_engine
    outs = {"CC": "cc", "Reach": "reach"}
    engines = {(p, mode): make_engine(w.programs[p],
                                      w.config(backend, mode=mode))
               for p in outs for mode in ("host", "device")}
    jobs = {k: (lambda e=e, p=k[0]: e.run(w.edbs(p)))
            for k, e in engines.items()}
    if also is not None:
        jobs["also"] = also
    cold, compile_s = clock.cold_runs(jobs)
    log(f"phase a  {len(jobs)} cold runs in parallel: "
        f"compile_s={compile_s:.3f}")
    wants = {p: w.reference(p) for p in outs}
    for (program, mode), eng in engines.items():
        (out, stats), cold_s, cold_c = cold[program, mode]
        (out2, stats2), warm_s, warm_c = clock.timed(
            lambda: eng.run(w.edbs(program)))
        for o, st in ((out, stats), (out2, stats2)):
            check(f"(a) {program} {mode}", o[outs[program]], wants[program])
            if st.grow_retries:
                raise AssertionError(f"(a) {program} {mode}: grow_retries "
                                     f"{st.grow_retries}")
        log(f"phase a  {program:5s} mode={mode:6s} cold_s={cold_s:.3f} "
            f"(compile_s={cold_c:.3f}) warm_s={warm_s:.3f} "
            f"(compile_s={warm_c:.3f}) iterations={stats2.total_iterations} "
            f"facts={stats2.total_facts[outs[program]]} "
            f"grow_retries={stats2.grow_retries} "
            f"peak_bytes_in_use={peak_bytes(device)} ok=true")


class IncrementalReach:
    """(b) ``make_engine(..., incremental=True)`` on Reach: initialize,
    then batches of BATCH_EDGES edges, inserts and deletes alternating,
    each checked against scipy on the current edge set."""

    def __init__(self, w: Workload, device, backend, clock: Clock):
        from repro.engine import make_engine
        self.w, self.device, self.clock = w, device, clock
        self.rng = np.random.default_rng(7)
        self.inc = make_engine(w.programs["Reach"], w.config(backend),
                               incremental=True)
        self.edges = np.unique(w.edges, axis=0)

    def _log(self, what: str, out, secs: float, compile_s: float,
             note: str) -> None:
        log(f"phase b  {what}{secs:.3f} (compile_s={compile_s:.3f}){note} "
            f"facts={len(out['reach'])} "
            f"grow_retries={self.inc._stats.grow_retries} "
            f"peak_bytes_in_use={peak_bytes(self.device)} ok=true")

    def initialize(self, note: str = "") -> None:
        out, s, c = self.clock.timed(lambda: self.inc.initialize(
            self.w.edbs("Reach", self.edges)))
        check("(b) initialize", out["reach"],
              self.w.reference("Reach", self.edges))
        self._log("initialize cold_s=", out, s, c, note)

    def batch(self, i: int, label: str, note: str = "") -> None:
        edges, w = self.edges, self.w
        if i % 2 == 0:
            kind = "insert"
            rows = self.rng.integers(0, w.n, size=(BATCH_EDGES, 2))
            update = {"inserts": {"edge": rows}}
            edges = np.unique(np.concatenate([edges, rows]), axis=0)
        else:
            kind = "delete"
            rows = edges[self.rng.choice(len(edges), BATCH_EDGES,
                                         replace=False)]
            update = {"deletes": {"edge": rows}}
            keep = np.ones(len(edges), bool)
            keep[np.searchsorted(edges[:, 0] * w.n + edges[:, 1],
                                 rows[:, 0] * w.n + rows[:, 1])] = False
            edges = edges[keep]
        out, s, c = self.clock.timed(lambda: self.inc.apply(**update))
        self.edges = edges
        check(f"(b) {kind} batch {i}", out["reach"],
              w.reference("Reach", edges))
        self._log(f"{kind:6s} batch={i} rows={BATCH_EDGES} {label}_s=",
                  out, s, c, note)


def _ref_segment(vals, seg, num_segments, op):
    info = np.iinfo(np.int32)
    init = {"sum": 0, "min": info.max, "max": info.min}[op]
    out = np.full(num_segments, init, np.int64)
    keep = (seg >= 0) & (seg < num_segments)
    ufunc = {"sum": np.add, "min": np.minimum, "max": np.maximum}[op]
    ufunc.at(out, seg[keep], vals[keep].astype(np.int64))
    return out.astype(np.int32)


def phase_kernels(w: Workload, device, clock: Clock,
                  mode: str = "pallas") -> None:
    """(c) each engine kernel once, at this scale's engine sizes
    (``mode`` is the ``repro.kernels.ops`` backend)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    big, small = w.caps["intermediate_cap"], w.caps["idb_cap"]

    def keys(k, hi=1 << 62):
        return np.sort(rng.integers(0, hi, size=k, dtype=np.int64))

    build = keys(big)
    probe = np.sort(np.concatenate([rng.choice(build, small // 2),
                                    keys(small - small // 2)]))
    bw = rng.integers(0, [1 << 32, 1 << 31], size=(small, 2))
    pw = np.concatenate([bw[rng.choice(small, small // 2)],
                         rng.integers(0, [1 << 32, 1 << 31],
                                      size=(small - small // 2, 2))])
    bw, pw = (a[np.lexsort(a.T[::-1])] for a in (bw, pw))
    bflat, pflat = (a[:, 0] * (1 << 31) + a[:, 1] for a in (bw, pw))
    a_keys, b_keys = keys(small), keys(small)
    vals = rng.integers(-(1 << 20), 1 << 20, size=big).astype(np.int32)

    def segs(num):   # sorted, with out-of-range ids at both ends
        return np.sort(rng.integers(-2, num + 2, size=big)).astype(np.int32)

    cases = [
        ("probe", lambda: ops.merge_probe_counts(
            jnp.asarray(build), jnp.asarray(probe), backend=mode),
         lambda: (np.searchsorted(build, probe, "left"),
                  np.searchsorted(build, probe, "right")),
         f"build={big} probe={small}"),
        ("probe_multi", lambda: ops.merge_probe_multi(
            jnp.asarray(bw), jnp.asarray(pw), backend=mode),
         lambda: (np.searchsorted(bflat, pflat, "left"),
                  np.searchsorted(bflat, pflat, "right")),
         f"build={small} probe={small} words=2"),
        ("merge_ranks", lambda: ops.merge_ranks(
            jnp.asarray(a_keys), jnp.asarray(b_keys), backend=mode),
         lambda: (np.arange(small) + np.searchsorted(b_keys, a_keys, "left"),
                  np.arange(small) + np.searchsorted(a_keys, b_keys,
                                                     "right")),
         f"a={small} b={small}"),
    ]
    from repro.kernels.segment_reduce import RESIDENT_MAX_SEGMENTS
    for path, num in (("resident", RESIDENT_MAX_SEGMENTS), ("tiled", big)):
        seg = segs(num)
        for op in ("sum", "min", "max"):
            cases.append((
                f"segment_{op}_{path}",
                lambda seg=seg, op=op, num=num: ops.segment_reduce(
                    jnp.asarray(vals), jnp.asarray(seg), num, op,
                    backend=mode),
                lambda seg=seg, op=op, num=num: (
                    _ref_segment(vals, seg, num, op),),
                f"rows={big} segments={num}"))

    for name, run, ref, shape in cases:
        secs = []
        for _ in range(2):
            got, s, c = clock.timed(lambda: jax.block_until_ready(run()))
            secs.append((s, c))
        got = got if isinstance(got, tuple) else (got,)
        for g, r in zip(got, ref()):
            if not np.array_equal(np.asarray(g), np.asarray(r)):
                raise AssertionError(f"(c) {name}: kernel != numpy")
        log(f"phase c  {name:20s} {shape} cold_s={secs[0][0]:.3f} "
            f"(compile_s={secs[0][1]:.3f}) warm_s={secs[1][0]:.3f} "
            f"peak_bytes_in_use={peak_bytes(device)} "
            f"ok=true")


def state_bytes_per_device(env) -> dict:
    """Bytes of the engine's stored relations on each device."""
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(env):
        for shard in getattr(leaf, "addressable_shards", ()):
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def phase_sharded(w: Workload, devices, backend, clock: Clock) -> None:
    """Four chips: ShardedEngine(shards=4) vs Engine vs scipy, one cold
    run of each of the four engines, all in parallel."""
    from repro.engine import make_engine
    shards = len(devices)
    outs = {"CC": "cc", "Reach": "reach"}
    engines = {(p, s): make_engine(w.programs[p],
                                   w.config(backend, shards=s))
               for p in outs for s in (0, shards)}
    cold, compile_s = clock.cold_runs(
        {k: (lambda e=e, p=k[0]: e.run(w.edbs(p)))
         for k, e in engines.items()})
    log(f"sharded  4 cold runs in parallel: compile_s={compile_s:.3f}")
    for program, out_name in outs.items():
        want = w.reference(program)
        (out1, st1), s1, _ = cold[program, 0]
        (out4, st4), s4, _ = cold[program, shards]
        check(f"{program} single", out1[out_name], want)
        check(f"{program} sharded", out4[out_name], want)
        for name in out1:
            if not np.array_equal(out1[name], out4[name]):
                raise AssertionError(f"{program}: sharded {name} differs")
        if st1.iterations != st4.iterations:
            raise AssertionError(
                f"{program}: iterations {st4.iterations} sharded vs "
                f"{st1.iterations} single")
        if st1.grow_retries or st4.grow_retries:
            raise AssertionError(f"{program}: grow_retries nonzero")
        sharded = engines[program, shards]
        log(f"sharded  {program:5s} shards={shards} single_cold_s={s1:.3f} "
            f"sharded_cold_s={s4:.3f} iterations={st4.total_iterations} "
            f"facts={st4.total_facts[out_name]} grow_retries=0 "
            f"byte_identical=true")
        log(f"sharded  {program:5s} state_bytes_per_device="
            f"{json.dumps(state_bytes_per_device(sharded.last_env))}")
    log("sharded  peak_bytes_in_use_per_device=" + json.dumps(
        {str(d.id): peak_bytes(d) for d in devices}))


# -- main ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-engine phase")
    ap.add_argument("--scale", type=int, default=DEFAULT_SCALE,
                    help=f"Graph500 scale (default {DEFAULT_SCALE})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not 1 <= args.scale <= 21:
        ap.error("--scale must be 1..21: 3-column key words hold vertex "
                 "ids below 2**21")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {REPO}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 1
    sys.path[:0] = [str(REPO / "src"), str(REPO)]
    from repro.compile_cache import enable_compile_cache
    from repro.engine.backend import PallasDispatch, resolve_backend

    cache = enable_compile_cache()
    clock = Clock()
    t_start = time.perf_counter()
    devices = devices[:args.chips]
    device = devices[0]
    bk = resolve_backend("auto")
    if not isinstance(bk, PallasDispatch) or bk.interpret:
        raise AssertionError(f"auto resolved to {bk!r}, not compiled "
                             f"Pallas kernels")
    log(f"device: {device.platform} {device.device_kind} "
        f"count={len(devices)}; backend: {bk.name} (compiled, "
        f"interpret={bk.interpret}); compile cache: {cache}")

    w = Workload(args.scale, args.seed)
    log(f"cut: Graph500 scale {args.scale} (source graph500-22 is scale "
        f"{SOURCE_SCALE}): {w.n} vertices, {len(w.edges)} edges; "
        f"vertex ids stay below 2**21 (3-column key words)")
    log(f"data: seed={args.seed} source={w.source} "
        f"caps={json.dumps(w.caps)} generate_s={w.gen_s:.3f}")

    if args.chips == 4:
        phase_sharded(w, devices, "auto", clock)
    else:
        # the cold half of (b) (initialize and the first insert and
        # delete batch, which compile the maintenance passes) runs in
        # parallel with the cold runs of (a); the later batches run
        # alone, and their compile_s shows what they still compile (a
        # DRed round at a new frontier capacity is a new program)
        inc = IncrementalReach(w, device, "auto", clock)

        def inc_cold():
            note = " (parallel with phase a)"
            inc.initialize(note)
            for i in range(2):
                inc.batch(i, "cold", note)

        phase_batch(w, device, "auto", clock, also=inc_cold)
        for i in range(2, BATCHES):
            inc.batch(i, "warm")
        phase_kernels(w, device, clock)
    log(f"total_s={time.perf_counter() - t_start:.3f} "
        f"compile_s={clock.compile_s:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
