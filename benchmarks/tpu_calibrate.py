"""Per-call times of the compiled Pallas kernels against their XLA
counterparts (``repro.kernels.ops`` backend ``"xla"``) at engine sizes,
on one TPU chip:

    PYTHONPATH=src python -m benchmarks.tpu_calibrate

Rows: the merge-path probe at 2^16-2^20 probe keys into 2^20-2^22
build keys, the tiled segment min at 2^18-2^21 rows (as many segments
as rows), and the resident segment sum (8192 segments). Each prints its
cold seconds (with compilation) and the median of three warm calls.
Exits non-zero without a TPU: ``backend="pallas"`` is compiled kernels
only. These are calibration numbers for choosing a dispatch, not
end-to-end results."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.kernels import ops


def _bench(name: str, fn, *args) -> None:
    fn = jax.jit(fn)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    cold = time.perf_counter() - t0
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        warm.append(time.perf_counter() - t0)
    print(f"{name} cold_s={cold:.3f} warm_s={sorted(warm)[1]:.5f}",
          flush=True)


def main() -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"tpu_calibrate: no TPU found (JAX platform "
              f"{device.platform!r})", file=sys.stderr)
        return 1
    enable_compile_cache()
    print(f"device: {device.platform} {device.device_kind}", flush=True)
    rng = np.random.default_rng(0)
    for n, m in ((1 << 16, 1 << 20), (1 << 18, 1 << 20),
                 (1 << 20, 1 << 20), (1 << 16, 1 << 22)):
        build = jnp.asarray(np.sort(rng.integers(0, 1 << 62, m)))
        probe = jnp.asarray(np.sort(rng.integers(0, 1 << 62, n)))
        for backend in ("pallas", "xla"):
            _bench(f"probe {backend} n={n} m={m} steps={(n >> 10) * (m >> 10)}",
                   lambda b, p, bk=backend: ops.merge_probe_counts(
                       b, p, backend=bk), build, probe)
    for n in (1 << 18, 1 << 20, 1 << 21):
        vals = jnp.asarray(rng.integers(-100, 100, n).astype(np.int32))
        segs = jnp.asarray(np.sort(rng.integers(0, n, n)).astype(np.int32))
        for backend in ("pallas", "xla"):
            _bench(f"segment_min_tiled {backend} n={n} "
                   f"steps={(n >> 10) ** 2}",
                   lambda v, s, n=n, bk=backend: ops.segment_reduce(
                       v, s, n, "min", backend=bk), vals, segs)
        _bench(f"segment_sum_resident pallas n={n} segments=8192",
               lambda v, s: ops.segment_reduce(v, s, 8192, "sum",
                                               backend="pallas"),
               vals, jnp.sort(segs % 8192))
    return 0


if __name__ == "__main__":
    sys.exit(main())
