"""Shared helpers of the benchmark's CPU tests: cells cut to a size the
CPU runs in seconds."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def shrink(cell, scale: int = 7, batch_edges: int = 8):
    """The cell at Graph500 scale ``scale``, with capacities that hold
    its relations, update batches of ``batch_edges`` edges after a
    warm-up of exactly two, on the portable kernels."""
    n, m = 1 << scale, cell.config["edge_factor"] << scale
    cell.config.update(scale=scale, caps={
        "idb_cap": max(16, 1 << n.bit_length()),
        "intermediate_cap": max(16, 1 << (4 * m).bit_length())})
    cell.config["engine"] = {**cell.config["engine"], "kernel_backend": "jnp"}
    if cell.traffic["driver"] == "updates":
        cell.traffic["batch_edges"] = batch_edges
        cell.driver.MIN_WARMUP_BATCHES = cell.driver.MAX_WARMUP_BATCHES = 2
    return cell


@pytest.fixture
def tiny():
    """``tiny(name, **kw)``: a cell of BENCHMARK.json cut by ``shrink``.
    The persistent compilation cache stays off, as in the repository's
    other CPU tests."""
    import jax
    from chipbench import bench
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda name, **kw: shrink(bench.resolve(name), **kw)
    jax.config.update("jax_enable_compilation_cache", before)
