"""Compile every engine Pallas kernel for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse
(block shapes off the tiling, 64-bit values inside a kernel, SMEM or
VMEM overflow) — which interpret-mode tests cannot see. Sizes are the
engine's: about 2**20 rows, int64 keys, one int32 value column.

The topology is described inside a fixture (never at import) so that
every xdist worker collects the same tests and only the worker running
this file loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.segment_reduce import RESIDENT_MAX_SEGMENTS

ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no TPU compiler log files
        from jax.experimental import topologies
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _keys(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int64, sharding=sharding)


def _int32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _probe(one):
    return (lambda b, p: ops.merge_probe_counts(b, p, backend="pallas"),
            (_keys((ROWS,), one), _keys((ROWS // 4,), one)))


def _probe_multi(one):
    return (lambda b, p: ops.merge_probe_multi(b, p, backend="pallas"),
            (_keys((ROWS, 2), one), _keys((ROWS // 4, 2), one)))


def _merge_ranks(one):
    return (lambda a, b: ops.merge_ranks(a, b, backend="pallas"),
            (_keys((ROWS,), one), _keys((ROWS // 4,), one)))


def _merge_ranks_multi(one):
    return (lambda a, b: ops.merge_ranks_multi(a, b, backend="pallas"),
            (_keys((ROWS, 2), one), _keys((ROWS // 4, 2), one)))


def _segment(op, num_segments):
    def case(one):
        return (lambda v, s: ops.segment_reduce(v, s, num_segments, op,
                                                backend="pallas"),
                (_int32((ROWS,), one), _int32((ROWS,), one)))
    return case


CASES = {
    "probe": _probe,
    "probe_multi": _probe_multi,
    "merge_ranks": _merge_ranks,
    "merge_ranks_multi": _merge_ranks_multi,
    **{f"segment_{op}_{path}": _segment(op, segs)
       for op in ("sum", "min", "max")
       for path, segs in (("resident", RESIDENT_MAX_SEGMENTS),
                          ("tiled", ROWS))},
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip, no_compile_cache):
    fn, specs = CASES[kernel](one_chip)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
