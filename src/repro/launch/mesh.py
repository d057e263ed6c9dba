"""Production mesh definitions.

Defined as FUNCTIONS (never module-level constants) so importing this
module does not touch jax device state — the dry-run must set
XLA_FLAGS before the first jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def use_mesh(mesh):
    """'Make this mesh active' context manager (``jax.set_mesh``) —
    what ``models.common.active_abstract_mesh`` reads back."""
    return jax.set_mesh(mesh)


def make_local_mesh():
    """Whatever devices exist locally (CPU tests: 1x1)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))


SHARD_AXIS = "shards"


def make_shard_mesh(num_shards: int):
    """1-D mesh for the sharded fixpoint engine (engine/shard.py): the
    first ``num_shards`` local devices on a single axis named "shards".
    On CPU, override the device count with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    the first jax initialization)."""
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if num_shards > len(devices):
        raise ValueError(
            f"num_shards={num_shards} exceeds the {len(devices)} visible "
            f"devices; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards}")
    return Mesh(np.array(devices[:num_shards]), (SHARD_AXIS,))


HARDWARE = {
    # TPU v5e per-chip targets (roofline constants; EXPERIMENTS.md)
    "peak_flops_bf16": 197e12,
    "hbm_bw": 819e9,
    "ici_bw_per_link": 50e9,
}
