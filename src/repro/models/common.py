"""Shared neural building blocks (explicit dtypes throughout — the
package enables x64 for the Datalog engine, so nothing here may rely on
dtype defaults)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def uniform_init(key, shape, scale, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def normal_init(key, shape, stddev, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        stddev, dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * (
        1.0 + gamma.astype(dt))


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * gamma.astype(dt) + beta.astype(dt)


def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
    }[name]


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                rot_dim: Optional[int] = None):
    """positions int32 [*S] -> (sin, cos) [*S, rot_dim/2] float32.
    ``rot_dim`` < head_dim gives partial rotary (ChatGLM's 2d RoPE applies
    rotation to half the head dimensions)."""
    rot = rot_dim or head_dim
    freqs = jnp.exp(
        -math.log(theta) *
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array):
    """x [..., S, H, D]; sin/cos [..., S, rot/2] broadcast over heads.
    Rotates the first ``2 * sin.shape[-1]`` dims, passes the rest."""
    rot = 2 * sin.shape[-1]
    xr, xp = x[..., :rot], x[..., rot:]
    x1 = xr[..., 0::2]
    x2 = xr[..., 1::2]
    s = sin[..., None, :].astype(x.dtype)
    c = cos[..., None, :].astype(x.dtype)
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([out, xp], axis=-1) if xp.shape[-1] else out


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ignore_id: int = -1):
    """logits [*, V] any float dtype; labels int32. fp32 logsumexp."""
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    idx = labels[..., None].astype(jnp.int32).clip(0, lg.shape[-1] - 1)
    ll = jnp.take_along_axis(lg, idx, axis=-1, mode="clip")[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(mask.sum(), 1.0)


def active_abstract_mesh():
    """The active (abstract) mesh set by ``jax.set_mesh``: an object
    with ``axis_names``/``axis_sizes``, empty when no mesh is active
    (CPU smoke tests)."""
    return jax.sharding.get_abstract_mesh()


def maybe_shard(x, *entries):
    """with_sharding_constraint that degrades to a no-op when no mesh is
    active (CPU smoke tests) or when a dim isn't divisible by its axis.

    Entries: None | axis name | "dp" (all non-'model' axes, i.e.
    pod+data) | "all" (every mesh axis — FSDP batch sharding).
    """
    am = active_abstract_mesh()
    names = getattr(am, "axis_names", ())
    if not names:
        return x
    sizes = dict(zip(names, am.axis_sizes))
    resolved = []
    for i, e in enumerate(entries):
        if e == "all":
            e = tuple(names) if len(names) > 1 else names[0]
        if e == "dp":
            axes = tuple(a for a in names if a != "model")
            e = axes if len(axes) > 1 else (axes[0] if axes else None)
        if e == "model" and "model" not in names:
            e = None
        if e is not None:
            need = 1
            for a in (e if isinstance(e, tuple) else (e,)):
                need *= sizes[a]
            if x.shape[i] % need != 0:
                e = None
        resolved.append(e)
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*resolved))
