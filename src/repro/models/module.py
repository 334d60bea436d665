"""Minimal pure-JAX module substrate.

Parameters are nested dicts of jnp arrays.  ``init_*`` functions build the
tree; ``apply``-style functions consume it.  No flax — the tree layout is
the API, and `models/sharding.py` pattern-matches tree paths to produce
PartitionSpecs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(cfg_dtype):
    return jnp.dtype(cfg_dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    """Truncated-normal dense kernel (d_in, d_out)."""
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d_model: int, dtype, scale: float = 1.0):
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d_model), jnp.float32)
            * scale).astype(dtype)


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype=jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(dt)


def rmsnorm_head(scale, x, eps: float = 1e-6):
    """RMSNorm over the trailing head_dim (qk-norm), scale shape (head_dim,)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    inv_freq = jnp.asarray(rope_frequencies(head_dim, theta))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., seq, half)
    angles = angles[..., None, :]  # (..., seq, 1, half) broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))


def fold_key(key, i: int):
    return jax.random.fold_in(key, i)
