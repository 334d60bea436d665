"""Quantization primitives: symmetric per-channel INT8 + FP8-E4M3 pytrees.

The quantize-on-sync parameter path: the trainer ships fp32/bf16 weights at
every sync and the rollout engine calls ``quantize_params`` before storing
them — replicas *hold* int8/fp8 tensors on device (the memory/bandwidth
win), and the jitted engine step calls ``dequantize_params`` at trace time
so the dequant multiply fuses into the first matmul consumer (W8A16 style).

Scheme (the FlashRL / vLLM loading recipe):

* matmul weights (ndim >= 2) are quantized **per output channel** — the
  absmax over every non-last axis sets one scale per last-axis column, so
  a stacked block tree ``(L, d_in, d_out)`` gets per-layer, per-column
  scales ``(L, 1, d_out)``.
* embeddings / lm_head / norm gains stay full precision (standard practice:
  their error lands directly on the logits, and they are a small fraction
  of parameter bytes).
* fp8 uses the ml_dtypes ``float8_e4m3fn`` grid (max normal 448) when the
  running jax exposes it, else an exact jnp simulation of the same grid —
  either way results are bit-identical casts, safe on CPU.

A quantized leaf is a ``QuantLeaf`` NamedTuple (codes, scale, dtype token)
— a pytree node, so quantized trees flow through jit / donate / tree_map
like plain parameter trees.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

MODES = ("off", "int8", "fp8")          # weight quantization modes
KV_MODES = ("off", "int8")              # KV-page quantization modes

_INT8_MAX = 127.0
_FP8_MAX = 448.0                        # e4m3fn max normal
_EPS = 1e-12                            # zero-tensor guard for absmax scales

# full-precision islands: tied/untied unembedding + embeddings by name,
# norm gains by leaf key (rmsnorm params are ``{"scale": (..., D)}`` dicts,
# q_norm/k_norm are direct leaves).
_SKIP_KEYS = frozenset({"embed", "lm_head", "scale", "bias"})
_SKIP_SUFFIXES = ("_norm",)


class QuantLeaf(NamedTuple):
    """One quantized tensor: integer/fp8 codes + broadcastable scales.

    ``dtype_token`` is a zero-size array carrying the ORIGINAL leaf dtype so
    dequantization restores it exactly (bf16 weights come back bf16 — the
    downstream matmul dtypes match the unquantized path)."""
    codes: jax.Array        # int8 or float8_e4m3fn, original shape
    scale: jax.Array        # float32, shape (..., 1, d_out)-broadcastable
    dtype_token: jax.Array  # shape (), original dtype


def _per_channel_scale(x, qmax: float):
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    return jnp.maximum(amax, _EPS) / qmax


def quantize_array(x: jax.Array, mode: str) -> QuantLeaf:
    """Symmetric per-output-channel quantization of one weight tensor."""
    xf = x.astype(jnp.float32)
    token = jnp.zeros((), x.dtype)
    if mode == "int8":
        scale = _per_channel_scale(x, _INT8_MAX)
        codes = jnp.clip(jnp.round(xf / scale), -_INT8_MAX, _INT8_MAX)
        return QuantLeaf(codes.astype(jnp.int8), scale, token)
    if mode == "fp8":
        scale = _per_channel_scale(x, _FP8_MAX)
        codes = (xf / scale).astype(jnp.float8_e4m3fn)
        return QuantLeaf(codes, scale, token)
    raise ValueError(f"unknown quant mode {mode!r} (expected int8 | fp8)")


def dequantize_array(leaf: QuantLeaf) -> jax.Array:
    """Back to the original dtype; jit-safe (fuses into the consumer)."""
    return (leaf.codes.astype(jnp.float32)
            * leaf.scale).astype(leaf.dtype_token.dtype)


def _skip(key: str, leaf: Any) -> bool:
    if key in _SKIP_KEYS or key.endswith(_SKIP_SUFFIXES):
        return True
    ndim = getattr(leaf, "ndim", 0)
    if ndim < 2:
        return True
    return not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)


def quantize_params(params: Any, mode: str) -> Any:
    """Quantize every matmul-weight leaf of a parameter pytree.

    ``mode="off"`` returns the tree untouched (the byte-identical path).
    Embeddings, lm_head and norm gains are kept full precision (see module
    docstring); everything else becomes a ``QuantLeaf``."""
    if mode == "off":
        return params
    if mode not in MODES:
        raise ValueError(f"unknown quant mode {mode!r} (expected "
                         "off | int8 | fp8)")

    def rec(node, key):
        if isinstance(node, dict):
            return {k: rec(v, k) for k, v in node.items()}
        if _skip(key, node):
            return node
        return quantize_array(node, mode)

    return rec(params, "")


def _is_leaf(x: Any) -> bool:
    return isinstance(x, QuantLeaf)


def dequantize_params(params: Any) -> Any:
    """Inverse of ``quantize_params``; identity on plain trees.

    Called at the top of the engine's jitted step — for an unquantized tree
    this traces to the exact same jaxpr as passing ``params`` through, so
    ``quant_mode="off"`` stays byte-identical to the pre-quant engine."""
    return jax.tree_util.tree_map(
        lambda leaf: dequantize_array(leaf) if _is_leaf(leaf) else leaf,
        params, is_leaf=_is_leaf)


def is_quantized_tree(params: Any) -> bool:
    """Whether any leaf of ``params`` is a ``QuantLeaf``."""
    found = False

    def check(leaf):
        nonlocal found
        found = found or _is_leaf(leaf)

    jax.tree_util.tree_map(check, params, is_leaf=_is_leaf)
    return found
