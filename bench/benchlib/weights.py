"""Seeded random weights in the program's parameter layout.

The benchmark makes the weights, hands them to the program, and the
reference makes them again from the same seed, layer by layer: the
reference never reads a weight the program holds.  Every leaf, and every
layer of a stacked leaf, has a key of its own (folded from the seed, the
leaf's path and the layer), so one layer can be regenerated alone and
comes out bit-identical to its slice of the whole tree.

Matrices are truncated normals (+-2 sigma) at 1/sqrt(fan_in) in bfloat16,
the type they are served in; norm scales are 1 + 0.1 N(0, 1) in float32,
as the program keeps them.  A tied table is also the output head and
starts at 1/sqrt(d_model); an untied input table at 1.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.counts import Dims
from benchlib.traffic import seed_words


def _layer_specs(d: Dims):
    """(path, shape, kind, scale) of one transformer block."""
    hd = d.head_dim
    return [
        (("ln1", "scale"), (d.d_model,), "norm", None),
        (("ln2", "scale"), (d.d_model,), "norm", None),
        (("attn", "wq"), (d.d_model, d.heads * hd), "mat", d.d_model ** -0.5),
        (("attn", "wk"), (d.d_model, d.kv_heads * hd), "mat", d.d_model ** -0.5),
        (("attn", "wv"), (d.d_model, d.kv_heads * hd), "mat", d.d_model ** -0.5),
        (("attn", "wo"), (d.heads * hd, d.d_model), "mat", (d.heads * hd) ** -0.5),
        (("attn", "q_norm"), (hd,), "norm", None),
        (("attn", "k_norm"), (hd,), "norm", None),
        (("mlp", "wi_gate"), (d.d_model, d.d_ff), "mat", d.d_model ** -0.5),
        (("mlp", "wi_up"), (d.d_model, d.d_ff), "mat", d.d_model ** -0.5),
        (("mlp", "wo"), (d.d_ff, d.d_model), "mat", d.d_ff ** -0.5),
    ]


def _top_specs(d: Dims, tied: bool):
    specs = [(("embed",), (d.vocab, d.d_model), "mat",
              d.d_model ** -0.5 if tied else 1.0),
             (("final_norm", "scale"), (d.d_model,), "norm", None)]
    if not tied:
        specs.append((("lm_head",), (d.d_model, d.vocab), "mat",
                      d.d_model ** -0.5))
    return specs


def _key(words, path, layer=None):
    """The key of one leaf (of one layer): the seed's two words, given to
    the compiled program as an argument so that one program serves every
    seed, folded with the leaf's path and the layer."""
    key = jax.random.fold_in(words, zlib.crc32("/".join(path).encode()))
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return key


def _draw(key, shape, kind, scale):
    if kind == "norm":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale
    return x.astype(jnp.bfloat16)


def _set(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _words(seed: int, device=None):
    words = np.asarray(seed_words(seed), np.uint32)
    return jax.device_put(words, device) if device is not None else words


@functools.lru_cache(maxsize=None)
def _make_fn(dims: Dims, tied: bool, device):
    def build(words):
        tree: dict = {}
        for path, shape, kind, scale in _top_specs(dims, tied):
            _set(tree, path, _draw(_key(words, path), shape, kind, scale))
        for path, shape, kind, scale in _layer_specs(dims):
            layers = [_draw(_key(words, ("blocks",) + path, i), shape, kind,
                            scale) for i in range(dims.layers)]
            _set(tree, ("blocks",) + path, jnp.stack(layers))
        return tree

    shard = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=shard)


def make(dims: Dims, tied: bool, seed: int, device=None):
    """The whole tree, made on ``device`` in one jitted call."""
    return _make_fn(dims, tied, device)(_words(seed, device))


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: Dims):
    def build(words, i):
        tree: dict = {}
        for path, shape, kind, scale in _layer_specs(dims):
            w = _draw(_key(words, ("blocks",) + path, i), shape, kind, scale)
            _set(tree, path, w.astype(jnp.float32))
        return tree
    return jax.jit(build)


def layer(dims: Dims, seed: int, i: int) -> dict:
    """Block ``i`` alone, upcast exactly to float32."""
    return _layer_fn(dims)(_words(seed), np.uint32(i))


@functools.lru_cache(maxsize=None)
def _top_fn(dims: Dims, tied: bool):
    def build(words):
        tree: dict = {}
        for path, shape, kind, scale in _top_specs(dims, tied):
            _set(tree, path, _draw(_key(words, path), shape, kind, scale)
                 .astype(jnp.float32))
        return tree
    return jax.jit(build)


def top(dims: Dims, tied: bool, seed: int) -> dict:
    """Embedding, final norm and (untied) head, upcast to float32."""
    return _top_fn(dims, tied)(_words(seed))


def per_layer_leaves(tree) -> dict:
    """``name -> array`` with every stacked block leaf split per layer
    (``blocks/attn/wq[2]``): the granularity of the training comparison."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("blocks/"):
            for i in range(leaf.shape[0]):
                out[f"{name}[{i}]"] = leaf[i]
        else:
            out[name] = leaf
    return out
