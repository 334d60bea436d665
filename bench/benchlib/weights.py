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

Which leaves there are, and their shapes and scales, is the architecture
module's (``bench/arch/<name>.py``: ``layer_specs(dims)`` for one block,
``top_specs(dims, tied)`` for the rest); this module keys and draws them.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.traffic import seed_words


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
def _make_fn(arch, dims, tied: bool, device):
    def build(words):
        tree: dict = {}
        for path, shape, kind, scale in arch.top_specs(dims, tied):
            _set(tree, path, _draw(_key(words, path), shape, kind, scale))
        for path, shape, kind, scale in arch.layer_specs(dims):
            layers = [_draw(_key(words, ("blocks",) + path, i), shape, kind,
                            scale) for i in range(dims.layers)]
            _set(tree, ("blocks",) + path, jnp.stack(layers))
        return tree

    shard = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=shard)


def make(arch, dims, tied: bool, seed: int, device=None):
    """The whole tree, made on ``device`` in one jitted call."""
    return _make_fn(arch, dims, tied, device)(_words(seed, device))


@functools.lru_cache(maxsize=None)
def _layer_fn(arch, dims):
    def build(words, i):
        tree: dict = {}
        for path, shape, kind, scale in arch.layer_specs(dims):
            w = _draw(_key(words, ("blocks",) + path, i), shape, kind, scale)
            _set(tree, path, w.astype(jnp.float32))
        return tree
    return jax.jit(build)


def layer(arch, dims, seed: int, i: int) -> dict:
    """Block ``i`` alone, upcast exactly to float32."""
    return _layer_fn(arch, dims)(_words(seed), np.uint32(i))


@functools.lru_cache(maxsize=None)
def _top_fn(arch, dims, tied: bool):
    def build(words):
        tree: dict = {}
        for path, shape, kind, scale in arch.top_specs(dims, tied):
            _set(tree, path, _draw(_key(words, path), shape, kind, scale)
                 .astype(jnp.float32))
        return tree
    return jax.jit(build)


def top(arch, dims, tied: bool, seed: int) -> dict:
    """Embedding, final norm and (untied) head, upcast to float32."""
    return _top_fn(arch, dims, tied)(_words(seed))


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
