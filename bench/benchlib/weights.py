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
module's (``bench/arch/<name>.py``): ``top_specs(dims, tied)`` for the
leaves outside the layers, and for the layers either ``layer_kinds(dims)``,
its kinds of layer in layer order (``LayerKind``), or ``layer_specs(dims)``
and ``block``, one kind of every layer rooted at ``blocks``.  Each kind's
leaves are stacked over its layers under its own root; this module keys
and draws them.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Callable

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


DEFAULT_ROOT = "blocks"     # the root of a module's one kind of layer


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One kind of layer: ``layers`` consecutive layers whose leaves
    (``specs``, one layer's ``(path, shape, kind, scale)``) are stacked
    under the tree's key ``root``, and ``block(w, x, dims, i, theta, eps,
    cast) -> (x, extra_loss)``, the plain reference's layer ``i`` (its
    index over all the layers, traced)."""
    root: str
    layers: int
    specs: tuple
    block: Callable


def layer_kinds(arch, dims) -> list:
    """The architecture's kinds of layer, in layer order: its
    ``layer_kinds(dims)``, or one kind of every layer rooted at
    ``blocks`` from its ``layer_specs`` and ``block``."""
    if not hasattr(arch, "layer_kinds"):
        return [LayerKind(DEFAULT_ROOT, dims.layers,
                          tuple(arch.layer_specs(dims)), arch.block)]
    kinds = list(arch.layer_kinds(dims))
    if sum(k.layers for k in kinds) != dims.layers:
        raise ValueError(f"layer kinds of {[k.layers for k in kinds]} layers, "
                         f"the configuration has {dims.layers}")
    if len({k.root for k in kinds}) != len(kinds):
        raise ValueError(f"two layer kinds share a root: {[k.root for k in kinds]}")
    return kinds


def roots(arch, dims) -> tuple:
    """The roots of the architecture's stacks of layers."""
    return tuple(k.root for k in layer_kinds(arch, dims))


@functools.lru_cache(maxsize=None)
def _make_fn(arch, dims, tied: bool, device):
    def build(words):
        tree: dict = {}
        for path, shape, kind, scale in arch.top_specs(dims, tied):
            _set(tree, path, _draw(_key(words, path), shape, kind, scale))
        for k in layer_kinds(arch, dims):
            for path, shape, kind, scale in k.specs:
                path = (k.root,) + path
                _set(tree, path, jnp.stack([_draw(_key(words, path, j), shape,
                                                  kind, scale)
                                            for j in range(k.layers)]))
        return tree

    shard = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=shard)


def make(arch, dims, tied: bool, seed: int, device=None):
    """The whole tree, made on ``device`` in one jitted call."""
    return _make_fn(arch, dims, tied, device)(_words(seed, device))


@functools.lru_cache(maxsize=None)
def _layer_fn(arch, dims, root: str):
    k, = (k for k in layer_kinds(arch, dims) if k.root == root)

    def build(words, j):
        tree: dict = {}
        for path, shape, kind, scale in k.specs:
            w = _draw(_key(words, (root,) + path, j), shape, kind, scale)
            _set(tree, path, w.astype(jnp.float32))
        return tree
    return jax.jit(build)


def layer(arch, dims, seed: int, i: int) -> dict:
    """Layer ``i`` (over all the layers) alone, upcast exactly to float32:
    its kind's leaves, keyed by the kind's root, each leaf's path and the
    layer's index within its stack."""
    j = i
    for k in layer_kinds(arch, dims):
        if j < k.layers:
            return _layer_fn(arch, dims, k.root)(_words(seed), np.uint32(j))
        j -= k.layers
    raise IndexError(f"layer {i} of {dims.layers}")


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


def named_leaves(tree, roots):
    """``(name, leaf, stacked)`` of every leaf of ``tree``: its keys joined
    by ``/`` (``blocks/attn/wq``), and whether it lies under one of
    ``roots``, a stack of layers."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        yield name, leaf, name.split("/", 1)[0] in roots


def per_layer_leaves(tree, roots) -> dict:
    """``name -> array`` with every stacked leaf under ``roots`` split per
    layer (``blocks/attn/wq[2]``, the layer's index within its stack): the
    granularity of the training comparison."""
    out = {}
    for name, leaf, stacked in named_leaves(tree, roots):
        if stacked:
            for i in range(leaf.shape[0]):
                out[f"{name}[{i}]"] = leaf[i]
        else:
            out[name] = leaf
    return out
