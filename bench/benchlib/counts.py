"""Analytic operation and byte counts: the algorithm's work, from shapes.

These count what the mathematics needs, not what an implementation does:
no padding, no recomputation (remat), no page walked past a sequence's
length, no relayout of the page pool.  So a share of a peak computed from
them can only rise when an implementation wastes less, and never passes
100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class Dims:
    """A dense GQA transformer's sizes.  An architecture module
    (``bench/arch/<name>.py``) reads them from its configuration file in
    ``dims(c)``; one whose layers multiply through other parameters (a
    mixture of experts: the k active experts and the router) returns an
    object whose ``layer_matmul_params`` counts those, and one with more
    than one kind of layer (``weights.LayerKind``) an object whose
    ``trunk_params``, ``matmul_params`` and ``attn_flops`` sum its kinds:
    the counts here take no notice of kinds.  The counts below read only
    ``matmul_params``, ``trunk_params``, ``head_params``, ``attn_flops``,
    ``heads``, ``kv_heads``, ``head_dim``, ``layers``, ``vocab`` and
    ``d_model``."""
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd + 2 * d * self.kv_heads * hd + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def trunk_params(self) -> int:
        """Matmul parameters every token passes through, head excluded."""
        return self.layers * self.layer_matmul_params

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def matmul_params(self) -> int:
        """N: the parameters one token's logits multiply through (the
        embedding lookup is a gather and does no matmul)."""
        return self.trunk_params + self.head_params

    def attn_flops(self, context: int) -> int:
        """QK^T and PV of one query over ``context`` positions, all layers."""
        return 4 * self.layers * self.heads * self.head_dim * context


def decode_token_flops(dims: Dims, context: int) -> int:
    """One decoded token that attends over ``context`` positions."""
    return 2 * dims.matmul_params + dims.attn_flops(context)


def prefill_flops(dims: Dims, start: int, n: int) -> int:
    """``n`` prompt tokens at positions ``start..start+n-1``: the trunk and
    causal attention for each, the head once for the last."""
    attn = dims.attn_flops(n * start + n * (n + 1) // 2)
    return 2 * dims.trunk_params * n + attn + 2 * dims.head_params


def train_flops(dims: Dims, lengths: Iterable[int]) -> int:
    """Forward and backward (3x forward) over sequences of the given real
    lengths, causal: 6N per token plus 3x the attention of each position."""
    total = 0
    for n in lengths:
        total += 6 * dims.matmul_params * n
        total += 3 * dims.attn_flops(n * (n + 1) // 2)
    return total


def paged_attn_work(dims: Dims, lengths: Iterable[int], *,
                    kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """(flops, bytes) of one decode-attention call of one layer over active
    slots with the given written lengths: q and the output once per slot,
    K and V of each slot's written positions once."""
    lengths = list(lengths)
    total = sum(lengths)
    flops = 4 * dims.heads * dims.head_dim * total
    qo = 2 * len(lengths) * dims.heads * dims.head_dim * act_bytes
    kv = 2 * total * dims.kv_heads * dims.head_dim * kv_bytes
    return flops, qo + kv


def roofline_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, bound) for one call: the larger of the compute and
    the memory time at the chip's peaks."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
