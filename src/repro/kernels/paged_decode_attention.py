"""Pallas TPU paged decode attention: one query token vs. a paged KV pool.

The continuous-batching engine keeps KV in a shared page pool
(``(num_pages, page_size, n_kv, d)`` per layer) with per-request block
tables.  This kernel is the decode inner loop on that layout: the block
table and sequence lengths ride in as scalar-prefetch operands
(``PrefetchScalarGridSpec``), so each grid step's K/V tile is DMA'd
straight from the *physical* page the table points at — no dense
gather/copy of the request's KV ever materializes.

Grid: (batch, kv_head, pages_per_seq) — page dim innermost for the online
softmax scratch carry, same structure as ``decode_attention.py``.
Unassigned table entries (−1) are clamped to page 0 for the DMA and masked
out positionally; one compiled kernel serves every fill level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, pages_per_seq: int, softcap,
                  quantized: bool = False):
    if quantized:
        # int8 KV pages: the per-(slot, kv-head) fp32 scales ride in as two
        # extra page-indexed operands (the MaxText AQT kv_quant idiom —
        # codes and scales DMA together from the same physical page the
        # block table points at).
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    bi = pl.program_id(0)
    pj = pl.program_id(2)

    @pl.when(pj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (page_size, d)
    v = v_ref[0, 0].astype(jnp.float32)
    d = q.shape[-1]
    length = len_ref[bi]
    assigned = bt_ref[bi * pages_per_seq + pj] >= 0

    logits = jax.lax.dot_general(q * (d ** -0.5), k,
                                 (((1,), (1,)), ((), ())))  # (G, page_size)
    if quantized:
        # a slot's scale factors out of its dot products, so the scales
        # apply lane-wise to the (G, page_size) logits and probabilities
        # instead of to the (page_size, d) tiles: no relayout of the
        # (1, page_size) scale row is needed.
        logits = logits * ks_ref[0, 0]           # (1, page_size) scales
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = pj * page_size + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = (pos < length) & assigned
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    pv = p * vs_ref[0, 0] if quantized else p
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(pv.astype(v.dtype), v)
    m_scr[...] = m_new

    @pl.when(pj == pages_per_seq - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           k_scales=None, v_scales=None,
                           softcap=None, interpret: bool = False):
    """q: (B, H, D); k_pages/v_pages: (N, page_size, KV, D);
    block_tables: (B, P) int32 physical page ids (-1 = unassigned);
    lengths: (B,) int32 tokens written so far.  Returns (B, H, D).

    ``k_scales``/``v_scales`` (both or neither): (N, page_size, KV) fp32
    per-(slot, kv-head) scales for int8 pages — the kernel applies them in
    VMEM to each page's logits and probabilities (``kv_quant="int8"``)."""
    b, h, d = q.shape
    n, page_size, kv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    p_seq = block_tables.shape[1]
    g = h // kv
    g_pad = max(8, g)  # sublane minimum
    quantized = k_scales is not None

    qg = q.reshape(b, kv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    # (N, page, KV, D) -> (N, KV, page, D) tile-friendly layout
    kt = k_pages.transpose(0, 2, 1, 3)
    vt = v_pages.transpose(0, 2, 1, 3)

    bt_flat = block_tables.reshape(-1).astype(jnp.int32)

    def page_map(bb, hh, pj, bt, ln):
        del ln
        idx = jnp.maximum(bt[bb * p_seq + pj], 0)  # -1 -> garbage page 0
        return (idx, hh, 0, 0)

    def scale_map(bb, hh, pj, bt, ln):
        del ln
        idx = jnp.maximum(bt[bb * p_seq + pj], 0)
        return (idx, hh, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g_pad, d), lambda bb, hh, pj, bt, ln: (bb, hh, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d), page_map),
        pl.BlockSpec((1, 1, page_size, d), page_map),
    ]
    operands = [qg, kt, vt]
    if quantized:
        # (N, page, KV) -> (N, KV, 1, page): same physical-page indexing as
        # the code tiles.  The unit axis makes each grid step's block a
        # (1, page_size) row whose last two dims equal the array's — the
        # TPU's tiling rule refuses a (1, 1, page_size) block over a
        # 3-D (N, KV, page) array.
        def scale_rows(s):
            return s.transpose(0, 2, 1)[:, :, None, :].astype(jnp.float32)
        in_specs += [pl.BlockSpec((1, 1, 1, page_size), scale_map),
                     pl.BlockSpec((1, 1, 1, page_size), scale_map)]
        operands += [scale_rows(k_scales), scale_rows(v_scales)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, p_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g_pad, d),
                               lambda bb, hh, pj, bt, ln: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               pages_per_seq=p_seq, softcap=softcap,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g_pad, d), q.dtype),
        interpret=interpret,
    )(bt_flat, lengths.astype(jnp.int32), *operands)
    return out[:, :, :g, :].reshape(b, h, d)
