"""Pallas TPU paged decode attention: one query token vs. a paged KV pool.

The continuous-batching engine keeps KV in a shared page pool
(``(num_pages, page_size, n_kv, d)`` per layer) with per-request block
tables.  This kernel is the decode inner loop on that layout: the block
table and sequence lengths ride in as scalar-prefetch operands
(``PrefetchScalarGridSpec``), so each page is DMA'd straight from the
*physical* page the table points at — no dense gather/copy of the
request's KV ever materializes.

Grid: (batch, page steps) — the page axis innermost for the online softmax
scratch carry.  A page is read as the pool stores it: its ``page_size``
tokens of every KV head are one contiguous ``(page_size·KV, d)`` block,
seen through a free reshape and never transposed.  All of a slot's query
heads meet it in one matmul, ``(H, d) @ (page_size·KV, d)ᵀ``; key row
``r`` holds token ``r // KV`` of KV head ``r % KV``, and each query head
masks out the rows of the other KV heads, so their probabilities are
exactly 0 and ``p @ v`` sums over the query head's own KV head only.

A grid step takes ``_pages_per_step`` pages, each through its own
BlockSpec'd view of the pool, and folds them into the softmax carry in one
update, so their matmuls overlap.  The index maps clamp a step's pages to
the slot's last live page: a repeated block index is not fetched again,
so pages at or past the length are never DMA'd, and ``pl.when`` skips
their compute.  Unassigned table entries (−1) are masked; a slot with no
assigned page under its length reads 0.  One compiled kernel serves every
fill level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# a key-row offset no length reaches: marks the rows of another KV head
_OTHER_HEAD = 1 << 30


def _pages_per_step(rows: int, pages_per_seq: int) -> int:
    """Pages a grid step takes: ~512 key rows, so that the pages' matmuls
    overlap on the MXU, and at most 8, since every page view costs its own
    DMA bookkeeping on every grid step.  At qwen3-4b's decode shapes on a
    TPU v5e (128 rows a page), 1, 2, 4, 8 and 16 pages a step took 132,
    106, 83, 88 and 106 us a call."""
    return max(1, min(pages_per_seq, 8, 512 // rows))


def _paged_kernel(bt_ref, len_ref, off_ref, q_ref, *refs, page_size: int,
                  pages_per_seq: int, pages_per_step: int, softcap,
                  quantized: bool = False):
    # one view a page of the step: K and V codes and, for int8 pages, the
    # per-(token, kv-head) fp32 K and V scales (the MaxText AQT kv_quant
    # idiom — codes and scales DMA together from the same physical page)
    n = pages_per_step
    k_refs, v_refs = refs[:n], refs[n:2 * n]
    ks_refs, vs_refs = refs[2 * n:3 * n], refs[3 * n:4 * n]
    o_ref, m_scr, l_scr, acc_scr = refs[(4 if quantized else 2) * n:]
    bi = pl.program_id(0)
    si = pl.program_id(1)
    length = jnp.minimum(len_ref[bi], pages_per_seq * page_size)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(si * n * page_size < length)
    def _pages():
        q = q_ref[0]                                 # (H, d)
        logits, limits = [], []
        for t in range(n):
            j = si * n + t                           # table index
            assigned = bt_ref[bi * pages_per_seq
                              + jnp.minimum(j, pages_per_seq - 1)] >= 0
            # tokens at or past the limit are masked: the whole page when
            # it is unassigned or lies past the length
            limit = jnp.where(assigned, length - j * page_size, 0)
            k = k_refs[t][0].astype(q.dtype)         # (page_size·KV, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (q.shape[-1] ** -0.5)
            if quantized:
                # a row's scale factors out of its dot products, so the
                # scales apply lane-wise to the (H, page_size·KV) logits and
                # probabilities instead of to the (page_size·KV, d) blocks
                s = s * ks_refs[t][0]                # (1, page_size·KV)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            logits.append(jnp.where(off_ref[...] < limit, s, _NEG_INF))
            limits.append(limit)

        m_prev = m_scr[...]
        m_new = functools.reduce(
            jnp.maximum, [s.max(axis=-1, keepdims=True) for s in logits],
            m_prev)
        alpha = jnp.exp(m_prev - m_new)
        l = l_scr[...] * alpha
        acc = acc_scr[...] * alpha
        for t, s in enumerate(logits):
            p = jnp.exp(s - m_new)
            l = l + p.sum(axis=-1, keepdims=True)
            if quantized:
                p = p * vs_refs[t][0]
            v = v_refs[t][0].astype(q.dtype)
            # a masked page's p is 0, but its V may hold anything, NaN too
            acc = acc + jnp.where(limits[t] > 0, jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32), 0.0)
        m_scr[...] = m_new
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(si == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _row_offsets(h: int, kv: int, page_size: int) -> np.ndarray:
    """(H, page_size·KV) int32: for query head ``i`` and key row ``r`` of a
    page, the token offset ``r // KV`` where ``r`` belongs to the head's
    KV head ``i // G``, else ``_OTHER_HEAD``."""
    g = h // kv
    r = np.arange(page_size * kv)
    own = (r % kv)[None, :] == (np.arange(h) // g)[:, None]
    return np.where(own, r // kv, _OTHER_HEAD).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           k_scales=None, v_scales=None,
                           softcap=None, interpret: bool = False):
    """q: (B, H, D); k_pages/v_pages: (N, page_size, KV, D);
    block_tables: (B, P) int32 physical page ids (-1 = unassigned);
    lengths: (B,) int32 tokens written so far.  Returns (B, H, D); a slot
    with no assigned page under its length reads 0.

    ``k_scales``/``v_scales`` (both or neither): (N, page_size, KV) fp32
    per-(token, kv-head) scales for int8 pages — the kernel applies them in
    VMEM to each page's logits and probabilities (``kv_quant="int8"``)."""
    b, h, d = q.shape
    n, page_size, kv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    p_seq = block_tables.shape[1]
    rows = page_size * kv
    per_step = _pages_per_step(rows, p_seq)

    def page_map(t):
        def index(bb, si, bt, ln):
            # the slot's last live page; past it every index repeats, so
            # nothing more is fetched.  -1 entries fetch page 0, masked.
            last = jnp.clip(ln[bb] - 1, 0, p_seq * page_size - 1) // page_size
            j = jnp.minimum(jnp.minimum(si, last // per_step) * per_step + t,
                            last)
            return (jnp.maximum(bt[bb * p_seq + j], 0), 0, 0)
        return index

    def slot_map(bb, si, bt, ln):
        del si, bt, ln
        return (bb, 0, 0)

    # (N, page, KV, D) -> (N, page·KV, D): the stored layout, reshaped.
    # Scales (N, page, KV) -> (N, 1, page·KV): one lane row a page; the unit
    # axis makes each block's last two dims equal the array's — the TPU's
    # tiling rule refuses a (1, rows) block over a 2-D (N, rows) array.
    pools = [(k_pages.reshape(n, rows, d), (1, rows, d)),
             (v_pages.reshape(n, rows, d), (1, rows, d))]
    if k_scales is not None:
        pools += [(s.reshape(n, 1, rows).astype(jnp.float32), (1, 1, rows))
                  for s in (k_scales, v_scales)]
    in_specs = [pl.BlockSpec((h, rows), lambda bb, si, bt, ln: (0, 0)),
                pl.BlockSpec((1, h, d), slot_map)]
    operands = [jnp.asarray(_row_offsets(h, kv, page_size)), q]
    for pool, block in pools:
        in_specs += [pl.BlockSpec(block, page_map(t)) for t in range(per_step)]
        operands += [pool] * per_step

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-p_seq // per_step)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), slot_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               pages_per_seq=p_seq, pages_per_step=per_step,
                               softcap=softcap,
                               quantized=k_scales is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
