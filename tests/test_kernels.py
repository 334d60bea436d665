"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan

pytestmark = [pytest.mark.kernels, pytest.mark.timeout(300)]

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA
    (1, 8, 1, 128, 128),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_sweep(b, h, kv, s, d, dtype, window):
    q = jax.random.normal(KEY, (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, kv, s, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


def test_flash_attention_blocks_dont_matter():
    b, h, kv, s, d = 1, 2, 2, 256, 64
    q = jax.random.normal(KEY, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, kv, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, kv, s, d))
    o1 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = flash_attention(q, k, v, block_q=128, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,h,kv,s,d", [
    (2, 8, 2, 512, 64),
    (3, 4, 4, 256, 128),
    (1, 8, 1, 1024, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, kv, s, d, dtype):
    q = jax.random.normal(KEY, (b, h, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, kv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, kv, d), dtype)
    lengths = jnp.asarray(np.random.default_rng(0).integers(1, s + 1, b), jnp.int32)
    out = decode_attention(q, k, v, lengths, block_k=128, interpret=True)
    expected = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


PAGE, PAGES_PER_SEQ = 16, 10
# slots: 1 token; mid-page; exactly on a page boundary; mid-page past the
# kernel's first grid step; the whole table; a table row all -1 with a
# length left over; a row all -1 at length 0
LENGTHS = [1, PAGE + PAGE // 2 - 1, 2 * PAGE, 5 * PAGE + 3,
           PAGE * PAGES_PER_SEQ, 37, 0]
MASKED = [5, 6]


def _paged_case(h, kv, d, rng):
    """A shuffled pool under LENGTHS, in random physical order.  Slot 1
    owns just the pages its length covers; the other live slots own a
    whole table row, as a request does that reserved its budget, so pages
    past their length are assigned.  Page 0 and a few spare pages are
    unassigned."""
    b = len(LENGTHS)
    need = [0 if s in MASKED else -(-n // PAGE) if s == 1 else PAGES_PER_SEQ
            for s, n in enumerate(LENGTHS)]
    num_pages = 1 + sum(need) + 3
    bt = np.full((b, PAGES_PER_SEQ), -1, np.int32)
    perm = rng.permutation(np.arange(1, num_pages))
    i = 0
    for s, n in enumerate(need):
        bt[s, :n] = perm[i:i + n]
        i += n
    q = rng.normal(size=(b, h, d)).astype(np.float32)
    kp = rng.normal(size=(num_pages, PAGE, kv, d)).astype(np.float32)
    vp = rng.normal(size=(num_pages, PAGE, kv, d)).astype(np.float32)
    return q, kp, vp, bt, np.asarray(LENGTHS, np.int32)


def _quantize_pages(x):
    """Per-(token, kv-head) symmetric int8 pages + fp32 scales, the same
    scheme ``paged.quantize_kv`` writes (scales laid out (N, page, kv))."""
    xf = np.asarray(x, np.float32)
    amax = np.abs(xf).max(-1)
    scale = np.maximum(amax, 1e-12) / 127.0
    codes = np.clip(np.round(xf / scale[..., None]), -127, 127).astype(np.int8)
    return codes, scale.astype(np.float32)


def _kernel_operands(q, kp, vp, kv_dtype):
    """(q, k pages, v pages, scales kwargs) as the kernel takes them: int8
    pages with a float32 query, else query and pages in ``kv_dtype``."""
    if kv_dtype == "int8":
        (kc, ks), (vc, vs) = _quantize_pages(kp), _quantize_pages(vp)
        return (jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                dict(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs)))
    dt = jnp.dtype(kv_dtype)
    return (jnp.asarray(q, dt), jnp.asarray(kp, dt), jnp.asarray(vp, dt), {})


@pytest.mark.parametrize("g,kv,d", [
    (4, 8, 128),     # qwen3-4b
    (16, 4, 128),    # qwen3-moe
    (4, 8, 120),     # h2o-danube-3-4b
    (1, 4, 128),     # MHA
    (16, 1, 120),    # MQA, 16 key rows a page
    (1, 1, 128),
])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_paged_decode_attention_sweep(g, kv, d, kv_dtype, softcap):
    """The kernel against the gathered jnp oracle, at every fill level of
    LENGTHS; int8 pages against the oracle on the same codes and scales,
    and within int8 error of the float pages.  A slot with no assigned
    page under its length reads 0 (the oracle averages page 0 there)."""
    rng = np.random.default_rng(g * 100 + kv * 10 + d)
    q, kp, vp, bt, lengths = _paged_case(g * kv, kv, d, rng)
    qk, kk, vk, scales = _kernel_operands(q, kp, vp, kv_dtype)
    bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    out = np.asarray(paged_decode_attention(
        qk, kk, vk, bt, lengths, softcap=softcap, interpret=True, **scales),
        np.float32)
    expected = np.asarray(ref.paged_decode_attention_ref(
        qk, kk, vk, bt, lengths, softcap=softcap, **scales), np.float32)
    live = [s for s in range(len(LENGTHS)) if s not in MASKED]
    tol = _tol(jnp.bfloat16 if kv_dtype == "bfloat16" else jnp.float32)
    np.testing.assert_allclose(out[live], expected[live], **tol)
    assert not out[MASKED].any()
    if kv_dtype == "int8":
        fp = ref.paged_decode_attention_ref(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), bt, lengths,
            softcap=softcap)
        np.testing.assert_allclose(out[live], np.asarray(fp)[live],
                                   rtol=0.05, atol=0.05)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_attention_never_reads_dead_pages(kv_dtype):
    """NaN in every page past each slot's length and in every unassigned
    page (page 0 included): the output is finite and equals the clean
    pool's, so dead pages are neither used nor leak through a 0 weight."""
    rng = np.random.default_rng(3)
    q, kp, vp, bt, lengths = _paged_case(32, 8, 128, rng)
    qk, kk, vk, scales = _kernel_operands(q, kp, vp, kv_dtype)
    read = {int(p) for s, n in enumerate(lengths)
            for p in bt[s, :-(-n // PAGE)] if p >= 0}
    dead = np.asarray([p for p in range(kp.shape[0]) if p not in read])
    if kv_dtype == "int8":     # int8 codes hold no NaN: poison the scales
        poisoned = (kk, vk, {k: v.at[dead].set(jnp.nan)
                             for k, v in scales.items()})
    else:
        poisoned = (kk.at[dead].set(jnp.nan), vk.at[dead].set(jnp.nan), {})
    bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    clean = paged_decode_attention(qk, kk, vk, bt, lengths, interpret=True,
                                   **scales)
    out = paged_decode_attention(qk, poisoned[0], poisoned[1], bt, lengths,
                                 interpret=True, **poisoned[2])
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(clean, np.float32))


def test_paged_decode_attention_matches_dense_decode():
    """A contiguous identity block table must reproduce the dense decode
    oracle: paging is pure bookkeeping, not different math."""
    b, h, kv, d, page_size, pages_per_seq = 2, 4, 2, 64, 16, 4
    s = page_size * pages_per_seq
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, kv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, kv, d))
    q = jax.random.normal(KEY, (b, h, d))
    lengths = jnp.asarray([s, 37], jnp.int32)
    # identity layout: request bi's page p is physical page 1 + bi*P + p
    bt = jnp.arange(1, 1 + b * pages_per_seq, dtype=jnp.int32).reshape(b, -1)
    kp = jnp.concatenate([jnp.zeros((1, page_size, kv, d)),
                          k.reshape(b * pages_per_seq, page_size, kv, d)])
    vp = jnp.concatenate([jnp.zeros((1, page_size, kv, d)),
                          v.reshape(b * pages_per_seq, page_size, kv, d)])
    out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    expected = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_window():
    b, h, kv, s, d = 2, 4, 2, 512, 64
    q = jax.random.normal(KEY, (b, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, kv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, kv, d))
    lengths = jnp.asarray([512, 300], jnp.int32)
    out = decode_attention(q, k, v, lengths, window=128, block_k=128, interpret=True)
    expected = ref.decode_attention_ref(q, k, v, lengths, window=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,t,h,d", [(2, 64, 3, 32), (1, 128, 2, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_sweep(b, t, h, d, dtype):
    mk = lambda i, scale=0.5: (jax.random.normal(
        jax.random.fold_in(KEY, i), (b, t, h, d)) * scale).astype(dtype)
    r, k, v = mk(1), mk(2), mk(3)
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 4),
                                         (b, t, h, d))).astype(dtype)
    u = (jax.random.normal(jax.random.fold_in(KEY, 5), (h, d)) * 0.1)
    s0 = jax.random.normal(jax.random.fold_in(KEY, 6), (b, h, d, d)) * 0.1
    y, s = rwkv6_scan(r, k, v, w, u, s0, block_t=32, interpret=True)
    yr, sr = ref.rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), **_tol(dtype))


def test_rwkv6_chunking_equivalence():
    """State carry across time chunks must be exact."""
    b, t, h, d = 1, 64, 2, 32
    mk = lambda i: jax.random.normal(jax.random.fold_in(KEY, i), (b, t, h, d)) * 0.5
    r, k, v = mk(1), mk(2), mk(3)
    w = jax.nn.sigmoid(mk(4))
    u = jax.random.normal(jax.random.fold_in(KEY, 5), (h, d)) * 0.1
    s0 = jnp.zeros((b, h, d, d))
    y1, s1 = rwkv6_scan(r, k, v, w, u, s0, block_t=16, interpret=True)
    y2, s2 = rwkv6_scan(r, k, v, w, u, s0, block_t=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,t,w", [(2, 128, 96), (1, 256, 64), (3, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(b, t, w, dtype):
    a = jax.nn.sigmoid(jax.random.normal(KEY, (b, t, w))).astype(dtype)
    bb = (jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, w)) * 0.5).astype(dtype)
    h0 = jax.random.normal(jax.random.fold_in(KEY, 2), (b, w)) * 0.5
    hs, hl = rglru_scan(a, bb, h0, block_t=32, block_w=32, interpret=True)
    hsr, hlr = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hsr), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), **_tol(dtype))


def test_rglru_state_continuation():
    """Scanning [0:T] == scanning [0:T/2] then [T/2:T] with carried state."""
    b, t, w = 1, 64, 32
    a = jax.nn.sigmoid(jax.random.normal(KEY, (b, t, w)))
    bb = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, w)) * 0.5
    h0 = jnp.zeros((b, w))
    full, _ = rglru_scan(a, bb, h0, block_t=32, block_w=32, interpret=True)
    h1, hmid = rglru_scan(a[:, :32], bb[:, :32], h0, block_t=32, block_w=32,
                          interpret=True)
    h2, _ = rglru_scan(a[:, 32:], bb[:, 32:], hmid, block_t=32, block_w=32,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(full),
                               np.concatenate([h1, h2], axis=1),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_non_causal():
    """Encoder-style dense attention exercises the unguarded tile path."""
    b, h, kv, s, d = 1, 2, 2, 128, 64
    q = jax.random.normal(KEY, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, kv, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, kv, s, d))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_window_prunes_but_matches():
    """Narrow window: most tiles are pruned at block level; numerics exact."""
    b, h, kv, s, d = 1, 2, 1, 512, 64
    q = jax.random.normal(KEY, (b, h, s, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, kv, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, kv, s, d))
    out = flash_attention(q, k, v, causal=True, window=32, block_q=64,
                          block_k=64, interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)
