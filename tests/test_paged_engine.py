"""Paged-KV engine: slot-engine parity, chunked prefill, abort→resume.

The two load-bearing guarantees (ISSUE acceptance criteria):

* the paged engine matches the seed slot engine token-for-token under
  greedy decoding, at mixed prompt lengths with co-scheduled prefill;
* ABORT with retained pages → resume produces byte-identical samples to
  an uninterrupted run (no prefix re-prefill, logprobs bit-equal).
"""
import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.core.llm_proxy import LLMProxy
from repro.core.sample_buffer import SampleBuffer
from repro.core.scheduler import RolloutProducer
from repro.core.types import RolloutTask, next_uid
from repro.models import get_api
from repro.rollout.engine import DecodeEngine
from repro.rollout.paged_engine import PagedDecodeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = tiny("qwen3-4b", vocab_size=32)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def _drain(eng, want, max_steps=500):
    results = {}
    for _ in range(max_steps):
        for rid, toks, lps in eng.step():
            results[rid] = (list(toks), list(lps))
        if len(results) >= want:
            return results
    raise AssertionError(f"engine stalled: {len(results)}/{want} finished")


def _solo_slot(api, params, prompt, budget, max_total_len=64):
    eng = DecodeEngine(api, params, num_slots=1, max_total_len=max_total_len,
                       eos_id=99, temperature=0.0, prefill_bucket=None)
    eng.add_request(0, prompt, budget)
    return _drain(eng, 1)[0]


def test_paged_matches_slot_engine_greedy_mixed_lengths(setup):
    """Mixed-length prompts admitted while others decode: every request's
    greedy output must equal the slot engine decoding it alone."""
    cfg, api, params = setup
    eng = PagedDecodeEngine(api, params, num_slots=3, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    rng = np.random.default_rng(7)
    prompts = {rid: rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for rid, n in enumerate([3, 17, 9, 26, 5])}
    # admit the first wave; feed the rest as slots free up
    pending = list(prompts)[::-1]
    for _ in range(3):
        eng.add_request(pending[-1], prompts[pending[-1]], 6)
        pending.pop()
    results = {}
    for _ in range(500):
        for rid, toks, lps in eng.step():
            results[rid] = (list(toks), list(lps))
            if pending:
                eng.add_request(pending[-1], prompts[pending[-1]], 6)
                pending.pop()
        if len(results) == len(prompts):
            break
    assert len(results) == len(prompts)
    for rid, prompt in prompts.items():
        want_t, want_l = _solo_slot(api, params, prompt, 6)
        got_t, got_l = results[rid]
        assert got_t == want_t, f"request {rid} diverged from slot engine"
        np.testing.assert_allclose(got_l, want_l, rtol=1e-5, atol=1e-6)


def test_chunked_prefill_coschedules_with_decode(setup):
    """While a long prompt prefills chunk-by-chunk, an already-decoding
    request keeps producing tokens every step (no prefill stall)."""
    cfg, api, params = setup
    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    eng.add_request(0, np.asarray([1, 2, 3], np.int32), 30)
    while eng.slots and eng.slots[list(eng.req_to_slot.values())[0]].phase != "decode":
        eng.step()
    # long prompt arrives: 4 chunks of prefill needed
    long_prompt = np.arange(1, 33, dtype=np.int32)
    eng.add_request(1, long_prompt, 4)
    tokens_before = len(eng.slots[eng.req_to_slot[0]].tokens)
    for _ in range(4):  # the 4 chunk steps
        eng.step()
    tokens_after = len(eng.slots[eng.req_to_slot[0]].tokens)
    assert tokens_after - tokens_before == 4, \
        "request 0 must decode one token per step during request 1's prefill"
    assert eng.total_prefill_chunks >= 4


def test_abort_resume_byte_identical(setup):
    """Retain pages on ABORT, resume later: final tokens AND logprobs are
    byte-identical to the uninterrupted run (prefix KV reused, not rebuilt)."""
    cfg, api, params = setup
    prompt = np.asarray([1, 5, 7, 9, 2, 4], np.int32)
    budget = 8

    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    eng.add_request(0, prompt, budget)
    base_t, base_l = _drain(eng, 1)[0]

    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    eng.add_request(0, prompt, budget)
    for _ in range(5):
        eng.step()
    partial = eng.abort(0, retain=True)
    assert partial.resumable and len(partial.tokens) > 0
    prefill_tokens_before_resume = eng.total_prefill_tokens
    # churn an unrelated request through the freed slot (page-pool reuse)
    eng.add_request(5, np.asarray([8, 8], np.int32), 3)
    _drain(eng, 1)
    eng.resume_request(0, 10, budget - len(partial.tokens))
    got = _drain(eng, 1)[10]
    # resume must NOT have re-prefilled the prefix
    assert eng.total_prefill_tokens == prefill_tokens_before_resume + 2, \
        "only request 5's 2-token prompt may have been prefilled after abort"
    full_t = list(partial.tokens) + got[0]
    full_l = list(partial.logprobs) + got[1]
    assert full_t == base_t
    np.testing.assert_array_equal(np.asarray(full_l, np.float32),
                                  np.asarray(base_l, np.float32))


def test_abort_resume_through_proxy_and_producer(setup):
    """The async path end-to-end: producer submits, ABORT_STALE(retain)
    interrupts, resume re-attaches pages; the published sample equals the
    uninterrupted greedy sequence."""
    cfg, api, params = setup
    prompt = np.asarray([2, 9, 4, 3], np.int32)
    budget = 40  # long enough that the abort below cannot race completion

    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    eng.add_request(0, prompt, budget)
    base_t, _ = _drain(eng, 1)[0]

    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    proxy = LLMProxy(eng).start()
    buf = SampleBuffer(batch_size=4, alpha=4)
    prompts = iter([(0, prompt)])
    producer = RolloutProducer(proxy, buf, prompts, group_size=1,
                               max_new_tokens=budget,
                               reward_fn=lambda s: 1.0)
    producer.start()
    # let generation get going, then abort everything with retained pages.
    # suspend() parks the loop so the ABORT command is guaranteed to be
    # processed before the request can run to completion.
    deadline = time.monotonic() + 30
    while eng.total_tokens_decoded < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng.total_tokens_decoded >= 2, "generation never started"
    proxy.suspend()
    proxy.abort_stale(min_version=10, retain=True)
    proxy.resume()
    while not buf._samples and time.monotonic() < deadline:
        time.sleep(0.01)
    producer.stop()
    proxy.stop()
    assert len(buf._samples) == 1
    sample = buf._samples[0]
    buf.close()
    assert proxy.requests_aborted >= 1
    assert list(sample.response_tokens) == base_t
    np.testing.assert_array_equal(sample.prompt_tokens, prompt)


def test_page_pool_accounting(setup):
    """Pages are exclusively owned, freed on finish/abort, admission is
    gated on pool headroom, and the refcount audit passes after every
    completion / abort / resume transition."""
    cfg, api, params = setup
    eng = PagedDecodeEngine(api, params, num_slots=4, max_total_len=32,
                            page_size=8, num_pages=9, prefill_chunk=8,
                            eos_id=99, temperature=0.0)
    total = eng.num_free_pages
    assert total == 8  # page 0 reserved as garbage
    assert eng.pages_shared == 0 and eng.pages_private == 0
    # 3 requests x (8 prompt + 8 budget) = 2 pages each
    for rid in range(3):
        assert eng.can_admit(8, 8)
        eng.add_request(rid, np.arange(1, 9, dtype=np.int32), 8)
    assert eng.num_free_pages == 2
    assert eng.pages_private == 6 and eng.pages_shared == 0
    assert eng.can_admit(8, 8) and not eng.can_admit(16, 16)
    eng.audit_pages()
    # retained pages stay allocated until release
    eng.step()
    partial = eng.abort(2, retain=True)
    assert partial.resumable
    assert eng.num_free_pages == 2
    eng.audit_pages()
    eng.release_retained(2)
    assert eng.num_free_pages == 4
    eng.audit_pages()
    # plain abort frees immediately
    eng.abort(1)
    assert eng.num_free_pages == 6
    eng.audit_pages()
    _drain(eng, 1)  # request 0 runs to completion
    assert eng.num_free_pages == total
    assert eng.pages_private == 0 and eng.pages_shared == 0
    assert eng.peak_pages_in_use == 6
    eng.audit_pages()
    assert not eng.slots and not eng.retained


def test_abort_resume_audit_cycle(setup):
    """Refcounts stay leak-free through a full abort->resume->finish cycle
    (the retained record holds the refs while parked)."""
    cfg, api, params = setup
    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=64,
                            page_size=8, prefill_chunk=8, eos_id=99,
                            temperature=0.0)
    eng.add_request(0, np.asarray([1, 5, 7, 9, 2, 4], np.int32), 8)
    for _ in range(5):
        eng.step()
    partial = eng.abort(0, retain=True)
    assert partial.resumable
    eng.audit_pages()
    eng.resume_request(0, 10, 8 - len(partial.tokens))
    eng.audit_pages()
    _drain(eng, 1)
    eng.audit_pages()
    assert eng.num_free_pages == eng.num_pages - 1


def test_total_decode_pages_is_the_sum_of_live_pages(setup):
    """The pages the decode kernel reads: ceil((pos + 1) / page_size) for
    every decoding slot on every step, across a finish and the admission
    that takes the freed slot."""
    _, api, params = setup
    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=32,
                            page_size=4, prefill_chunk=4, eos_id=99,
                            temperature=0.0)
    rng = np.random.default_rng(0)
    eng.add_request(0, rng.integers(1, 32, 3), 4)
    eng.add_request(1, rng.integers(1, 32, 6), 16)
    expect, done, admitted = 0, set(), False
    for _ in range(60):
        pos = np.asarray(eng.pos)
        # a slot with no budget left finishes before the step decodes
        expect += sum(-(-(int(pos[s]) + 1) // 4)
                      for s, st in eng.slots.items()
                      if st.phase == "decode" and st.remaining > 0)
        done |= {rid for rid, _, _ in eng.step()}
        if 0 in done and not admitted:
            eng.add_request(2, rng.integers(1, 32, 9), 8)
            admitted = True
        if done == {0, 1, 2}:
            break
    assert done == {0, 1, 2}
    assert eng.total_decode_pages == expect > eng.total_decode_steps
    # the count sits between one page a token and the table's width
    assert (eng.total_tokens_decoded <= eng.total_decode_pages
            <= eng.pages_per_seq * eng.total_tokens_decoded)


@pytest.mark.kernels
def test_engine_kernel_attention_matches_ref(setup):
    """The Pallas paged decode-attention path (interpret mode) plugged into
    the fused engine step must produce the ref path's greedy tokens.  In
    float32: bf16 rounding differs between the two paths and can flip the
    argmax of near-tied random-weight logits."""
    cfg, api, params = setup
    api = get_api(dataclasses.replace(cfg, dtype="float32"))
    params = jax.tree_util.tree_map(lambda a: a.astype(np.float32), params)
    outs = {}
    for impl in ("ref", "kernel_interpret"):
        eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=32,
                                page_size=8, prefill_chunk=8, eos_id=99,
                                temperature=0.0, attn_impl=impl)
        eng.add_request(0, np.asarray([1, 5, 7], np.int32), 6)
        outs[impl] = _drain(eng, 1)[0][0]
    assert outs["ref"] == outs["kernel_interpret"]


def test_paged_engine_rejects_recurrent_families(setup):
    cfg = tiny("rwkv6-3b", vocab_size=32)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged"):
        PagedDecodeEngine(api, params, num_slots=1, max_total_len=16)


def test_resume_bypasses_page_starved_head_of_queue(setup):
    """Liveness: a page-starved plain request at the head of the pending
    queue must NOT block resume requests behind it — the resumes re-attach
    already-retained pages and are what frees the pool again."""
    cfg, api, params = setup
    # pool fits exactly two 2-page requests (page 0 is garbage)
    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=32,
                            page_size=8, num_pages=5, prefill_chunk=8,
                            eos_id=99, temperature=0.0)
    proxy = LLMProxy(eng)
    results = []
    for rid in (0, 1):
        task = RolloutTask(task_id=rid, prompt_id=rid, replica_idx=0,
                           prompt_tokens=np.asarray([1 + rid, 2, 3], np.int32),
                           max_new_tokens=8)
        proxy.generate(task, version=0, callback=results.append)
    proxy._process_commands()
    proxy._admit_pending()
    for _ in range(6):
        eng.step()
    # park both requests (all pages stay allocated)...
    proxy.abort_stale(min_version=5, retain=True)
    proxy._process_commands()
    assert eng.num_free_pages == 0 and len(eng.retained) == 2
    # ...then a page-hungry plain request jumps the queue ahead of resumes
    blocker = RolloutTask(task_id=99, prompt_id=99, replica_idx=0,
                          prompt_tokens=np.asarray([7] * 16, np.int32),
                          max_new_tokens=16)
    proxy.generate(blocker, version=5, callback=results.append)
    for i, r in enumerate(results[:2]):
        resumed = RolloutTask(task_id=10 + i, prompt_id=r.task.prompt_id,
                              replica_idx=0, prompt_tokens=r.task.prompt_tokens,
                              max_new_tokens=8 - len(r.tokens))
        proxy.generate_resumed(resumed, 5, results.append,
                               resume_from=r.request_id)
    proxy._process_commands()
    proxy._admit_pending()
    # the two resumes are running despite the blocked head
    assert sorted(eng.req_to_slot) == [10, 11]
    finished = set()
    for _ in range(200):
        for rid, _toks, _lps in eng.step():
            finished.add(rid)
        proxy._admit_pending()
        if finished >= {10, 11, 99}:
            break
    assert finished >= {10, 11, 99}, "blocker was never admitted"


def test_proxy_admits_paged_requests_beyond_pool(setup):
    """LLMProxy + can_admit: requests queue when the pool is full and are
    admitted as pages free up — no assertion crashes."""
    cfg, api, params = setup
    eng = PagedDecodeEngine(api, params, num_slots=2, max_total_len=32,
                            page_size=8, num_pages=5, prefill_chunk=8,
                            eos_id=99, temperature=0.0)
    proxy = LLMProxy(eng).start()
    results = []
    lock = threading.Lock()

    def cb(r):
        with lock:
            results.append(r)

    for i in range(4):
        task = RolloutTask(task_id=next_uid(), prompt_id=i, replica_idx=0,
                           prompt_tokens=np.asarray([1 + i, 2, 3], np.int32),
                           max_new_tokens=5)
        proxy.generate(task, version=0, callback=cb)
    deadline = time.monotonic() + 30
    while len(results) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    proxy.stop()
    assert len(results) == 4
    assert all(not r.aborted and len(r.tokens) > 0 for r in results)
