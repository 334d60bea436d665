"""Paged-KV continuous-batching engine: chunked prefill, abort→resume, and
copy-on-write prefix sharing for GRPO prompt groups.

The slot engine (`engine.py`) prefills each admitted prompt at batch=1 in a
single variable-length call — every active request stalls for the whole
prefill, each distinct prompt length compiles a new executable, and an
ABORTed request's KV is lost (resume re-prefills the accumulated prefix).
This engine fixes all three pathologies:

* **Paged KV** — KV lives in a shared page pool with per-request block
  tables (`repro.models.paged`); admission allocates pages, ABORT with
  ``retain=True`` parks them, resume re-attaches them.  No prefix is ever
  recomputed on the abort→resume path (§5.1 queue scheduling + the async
  architecture's abort-under-new-weights).  Behaviour-policy logprobs of
  the retained prefix are kept — they are exactly what the IS-based
  off-policy correctors consume; new-policy logprobs are recomputed by the
  trainer's forward pass where needed, never by the engine.
* **Chunked prefill** — prompts are fed in fixed-size token chunks
  co-scheduled with decode inside the same ``step()``: one chunk of ONE
  prefilling request plus one decode token for EVERY decoding slot.
  Admitting a 32k prompt no longer blocks the batch for a full prefill.
* **COW prefix sharing** — ``submit_group`` admits the G candidates of one
  GRPO prompt as a unit: the prompt is chunk-prefilled ONCE into the
  leader lane, then the group forks — follower block tables alias the
  fully-filled prompt pages (refcount G in the ``PagePool``) and each lane
  privately owns only the partial tail page (copied at fork) plus its
  decode region.  G× less prefill compute, ~(G-1)/G of the prompt KV
  reclaimed; divergence after the fork only ever writes privately owned
  pages, so the Pallas ``paged_decode_attention`` kernel is unchanged —
  only block-table construction knows about sharing.
* **Automatic cross-prompt prefix caching** — with ``prefix_cache=True`` a
  radix tree (`repro.models.paged.RadixCache`) indexes every fully-filled
  KV page of finished/aborted requests by token content; admission aliases
  the longest cached page-aligned prefix into the new block table and
  chunked prefill starts at the first uncached token.  A prefilling slot at
  a page boundary also adopts pages a concurrent request just published, so
  a shared system prompt prefills exactly once per batch.  LRU leaves evict
  under page pressure (the cache never causes admission failure) and the
  whole tree flushes on ``update_weights`` (cached KV is policy-dependent).
* **Static shapes** — ``step()`` is a single jitted call (chunk + decode
  fused, ``lax.cond``-gated) whose shapes never depend on prompt length or
  fill level: exactly ONE executable serves every workload (TPU-friendly;
  the slot engine compiles one prefill per padded prompt length).

Implements `repro.core.llm_proxy.InferenceEngine` plus the retain/resume
and group-submit extensions consumed by `repro.core.scheduler`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.types import GenerationResult
from repro.models import paged
from repro.models.api import ModelAPI
from repro.quant import core as quant
from repro.rollout.sampler import sample_tokens

_PREFILL = "prefill"
_DECODE = "decode"
_FORKWAIT = "forkwait"   # group follower parked until the leader's prefill


@dataclasses.dataclass
class _SlotState:
    request_id: int
    prompt: np.ndarray
    tokens: List[int]
    logprobs: List[float]
    remaining: int
    phase: str = _PREFILL
    prefill_done: int = 0
    carried_last: Optional[int] = None   # last sampled token of a resumed prefix
    followers: List[int] = dataclasses.field(default_factory=list)
    group_leader: Optional[int] = None   # follower pre-fork: leader's slot
    # token content backing the slot's written KV region: positions
    # [0, len(content_prefix)) hold content_prefix, sampled tokens append
    # after it.  Equals ``prompt`` except for resumed-decode slots, whose
    # written region already includes previously decoded tokens.
    content_prefix: Optional[np.ndarray] = None
    # weight epoch the slot's KV was (first) computed under: pages are only
    # published to the prefix cache while this matches the engine's current
    # epoch — a post-weight-sync abort must not repopulate the flushed
    # cache with old-policy KV.
    epoch: int = 0


@dataclasses.dataclass
class _Retained:
    """A parked request: pages stay allocated (refs held), state frozen."""
    pages: List[int]
    phase: str
    prompt: np.ndarray
    prefill_done: int
    length: int                          # KV positions written (pos value)
    last_token: int
    # full token content of the written region (plus the pending last token
    # for decode-phase records): lets the prefix cache index these pages
    # if the record is released instead of resumed.
    content: Optional[np.ndarray] = None
    epoch: int = 0                       # weight epoch the KV was computed under


ATTN_IMPLS = ("ref", "kernel", "kernel_interpret")


def platform_attn_impl(device=None) -> str:
    """Decode attention for a platform: the Pallas kernel on TPU, the
    pure-JAX gather reference elsewhere."""
    platform = device.platform if device is not None else jax.default_backend()
    return "kernel" if platform == "tpu" else "ref"


class PagedDecodeEngine:
    """Continuous-batching engine over a refcounted paged KV pool.

    ``attn_impl``: None (default) follows the platform — see
    ``platform_attn_impl``.  The explicit choices serve parity tests:
    "ref" (pure-JAX gather, exact vs the slot engine), "kernel" (Pallas
    paged decode attention) or "kernel_interpret" (Pallas interpret mode,
    for CPU validation).

    ``device``: commit the engine's weights, page pool and tables to this
    device (each replica of a fleet on its own chip); weight syncs land
    there too.  None keeps JAX's default placement.
    """

    supports_retain = True
    supports_group = True

    def __init__(self, api: ModelAPI, params, *, num_slots: int = 8,
                 max_total_len: int = 128, page_size: int = 16,
                 prefill_chunk: int = 16, num_pages: Optional[int] = None,
                 eos_id: int = 2, temperature: float = 1.0, top_k: int = 0,
                 pad_id: int = 0, seed: int = 0,
                 attn_impl: Optional[str] = None,
                 prefix_cache: bool = False, quant_mode: str = "off",
                 kv_quant: str = "off", device=None):
        cfg = api.cfg
        if api.init_paged_cache is None:
            raise ValueError(f"family {cfg.family} has no paged-KV support "
                             "(use the slot DecodeEngine)")
        if cfg.sliding_window is not None and cfg.sliding_window < max_total_len:
            raise ValueError("engine requires cache >= max_total_len "
                             "(enlarge window or shorten sequences)")
        if quant_mode not in quant.MODES:
            raise ValueError(f"unknown quant_mode {quant_mode!r} "
                             f"(expected {' | '.join(quant.MODES)})")
        if kv_quant not in quant.KV_MODES:
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             f"(expected {' | '.join(quant.KV_MODES)})")
        if attn_impl is None:
            attn_impl = platform_attn_impl(device)
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(f"unknown attn_impl {attn_impl!r} "
                             f"(expected {' | '.join(ATTN_IMPLS)})")
        self.api = api
        self.device = device
        # quantize-on-sync: replicas hold int8/fp8 codes on device (the
        # trainer's tree is quantized HERE, at construction and on every
        # update_weights) and the jitted step dequantizes at trace time.
        self.quant_mode = quant_mode
        self.kv_quant = kv_quant
        self.params = self._place(quant.quantize_params(params, quant_mode))
        self.total_weight_syncs_quantized = 0
        self.num_slots = num_slots
        self.max_total_len = max_total_len
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.pages_per_seq = paged.pages_per_seq(max_total_len, page_size)
        if num_pages is None:
            num_pages = 1 + num_slots * self.pages_per_seq  # +1: garbage page
        self.num_pages = num_pages
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.temperature = temperature
        self.top_k = top_k
        self.attn_impl = attn_impl
        self._key = jax.random.PRNGKey(seed)

        self.cache = self._place(api.init_paged_cache(num_pages, page_size,
                                                      kv_quant=kv_quant))
        self.block_tables = self._place(
            jnp.full((num_slots, self.pages_per_seq), -1, jnp.int32))
        self.cur_token = self._place(jnp.full((num_slots,), pad_id, jnp.int32))
        self.pos = self._place(jnp.zeros((num_slots,), jnp.int32))
        self.pool = paged.PagePool(num_pages, page_size)
        # automatic cross-prompt prefix caching (radix tree over page
        # contents); None = disabled, every page frees on release.
        self.prefix_cache: Optional[paged.RadixCache] = \
            paged.RadixCache(self.pool) if prefix_cache else None
        self._weight_epoch = 0
        self._slot_pages: Dict[int, List[int]] = {}
        self.slots: Dict[int, _SlotState] = {}
        self.req_to_slot: Dict[int, int] = {}
        self.retained: Dict[int, _Retained] = {}
        self._rr = 0

        self.total_decode_steps = 0
        self.total_tokens_decoded = 0
        self.total_prefill_chunks = 0
        self.total_prefill_tokens = 0
        self.total_groups_forked = 0
        # sum over decode steps and decoding slots of the context the decode
        # kernel attends (pos + 1), from host slot state
        self.total_decode_context = 0
        # the pages the decode kernel reads: ceil((pos + 1) / page_size) a
        # decoding slot a step; pages_per_seq a slot a step less this is
        # what the kernel skips
        self.total_decode_pages = 0
        # cross-replica transfers issue ONE gather/scatter device call per
        # request: ops counters stay O(requests), page counters O(pages).
        self.pages_transferred_in = 0    # cross-replica pages imported
        self.pages_transferred_out = 0   # cross-replica pages exported
        self.transfer_bytes_in = 0
        self.transfer_bytes_out = 0
        self.transfer_device_ops = 0     # batched export/import dispatches

        self._step = jax.jit(self._step_impl, donate_argnums=(1,))
        self._copy_pages = jax.jit(paged.copy_pages, donate_argnums=(0,))
        self._import_pages = jax.jit(paged.import_pages, donate_argnums=(0,))

    def _place(self, tree):
        """Copy to the engine's device every leaf of ``tree`` that is not
        already there.  The copies are committed, so uncommitted operands
        (host inputs, fresh jnp arrays) follow them into every step.  Leaves
        already on the device are kept as they are: a replica on the
        trainer's device shares the trainer's weight buffers."""
        if self.device is None:
            return tree
        return jax.tree_util.tree_map(
            lambda a: a if a.devices() == {self.device}
            else jax.device_put(a, self.device), tree)

    # ----------------------------------------------------------- jit body
    def _step_impl(self, params, cache, cur_token, pos, decode_tables,
                   chunk_tokens, chunk_valid, chunk_start, chunk_row,
                   do_prefill, do_decode, key):
        """ONE fused engine step: a prefill chunk for one request (cond-gated)
        plus a decode token for every unmasked slot.  All shapes static."""
        cfg = self.api.cfg
        vocab = cfg.vocab_size
        # dequantize quantize-on-sync weights at trace time: the multiply
        # fuses into each matmul consumer (W8A16), and for an unquantized
        # tree this is an identity traversal — the jaxpr is unchanged, so
        # quant_mode="off" stays byte-identical.
        params = quant.dequantize_params(params)

        def run_prefill(c):
            return self.api.prefill_chunk(params, chunk_tokens, chunk_valid,
                                          chunk_start, chunk_row, c)

        def skip_prefill(c):
            return jnp.zeros((1, vocab), jnp.float32), c

        chunk_logits, cache = jax.lax.cond(do_prefill, run_prefill,
                                           skip_prefill, cache)

        def run_decode(c):
            return self.api.decode_paged(params, cur_token, pos, c,
                                         decode_tables,
                                         attn_impl=self.attn_impl)

        def skip_decode(c):
            return jnp.zeros((self.num_slots, vocab), jnp.float32), c

        dec_logits, cache = jax.lax.cond(do_decode, run_decode,
                                         skip_decode, cache)

        kp, kd = jax.random.split(key)
        ptok, plp = sample_tokens(kp, chunk_logits,
                                  temperature=self.temperature, top_k=self.top_k)
        dtok, dlp = sample_tokens(kd, dec_logits,
                                  temperature=self.temperature, top_k=self.top_k)
        # chunk_logits ride along so group forks can sample per-follower
        # first tokens from the final prefill position.
        return (ptok.astype(jnp.int32), plp, dtok.astype(jnp.int32), dlp,
                chunk_logits, cache)

    # ------------------------------------------------------------ protocol
    @property
    def num_free_slots(self) -> int:
        return self.num_slots - len(self.slots)

    @property
    def num_free_pages(self) -> int:
        return self.pool.pages_free

    @property
    def pages_free(self) -> int:
        return self.pool.pages_free

    @property
    def pages_shared(self) -> int:
        return self.pool.pages_shared

    @property
    def pages_private(self) -> int:
        return self.pool.pages_private

    @property
    def peak_pages_in_use(self) -> int:
        return self.pool.peak_pages_in_use

    @property
    def active_request_ids(self) -> List[int]:
        return list(self.req_to_slot)

    # ------------------------------------------------- prefix-cache counters
    @property
    def cache_lookups(self) -> int:
        return self.prefix_cache.lookups if self.prefix_cache else 0

    @property
    def cache_hits(self) -> int:
        return self.prefix_cache.hits if self.prefix_cache else 0

    @property
    def cache_ext_hits(self) -> int:
        """Productive mid-prefill extensions (concurrent-preamble pickups)."""
        return self.prefix_cache.ext_hits if self.prefix_cache else 0

    @property
    def cache_hit_tokens(self) -> int:
        """Prefill tokens skipped by aliasing cached prefix pages."""
        return self.prefix_cache.hit_tokens if self.prefix_cache else 0

    @property
    def cache_evicted_pages(self) -> int:
        return self.prefix_cache.evicted_pages if self.prefix_cache else 0

    @property
    def cache_pages_held(self) -> int:
        return len(self.prefix_cache.held_pages()) if self.prefix_cache else 0

    def set_quant_mode(self, mode: str) -> None:
        """Change the weight-quantization mode mid-run.  Takes effect at the
        NEXT ``update_weights`` — the current tree is already (lossily)
        quantized, so re-quantizing in place would compound error; the next
        sync ships fresh full-precision weights to quantize."""
        if mode not in quant.MODES:
            raise ValueError(f"unknown quant_mode {mode!r} "
                             f"(expected {' | '.join(quant.MODES)})")
        self.quant_mode = mode

    def update_weights(self, params) -> None:
        self.params = self._place(quant.quantize_params(params,
                                                        self.quant_mode))
        if self.quant_mode != "off":
            self.total_weight_syncs_quantized += 1
        # bump the epoch even with the cache off: slot/retained records
        # stamped with an older epoch must never publish their (now
        # stale-policy) KV if the cache is enabled later.
        self._weight_epoch += 1
        if self.prefix_cache is not None:
            # every cached page was computed under the old policy: new
            # admissions must not alias stale KV.  Running requests keep
            # their own references (existing retain/resume semantics), and
            # the epoch stamp keeps their later release/abort/finish from
            # re-inserting old-policy pages into the flushed tree.
            self.prefix_cache.clear()

    def _pages_needed(self, total_len: int) -> int:
        return -(-total_len // self.page_size)

    def _can_cover(self, n: int) -> bool:
        """Whether ``n`` pages can be produced right now: free pages first,
        cache-evictable holds as the fallback — the cache must never cause
        an admission failure.  The free-page check short-circuits so the
        evictability tree walk only runs under actual page pressure."""
        if n <= self.pool.pages_free:
            return True
        if self.prefix_cache is None:
            return False
        return n <= self.pool.pages_free + self.prefix_cache.evictable_pages

    def _alloc(self, n: int) -> List[int]:
        """Pool alloc that evicts LRU cache leaves when free pages run dry."""
        short = n - self.pool.pages_free
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)
        return self.pool.alloc(n)

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        if self.num_free_slots <= 0:
            return False
        return self._can_cover(self._pages_needed(prompt_len + max_new_tokens))

    def can_cover_pages(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Page-only admission check (ignores slots): whether the pages for
        a full-budget request could be produced right now.  The SLO
        preemption path uses this — preempting frees a SLOT, never pages
        (the victim keeps its KV parked), so it must only fire when pages
        already cover the arrival."""
        return self._can_cover(self._pages_needed(prompt_len + max_new_tokens))

    def num_decoded(self, request_id: int) -> int:
        """Decode progress of an active request (0 if unknown) — the SLO
        watchdog's stall/long-tail signal."""
        slot = self.req_to_slot.get(request_id)
        if slot is None:
            return 0
        return len(self.slots[slot].tokens)

    def _set_table_row(self, slot: int, pages: List[int]) -> None:
        row = np.full((self.pages_per_seq,), -1, np.int32)
        row[:len(pages)] = pages
        self.block_tables = self.block_tables.at[slot].set(jnp.asarray(row))

    def _free_slot_id(self) -> int:
        return next(i for i in range(self.num_slots) if i not in self.slots)

    def add_request(self, request_id: int, prompt_tokens,
                    max_new_tokens: int) -> None:
        assert self.num_free_slots > 0, "no free slot"
        prompt = np.asarray(prompt_tokens, np.int32).ravel()
        plen = len(prompt)
        assert plen + max_new_tokens <= self.max_total_len, "sequence budget"
        slot = self._free_slot_id()
        # automatic prefix caching: alias the longest cached page-aligned
        # prefix into the block table and start chunked prefill at the first
        # uncached token.  The match is capped at plen-1 tokens — the final
        # prompt token must always prefill to produce first-sample logits.
        cached: List[int] = []
        if self.prefix_cache is not None and plen > 1:
            cached = self.prefix_cache.match(prompt[:plen - 1])
        pages = cached + self._alloc(
            self._pages_needed(plen + max_new_tokens) - len(cached))
        self._set_table_row(slot, pages)
        self._slot_pages[slot] = pages
        self.slots[slot] = _SlotState(request_id=request_id, prompt=prompt,
                                      tokens=[], logprobs=[],
                                      remaining=max_new_tokens,
                                      prefill_done=len(cached) * self.page_size,
                                      content_prefix=prompt,
                                      epoch=self._weight_epoch)
        self.req_to_slot[request_id] = slot

    # -------------------------------------------------- group (COW) submit
    def _group_page_plan(self, prompt_len: int,
                         max_new_tokens: int) -> Tuple[int, int]:
        """(shared-prefix pages, private pages per lane) for one group lane."""
        total = self._pages_needed(prompt_len + max_new_tokens)
        full = prompt_len // self.page_size
        return full, total - full

    def can_admit_group(self, prompt_len: int, group_size: int,
                        max_new_tokens: int) -> bool:
        full, priv = self._group_page_plan(prompt_len, max_new_tokens)
        return (self.num_free_slots >= group_size
                and self._can_cover(full + group_size * priv))

    def group_fits_pool(self, prompt_len: int, group_size: int,
                        max_new_tokens: int) -> bool:
        """Whether the group could EVER be admitted as a unit (vs the whole
        pool, not current headroom).  The proxy expands never-fitting groups
        into singles instead of letting them block the queue forever."""
        full, priv = self._group_page_plan(prompt_len, max_new_tokens)
        return (group_size <= self.num_slots
                and full + group_size * priv <= self.num_pages - 1)

    def submit_group(self, request_ids: List[int], prompt_tokens,
                     max_new_tokens: int) -> None:
        """Admit the G candidates of ONE prompt as a COW group.

        The first request becomes the prefill leader (a normal chunked
        prefill over its fully allocated block table); the rest park in
        ``forkwait`` holding only their private pages.  When the leader's
        prefill completes, ``_fork_followers`` aliases the fully-filled
        prompt pages into every follower's table (refcount++), copies the
        partial tail page once per follower, and flips them all to decode —
        the prompt is prefilled exactly once for the whole group."""
        g = len(request_ids)
        assert g >= 1
        prompt = np.asarray(prompt_tokens, np.int32).ravel()
        plen = len(prompt)
        assert plen + max_new_tokens <= self.max_total_len, "sequence budget"
        assert self.num_free_slots >= g, "not enough free slots for group"
        full, priv = self._group_page_plan(plen, max_new_tokens)
        assert self._can_cover(full + g * priv), "page pool exhausted"

        leader = self._free_slot_id()
        # the leader's prefill rides the cross-prompt prefix cache just like
        # a single request (matched pages never reach the tail page, so the
        # COW fork below is untouched).
        cached: List[int] = []
        if self.prefix_cache is not None and plen > 1:
            cached = self.prefix_cache.match(prompt[:plen - 1])
        pages = cached + self._alloc(full + priv - len(cached))
        self._set_table_row(leader, pages)
        self._slot_pages[leader] = pages
        lst = _SlotState(request_id=request_ids[0], prompt=prompt,
                         tokens=[], logprobs=[], remaining=max_new_tokens,
                         prefill_done=len(cached) * self.page_size,
                         content_prefix=prompt, epoch=self._weight_epoch)
        self.slots[leader] = lst
        self.req_to_slot[request_ids[0]] = leader

        for rid in request_ids[1:]:
            slot = self._free_slot_id()
            self._slot_pages[slot] = self._alloc(priv)
            self.slots[slot] = _SlotState(
                request_id=rid, prompt=prompt, tokens=[], logprobs=[],
                remaining=max_new_tokens, phase=_FORKWAIT, group_leader=leader,
                content_prefix=prompt, epoch=self._weight_epoch)
            self.req_to_slot[rid] = slot
            lst.followers.append(slot)

    def _fork_followers(self, leader: int, chunk_logits,
                        first_tok: int, first_lp: float) -> None:
        """The COW fork: leader finished prefilling, so alias the prompt's
        fully-filled pages into every follower and copy only the partial
        tail page (one batched device copy).  Each follower samples its own
        first token from the final prefill logits (greedy reuses the
        leader's — bit-identical by construction)."""
        st = self.slots[leader]
        plen = len(st.prompt)
        srcs: List[int] = []
        dsts: List[int] = []
        for fslot in st.followers:
            fst = self.slots[fslot]
            shared, tail_src = self.pool.fork_prefix(
                self._slot_pages[leader], plen)
            priv = self._slot_pages[fslot]
            if tail_src is not None:
                srcs.append(tail_src)
                dsts.append(priv[0])
            pages = shared + priv
            self._slot_pages[fslot] = pages
            self._set_table_row(fslot, pages)
            if self.temperature <= 0.0:
                t0, l0 = first_tok, first_lp
            else:
                self._key, sub = jax.random.split(self._key)
                ftok, flp = sample_tokens(sub, chunk_logits,
                                          temperature=self.temperature,
                                          top_k=self.top_k)
                t0, l0 = int(ftok[0]), float(flp[0])
            fst.phase = _DECODE
            fst.group_leader = None
            fst.tokens.append(t0)
            fst.logprobs.append(l0)
            fst.remaining -= 1
            fst.prefill_done = plen
            self.cur_token = self.cur_token.at[fslot].set(t0)
            self.pos = self.pos.at[fslot].set(plen)
        st.followers = []
        self.total_groups_forked += 1
        if srcs:
            self.cache = self._copy_pages(self.cache, jnp.asarray(srcs),
                                          jnp.asarray(dsts))

    def _promote_follower(self, st: _SlotState, leader_pages: List[int]) -> None:
        """The group's prefill leader was aborted before the fork: hand its
        full page allocation (prefilled content intact) to the first waiting
        follower, which becomes the new leader and continues the chunked
        prefill where the old one stopped — no prompt work is repeated."""
        new_leader = st.followers[0]
        nst = self.slots[new_leader]
        self.pool.release(self._slot_pages[new_leader])
        self._slot_pages[new_leader] = leader_pages
        self._set_table_row(new_leader, leader_pages)
        nst.phase = _PREFILL
        nst.group_leader = None
        nst.prefill_done = st.prefill_done
        nst.followers = st.followers[1:]
        for f in nst.followers:
            self.slots[f].group_leader = new_leader

    # ------------------------------------------ content-addressed release
    def _written_content(self, st: _SlotState, slot: int):
        """(token content, written length) of the slot's written KV region.

        Decode phase: ``content_prefix`` + sampled tokens, of which the
        final sampled token's KV is not yet written (written == pos).
        Prefill phase: the prompt up to ``prefill_done``."""
        if st.phase == _DECODE:
            content = np.concatenate(
                [st.content_prefix, np.asarray(st.tokens, np.int32)])
            return content, int(self.pos[slot])
        if st.phase == _PREFILL:
            return st.content_prefix, st.prefill_done
        return st.content_prefix, 0          # forkwait: nothing written yet

    def _release_pages(self, pages: List[int], content, written: int,
                       epoch: int) -> None:
        """Release a request's pages — but first index every fully-written
        page in the prefix cache (the cache takes its own reference, so the
        KV survives this release for future cross-prompt hits).  Pages whose
        KV predates the current weight epoch are NOT published: a
        post-weight-sync abort must not repopulate the flushed cache with
        old-policy KV."""
        if (self.prefix_cache is not None and written >= self.page_size
                and epoch == self._weight_epoch):
            full = written // self.page_size
            self.prefix_cache.insert(content[:full * self.page_size],
                                     pages[:full])
        self.pool.release(pages)

    def peek_tokens(self, request_id: int, start: int = 0) -> List[int]:
        """Decoded tokens[start:] of an active request (streaming hook)."""
        slot = self.req_to_slot.get(request_id)
        if slot is None:
            return []
        return list(self.slots[slot].tokens[start:])

    # --------------------------------------------------- retain / resume
    def abort(self, request_id: int, *, retain: bool = False) -> GenerationResult:
        slot = self.req_to_slot.pop(request_id)
        st = self.slots.pop(slot)
        pages = self._slot_pages.pop(slot)
        self.block_tables = self.block_tables.at[slot].set(-1)
        if st.phase == _FORKWAIT:
            # pre-fork follower: it has no KV yet — nothing to retain.
            leader = self.slots.get(st.group_leader)
            if leader is not None and slot in leader.followers:
                leader.followers.remove(slot)
            self.pool.release(pages)
            retain = False
        elif st.followers:
            # pre-fork group leader: its pages must keep serving the group
            # (the promoted follower continues the prefill in-place), so
            # there is nothing left to park — degrade retain to a plain
            # abort.  Zero tokens have been decoded at this point, so the
            # caller loses only partial prompt prefill.
            self._promote_follower(st, pages)
            retain = False
        elif retain:
            content, length = self._written_content(st, slot)
            self.retained[request_id] = _Retained(
                pages=pages, phase=st.phase, prompt=st.prompt,
                prefill_done=st.prefill_done,
                length=length if st.phase == _DECODE else 0,
                last_token=int(self.cur_token[slot]), content=content,
                epoch=st.epoch)
        else:
            content, written = self._written_content(st, slot)
            self._release_pages(pages, content, written, st.epoch)
        return GenerationResult(
            request_id=request_id, task=None,
            tokens=np.asarray(st.tokens, np.int32),
            logprobs=np.asarray(st.logprobs, np.float32),
            version_started=-1, aborted=True, partial=True, resumable=retain)

    def _resume_pages_needed(self, ret: _Retained, max_new_tokens: int) -> int:
        base = ret.length if ret.phase == _DECODE else len(ret.prompt)
        return self._pages_needed(base + max_new_tokens)

    def can_resume(self, request_id: int, max_new_tokens: int) -> bool:
        ret = self.retained.get(request_id)
        if ret is None or self.num_free_slots == 0:
            return False
        extra = self._resume_pages_needed(ret, max_new_tokens) - len(ret.pages)
        return extra <= 0 or self._can_cover(extra)

    def resume_request(self, request_id: int, new_request_id: int,
                       max_new_tokens: int) -> None:
        """Re-attach a retained request: its pages (the whole decoded prefix's
        KV) come back verbatim — zero prefix recomputation.  A budget larger
        than the original allocation tops the table up from the free pool
        (both phases: a prefill-phase resume still needs decode headroom).
        A forked lane's shared prefix pages re-attach through the refcounts
        its retained record kept holding — siblings finishing or aborting in
        the meantime never invalidates them."""
        ret = self.retained.pop(request_id)
        assert self.num_free_slots > 0, "no free slot"
        base = ret.length if ret.phase == _DECODE else len(ret.prompt)
        assert base + max_new_tokens <= self.max_total_len, "sequence budget"
        slot = self._free_slot_id()
        pages = ret.pages
        need = self._resume_pages_needed(ret, max_new_tokens)
        if need > len(pages):
            pages = pages + self._alloc(need - len(pages))
        self._set_table_row(slot, pages)
        self._slot_pages[slot] = pages
        st = _SlotState(request_id=new_request_id, prompt=ret.prompt,
                        tokens=[], logprobs=[], remaining=max_new_tokens,
                        phase=ret.phase, prefill_done=ret.prefill_done,
                        carried_last=(ret.last_token if ret.phase == _DECODE
                                      else None),
                        content_prefix=(ret.content if ret.content is not None
                                        else ret.prompt),
                        epoch=ret.epoch)
        self.slots[slot] = st
        self.req_to_slot[new_request_id] = slot
        if ret.phase == _DECODE:
            self.cur_token = self.cur_token.at[slot].set(ret.last_token)
            self.pos = self.pos.at[slot].set(ret.length)

    def release_retained(self, request_id: int) -> None:
        ret = self.retained.pop(request_id, None)
        if ret is not None:
            written = ret.length if ret.phase == _DECODE else ret.prefill_done
            content = ret.content if ret.content is not None else ret.prompt
            self._release_pages(ret.pages, content, written, ret.epoch)

    # ------------------------------------------- cross-replica page transfer
    def export_retained(self, request_id: int) -> Optional[dict]:
        """Extract a retained request's pages into a host-side record another
        replica can ``import_retained``.  One batched gather + one device_get
        — no per-page dispatch.  The local record is NOT released: the caller
        releases it only after the import landed, so a failed transfer leaves
        in-place resume intact."""
        ret = self.retained.get(request_id)
        if ret is None:
            return None
        t = paged.export_pages(self.cache, ret.pages)
        self.pages_transferred_out += t.num_pages
        self.transfer_bytes_out += t.nbytes
        self.transfer_device_ops += 1
        return {
            "transfer": t, "phase": ret.phase, "prompt": ret.prompt,
            "prefill_done": ret.prefill_done, "length": ret.length,
            "last_token": ret.last_token, "content": ret.content,
            "epoch": ret.epoch, "home_epoch": self._weight_epoch,
            "kv_quant": self.kv_quant,
        }

    def import_retained(self, request_id: int, record: dict) -> bool:
        """Re-admit an exported retained record into THIS replica's pool via
        one batched scatter, recreating the ``retained`` entry so the normal
        ``can_resume``/``resume_request`` path picks it up — the migrated
        request resumes with zero re-prefill.  Returns False (and imports
        nothing) when the record can't land here: quant-mode mismatch, rid
        collision, or the pool can't cover the pages."""
        t: paged.PageTransfer = record["transfer"]
        if (record.get("kv_quant", "off") != self.kv_quant
                or request_id in self.retained
                or not self._can_cover(t.num_pages)):
            return False
        pages = self._alloc(t.num_pages)
        self.cache = self._import_pages(
            self.cache, jnp.asarray(pages, jnp.int32), t)
        self.pages_transferred_in += t.num_pages
        self.transfer_bytes_in += t.nbytes
        self.transfer_device_ops += 1
        # Epoch translation: the KV is current-policy only if it was current
        # at home AND home and here sit at the same weight epoch.  A stale
        # stamp (never equal to a future epoch) keeps old-policy KV out of
        # the prefix cache on release — it never affects decode itself, so
        # greedy byte-identity is preserved either way.
        current = (record["epoch"] == record["home_epoch"]
                   and record["home_epoch"] == self._weight_epoch)
        self.retained[request_id] = _Retained(
            pages=pages, phase=record["phase"], prompt=record["prompt"],
            prefill_done=record["prefill_done"], length=record["length"],
            last_token=record["last_token"], content=record["content"],
            epoch=self._weight_epoch if current else self._weight_epoch - 1)
        return True

    def export_prefix(self, tokens) -> Optional[dict]:
        """Extract this replica's cached prefix pages for ``tokens`` into a
        host-side record (for a router-directed pull to another replica).
        Like admission, the match is capped at ``len(tokens) - 1`` — the
        final prompt token always prefills to produce first logits."""
        if self.prefix_cache is None or len(tokens) < 2:
            return None
        tokens = np.asarray(tokens, np.int32).ravel()
        path = self.prefix_cache._walk(tokens[:len(tokens) - 1])
        if not path:
            return None
        pages = [n.page for n in path]
        t = paged.export_pages(self.cache, pages)
        self.pages_transferred_out += t.num_pages
        self.transfer_bytes_out += t.nbytes
        self.transfer_device_ops += 1
        covered = tokens[:len(pages) * self.page_size].copy()
        return {"transfer": t, "tokens": covered,
                "home_epoch": self._weight_epoch, "kv_quant": self.kv_quant}

    def import_prefix(self, record: dict) -> int:
        """Admit a pulled prefix record into this replica's radix cache so an
        incoming request prefills only its uncached tail.  Conservative by
        design: a pull never evicts (plain free-page check), never imports
        cross-epoch KV, and dedups against pages already cached here.
        Returns the number of pages imported (0 = skipped, perf-only)."""
        if (self.prefix_cache is None
                or record.get("kv_quant", "off") != self.kv_quant
                or record["home_epoch"] != self._weight_epoch):
            return 0
        t: paged.PageTransfer = record["transfer"]
        tokens = record["tokens"]
        have_nodes = self.prefix_cache._walk(tokens)
        have = len(have_nodes)
        if have >= t.num_pages:
            return 0
        need = t.num_pages - have
        if need > self.pool.pages_free:
            return 0
        sub = paged.PageTransfer(
            k=t.k[:, have:], v=t.v[:, have:],
            k_scales=None if t.k_scales is None else t.k_scales[:, have:],
            v_scales=None if t.v_scales is None else t.v_scales[:, have:])
        pages = self._alloc(need)
        self.cache = self._import_pages(
            self.cache, jnp.asarray(pages, jnp.int32), sub)
        self.pages_transferred_in += need
        self.transfer_bytes_in += sub.nbytes
        self.transfer_device_ops += 1
        # insert() takes the cache's own ref on each new page: the shared
        # prefix [0, have) dedups onto existing nodes and only the tail
        # binds the freshly imported pages.
        full = [n.page for n in have_nodes] + pages
        self.prefix_cache.insert(tokens, full)
        self.pool.release(pages)
        return need

    # ------------------------------------------------------------ auditing
    def audit_pages(self) -> None:
        """Assert the refcount invariant: every page's refcount equals its
        number of appearances across live block tables, retained records and
        prefix-cache holds, and a page is free exactly when its refcount is
        zero."""
        expect = np.zeros((self.num_pages,), np.int64)
        for pages in self._slot_pages.values():
            for p in pages:
                expect[p] += 1
        for ret in self.retained.values():
            for p in ret.pages:
                expect[p] += 1
        if self.prefix_cache is not None:
            for p in self.prefix_cache.held_pages():
                expect[p] += 1
        actual = np.asarray([self.pool.refcount(p)
                             for p in range(self.num_pages)], np.int64)
        assert (expect == actual).all(), \
            f"refcount leak: expected {expect.tolist()} got {actual.tolist()}"
        free = set(self.pool._free)
        assert paged.GARBAGE_PAGE not in free
        for p in range(1, self.num_pages):
            assert (p in free) == (actual[p] == 0), \
                f"page {p}: refcount {actual[p]} vs free={p in free}"

    # --------------------------------------------------------------- step
    def step(self) -> List[Tuple[int, np.ndarray, np.ndarray]]:
        """One fused engine step; returns finished (rid, tokens, logprobs).

        Its host phases are program spans (``repro.core.tracing``):
        ``roll.engine.finish``, ``.prepare``, ``.dispatch``, ``.readback``
        (the host waits for the device) and ``.update``."""
        if not self.slots:
            return []
        finished: List[Tuple[int, np.ndarray, np.ndarray]] = []
        # finish BEFORE stepping: the last sampled (or carried) token may
        # already terminate the request.
        with tracing.span("roll.engine.finish"):
            for slot in list(self.slots):
                st = self.slots[slot]
                if st.phase != _DECODE:
                    continue
                last = st.tokens[-1] if st.tokens else st.carried_last
                if last is not None and (last == self.eos_id
                                         or st.remaining <= 0):
                    finished.append(self._finish(slot))
        if not self.slots:
            return finished

        with tracing.span("roll.engine.prepare"):
            prefill_slots = [s for s, st in sorted(self.slots.items())
                             if st.phase == _PREFILL]
            decode_slots = [s for s, st in self.slots.items()
                            if st.phase == _DECODE]

            c = self.prefill_chunk
            chunk_slot = None
            n_chunk = 0
            prefill_ends = False
            toks = np.full((1, c), self.pad_id, np.int32)
            valid = np.zeros((1, c), bool)
            start = 0
            row = jnp.full((self.pages_per_seq,), -1, jnp.int32)
            if prefill_slots:
                chunk_slot = prefill_slots[self._rr % len(prefill_slots)]
                self._rr += 1
                st = self.slots[chunk_slot]
                if self.prefix_cache is not None:
                    self._extend_cached_prefix(chunk_slot, st)
                start = st.prefill_done
                chunk = st.prompt[start:start + c]
                n_chunk = len(chunk)
                prefill_ends = start + n_chunk >= len(st.prompt)
                toks[0, :n_chunk] = chunk
                valid[0, :n_chunk] = True
                row = self.block_tables[chunk_slot]

            decode_mask = np.zeros((self.num_slots,), bool)
            decode_mask[decode_slots] = True
            mask_j = jnp.asarray(decode_mask)
            masked_tables = jnp.where(mask_j[:, None], self.block_tables, -1)

            self._key, sub = jax.random.split(self._key)

        with tracing.span("roll.engine.dispatch"):
            ptok, plp, dtok, dlp, chunk_logits, self.cache = self._step(
                self.params, self.cache, self.cur_token, self.pos,
                masked_tables, jnp.asarray(toks), jnp.asarray(valid),
                jnp.asarray(start, jnp.int32), row,
                np.bool_(chunk_slot is not None), np.bool_(bool(decode_slots)),
                sub)

        # the host waits here for the device to finish the step
        with tracing.span("roll.engine.readback"):
            if prefill_ends:
                t0, l0 = int(ptok[0]), float(plp[0])
            if decode_slots:
                tok_np, lp_np = np.asarray(dtok), np.asarray(dlp)

        with tracing.span("roll.engine.update"):
            if chunk_slot is not None:
                st = self.slots[chunk_slot]
                st.prefill_done += n_chunk
                self.total_prefill_chunks += 1
                self.total_prefill_tokens += n_chunk
                if (self.prefix_cache is not None
                        and st.epoch == self._weight_epoch):
                    # publish freshly completed prompt pages immediately so
                    # CONCURRENT same-prefix requests pick them up
                    # mid-prefill (lazy extension above) — the shared
                    # preamble of a batch prefills exactly once even when
                    # everything is admitted together.
                    full = st.prefill_done // self.page_size
                    if full:
                        self.prefix_cache.insert(
                            st.prompt[:full * self.page_size],
                            self._slot_pages[chunk_slot][:full])
                if prefill_ends:
                    st.phase = _DECODE
                    st.tokens.append(t0)
                    st.logprobs.append(l0)
                    st.remaining -= 1
                    self.cur_token = self.cur_token.at[chunk_slot].set(t0)
                    self.pos = self.pos.at[chunk_slot].set(len(st.prompt))
                    if st.followers:
                        self._fork_followers(chunk_slot, chunk_logits, t0, l0)

            if decode_slots:
                self.total_decode_steps += 1
                self.cur_token = jnp.where(mask_j, dtok, self.cur_token)
                self.pos = jnp.where(mask_j, self.pos + 1, self.pos)
                for s in decode_slots:
                    st = self.slots[s]
                    # the context the kernel attended: every written
                    # position and this step's, pos + 1
                    context = len(st.content_prefix) + len(st.tokens)
                    self.total_decode_context += context
                    self.total_decode_pages += -(-context // self.page_size)
                    st.tokens.append(int(tok_np[s]))
                    st.logprobs.append(float(lp_np[s]))
                    st.remaining -= 1
                    self.total_tokens_decoded += 1
        return finished

    def _extend_cached_prefix(self, slot: int, st: _SlotState) -> None:
        """Mid-prefill cache extension: when a prefilling slot sits at a page
        boundary and the cache meanwhile learned a longer prefix of its
        prompt (e.g. a concurrent request prefilled the shared preamble
        first), swap the slot's unwritten pages for the cached ones and jump
        ``prefill_done`` forward.  The swapped-out pages were never written,
        so this is pure block-table/refcount bookkeeping."""
        if st.prefill_done % self.page_size:
            return                       # mid-page: cannot swap whole pages
        plen = len(st.prompt)
        j = st.prefill_done // self.page_size
        ext = self.prefix_cache.match(st.prompt[:plen - 1], from_page=j,
                                      extend=True)
        if not ext:
            return
        pages = self._slot_pages[slot]
        k = j + len(ext)
        swapped_out = pages[j:k]
        pages[j:k] = ext
        self.pool.release(swapped_out)
        self._set_table_row(slot, pages)
        st.prefill_done = k * self.page_size

    def _finish(self, slot: int) -> Tuple[int, np.ndarray, np.ndarray]:
        st = self.slots.pop(slot)
        self.req_to_slot.pop(st.request_id, None)
        content, written = self._written_content(st, slot)
        self._release_pages(self._slot_pages.pop(slot), content, written,
                            st.epoch)
        self.block_tables = self.block_tables.at[slot].set(-1)
        return (st.request_id, np.asarray(st.tokens, np.int32),
                np.asarray(st.logprobs, np.float32))
