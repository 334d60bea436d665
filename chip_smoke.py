#!/usr/bin/env python3
"""Chip smoke test: the RLVR main path on TPU at qwen3-4b's published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four rollout replicas, one per chip

One chip, in one process:
  1. Backend check: JAX must find a TPU.  There is no CPU fallback.
  2. Logits comparison: prefill, then decode through the paged KV cache
     with the Pallas kernel, in bf16 and with int8 KV pages, against the
     model's plain float32 forward (``api.apply``) on the same seeded
     weights.  Two negative controls (another sequence's pages; int8 codes
     read without their scales) must land outside the tolerances.
  3. Train run: ``build_rlvr_pipeline`` (pipeline -> proxy -> paged engine
     -> trainer), GRPO groups of 8 on ArithmeticTask prompts, 16 slots,
     256 new tokens per sample, 3 train steps with overlapped weight sync.

``--chips 4`` runs only a four-replica version of phase 3, each replica on
its own chip, and then phase 2 once per replica on the weights the last
sync left on that replica's chip.

qwen3-4b keeps every published width (d_model 2560, 32 query / 8 KV heads
of 128, d_ff 9728, the tied 151,936-row vocabulary); only the depth is cut,
to ``DEPTH`` layers.  Weights are random, from ``SEED``.  Earlier lines of
output are information: depth, parameter count, compile seconds, step
times, peak device memory, each error beside its tolerance.  The last line
is the result, printed only when every phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure, including an exception on the proxy or producer thread, exits
non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import threading
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import REGISTRY  # noqa: E402
from repro.data.dataset import ArithmeticTask  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.pipeline import (PipelineSettings,  # noqa: E402
                                   build_rlvr_pipeline)
from repro.models import get_api  # noqa: E402
from repro.rewards.verifier import ArithmeticVerifier  # noqa: E402

SEED = 0
# Depth kept: the most layers one 16 GB v5e holds with the trainer and one
# rollout replica.  The train step compiled for a described v5e (remat on,
# optimizer state donated, 16 x 320 tokens) needs 11.16 GB at 2 layers,
# 12.83 GB at 3, 14.50 GB at 4 and 15.63 GB at 5, of the 15.75 GB the
# compiler allows; the replica's page pool and engine step add another
# ~0.1 GB per 4-5 layers, which 5 layers no longer leave.
DEPTH = 4

# Logits comparison shapes: 16 sequences (the engine's slot count), a
# 40-token prompt (2.5 pages) prefilled in 16-token chunks, then 24
# teacher-forced decode steps across the 3rd and 4th page boundaries.
COMPARE_BATCH, PROMPT_LEN, TOTAL_LEN = 16, 40, 64
PAGE_SIZE, PREFILL_CHUNK = 16, 16

# Tolerances on the relative error of a logits row, ||x - ref|| / ||ref||,
# worst row.  The reference is float32 at "highest" matmul precision; the
# system computes in bf16 (8-bit mantissa: relative rounding 2^-9 per
# operation, compounded over the layers, the residual adds and the
# 151,936-wide unembedding).  On a v5e at 3 and at 4 layers the plain bf16
# forward (api.apply, printed beside the paged errors as the floor) sat
# 0.0115 from fp32, the paged path 0.0111-0.0112, and the paged path over
# int8 KV pages (8-bit codes per token and kv-head: up to 1/254 relative
# error per element) 0.0199-0.0203.
# - bf16: 0.02, under 2x the floor.  fp8 activations (3-bit mantissa, 16x
#   bf16's rounding) land far above it.
# - int8 KV: 0.03, 1.5x what was measured.
# An off-by-one in the kernel's length mask (one unwritten slot attended)
# added ~0.013 at reduced width on the CPU, enough to cross both.
# Attending through another sequence's pages, or reading int8 codes
# without their scales, gave 1.2-1.4 on the chip; each run checks that
# both land above the tolerance rather than assuming it.
TOL_BF16 = 0.02
TOL_INT8 = 0.03

# Train run: GRPO groups of 8, 16 samples a step, 16 decode slots per chip.
GROUP, BATCH, SLOTS = 8, 16, 16
MAX_NEW_TOKENS, MAX_SEQ_LEN = 256, 320
TRAIN_STEPS = 3
BATCH_TIMEOUT_S = 300.0    # per get_batch; the first includes compiles


def qwen3_4b(depth: int = DEPTH):
    return dataclasses.replace(REGISTRY["qwen3-4b"], num_layers=depth)


def param_count(params) -> int:
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(params))


class CompileClock:
    """Sums the backend compile seconds JAX reports while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


class ThreadFailures:
    """Records an exception raised on any other thread (the proxy loop,
    the producer) and closes the sample buffer, so the controller's
    ``get_batch`` fails at once instead of waiting out its timeout."""

    def __init__(self, buffer):
        self.buffer = buffer
        self.errors = []
        self._prev = None

    def _hook(self, args):
        self.errors.append(f"{args.thread.name if args.thread else '?'}: "
                           f"{args.exc_type.__name__}: {args.exc_value}")
        traceback.print_exception(args.exc_type, args.exc_value,
                                  args.exc_traceback)
        self.buffer.close()

    def __enter__(self):
        self._prev = threading.excepthook
        threading.excepthook = self._hook
        return self

    def __exit__(self, *exc):
        threading.excepthook = self._prev
        return False


class TieBreakReward:
    """The verifier's reward plus a tie-break in [0, 0.01), seeded by the
    sample's id.  A random-weight policy never answers an arithmetic
    prompt right, so every verifier reward is 0: GRPO's group-normalized
    advantages, and with them every gradient, would be exactly 0.  The
    tie-break keeps the train step's update non-trivial."""

    def __init__(self, verifier):
        self.verifier = verifier

    def __call__(self, sample) -> float:
        noise = np.random.default_rng([SEED, sample.sample_id]).random()
        return self.verifier(sample) + 0.01 * float(noise)


# --------------------------------------------------------------------------
# logits comparison
# --------------------------------------------------------------------------

def compare_tokens(vocab: int, seed: int = SEED) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (COMPARE_BATCH, TOTAL_LEN), dtype=np.int32)


def reference_logits(cfg, params, tokens):
    """The model's plain forward in float32 at "highest" matmul precision:
    (B, T, V) logits from the same weights upcast (exactly) to fp32."""
    ref_api = get_api(dataclasses.replace(cfg, dtype="float32"))
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(ref_api.apply)(p32, {"tokens": jnp.asarray(tokens)})
    return logits


@jax.jit
def _rel_err(x, ref):
    """Worst row of ||x - ref|| / ||ref|| over the last axis."""
    x = x.astype(jnp.float32).reshape(-1, ref.shape[-1])
    ref = ref.astype(jnp.float32).reshape(-1, ref.shape[-1])
    num = jnp.linalg.norm(x - ref, axis=-1)
    return (num / jnp.linalg.norm(ref, axis=-1)).max()


def rel_err(x, ref) -> float:
    return float(_rel_err(jax.device_put(x, ref.sharding), ref))


def bf16_forward_err(cfg, params, tokens, ref) -> float:
    """Error of the plain forward at the configuration's own dtype — the
    floor any bf16 path sits on."""
    logits, _ = jax.jit(get_api(cfg).apply)(params, {"tokens": jnp.asarray(tokens)})
    return rel_err(logits, ref)


def paged_logits_errors(cfg, params, tokens, ref, *, kv_quant: str,
                        attn_impl: str = "kernel", device=None) -> dict:
    """Prefill ``tokens[:, :PROMPT_LEN]`` chunk by chunk into a paged pool
    whose pages are scattered at random, then decode the remaining tokens
    teacher-forced through the paged cache with ``attn_impl``.  Returns
    the worst relative errors against ``ref`` (B, T, V) of the prefill's
    last-position logits and of every decode step, plus the negative
    controls, each run for one decode step from the post-prefill pool."""
    api = get_api(cfg)
    b, t = tokens.shape
    device = device or jax.devices()[0]

    def put(x):
        return jax.device_put(x, device)

    per_seq = -(-t // PAGE_SIZE)
    num_pages = 1 + b * per_seq
    rng = np.random.default_rng(SEED + 1)
    tables = (1 + rng.permutation(num_pages - 1)).reshape(b, per_seq)
    tables = tables.astype(np.int32)
    params = put(params)
    cache = put(api.init_paged_cache(num_pages, PAGE_SIZE, kv_quant=kv_quant))
    prefill = jax.jit(api.prefill_chunk, donate_argnums=(5,))
    decode = jax.jit(functools.partial(api.decode_paged, attn_impl=attn_impl),
                     donate_argnums=(3,))

    last = []
    for i in range(b):
        for start in range(0, PROMPT_LEN, PREFILL_CHUNK):
            n = min(PREFILL_CHUNK, PROMPT_LEN - start)
            toks = np.zeros((1, PREFILL_CHUNK), np.int32)
            toks[0, :n] = tokens[i, start:start + n]
            valid = np.zeros((1, PREFILL_CHUNK), bool)
            valid[0, :n] = True
            logits, cache = prefill(params, put(toks), put(valid),
                                    put(np.int32(start)), put(tables[i]), cache)
        last.append(logits)
    out = {"prefill": rel_err(jnp.concatenate(last), ref[:, PROMPT_LEN - 1])}

    snapshot = jax.tree_util.tree_map(jnp.copy, cache)

    def step(pool, pos, tabs):
        return decode(params, put(tokens[:, pos]),
                      put(np.full((b,), pos, np.int32)), pool, put(tabs))

    worst = 0.0
    for pos in range(PROMPT_LEN, t):
        logits, cache = step(cache, pos, tables)
        worst = max(worst, rel_err(logits, ref[:, pos]))
    out["decode"] = worst

    # negative controls: what the tolerance must reject
    logits, _ = step(jax.tree_util.tree_map(jnp.copy, snapshot), PROMPT_LEN,
                     np.roll(tables, 1, axis=0))
    out["control_wrong_pages"] = rel_err(logits, ref[:, PROMPT_LEN])
    if kv_quant == "int8":
        unscaled = snapshot._replace(k_scales=jnp.ones_like(snapshot.k_scales),
                                     v_scales=jnp.ones_like(snapshot.v_scales))
        logits, _ = step(unscaled, PROMPT_LEN, tables)
        out["control_no_scales"] = rel_err(logits, ref[:, PROMPT_LEN])
    return out


def check_logits(name: str, errs: dict, tol: float, failures: list) -> None:
    for key in ("prefill", "decode"):
        ok = errs[key] <= tol
        print(f"[logits] {name} {key}: rel_err={errs[key]!r} tol={tol} "
              f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"logits {name} {key} {errs[key]!r} > {tol}")
    for key in ("control_wrong_pages", "control_no_scales"):
        if key in errs:
            ok = errs[key] > tol
            print(f"[logits] {name} {key}: rel_err={errs[key]!r} must exceed "
                  f"tol={tol} {'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(f"logits {name} {key} {errs[key]!r} <= {tol}: "
                                "the tolerance cannot tell this fault apart")


def check_paged_paths(cfg, params, tokens, ref, failures: list, *,
                      name: str = "paged", attn_impl: str = "kernel",
                      device=None) -> None:
    """The paged comparison over bf16 pages, then int8 pages."""
    for kv_quant, tol in (("off", TOL_BF16), ("int8", TOL_INT8)):
        errs = paged_logits_errors(cfg, params, tokens, ref, kv_quant=kv_quant,
                                   attn_impl=attn_impl, device=device)
        check_logits(f"{name} kv={kv_quant}", errs, tol, failures)


def logits_phase(cfg, params, failures: list, *,
                 attn_impl: str = "kernel") -> None:
    tokens = compare_tokens(cfg.vocab_size)
    ref = reference_logits(cfg, params, tokens)
    print(f"[logits] plain bf16 forward vs fp32: "
          f"rel_err={bf16_forward_err(cfg, params, tokens, ref)!r}")
    check_paged_paths(cfg, params, tokens, ref, failures, attn_impl=attn_impl)


# --------------------------------------------------------------------------
# train run
# --------------------------------------------------------------------------

def train_run(cfg, failures: list, *, replicas: int = 1,
              attn_impl=None, expect_attn: str = "kernel",
              steps: int = TRAIN_STEPS, max_new_tokens: int = MAX_NEW_TOKENS,
              max_seq_len: int = MAX_SEQ_LEN):
    """Build and run the RLVR pipeline, appending every failed check to
    ``failures``; returns the pipeline, shut down, for per-replica checks."""
    settings = PipelineSettings(
        async_generation_ratio=1.0, rollout_batch_size=BATCH,
        num_return_sequences_in_group=GROUP, num_slots=SLOTS * replicas,
        num_rollout_replicas=replicas, max_new_tokens=max_new_tokens,
        max_seq_len=max_seq_len, page_size=PAGE_SIZE,
        prefill_chunk=PREFILL_CHUNK, learning_rate=1e-5, seed=SEED,
        weight_sync="overlapped", attn_impl=attn_impl)
    task = ArithmeticTask(seed=SEED)
    pipe = build_rlvr_pipeline(cfg, settings, task=task,
                               reward_fn=TieBreakReward(ArithmeticVerifier(task)))
    n_params = param_count(pipe.trainer.get_weights())
    print(f"[train] depth={cfg.num_layers} params={n_params} "
          f"replicas={replicas} slots/replica={pipe.engines[0].num_slots} "
          f"group={GROUP} batch={BATCH} new_tokens={max_new_tokens} "
          f"max_seq_len={max_seq_len} steps={steps}")
    for i, e in enumerate(pipe.engines):
        if e.attn_impl != expect_attn:
            failures.append(f"replica {i} attn_impl={e.attn_impl!r}, "
                            f"expected {expect_attn!r}")
    initial_scale = np.asarray(pipe.trainer.get_weights()["final_norm"]["scale"])

    with CompileClock() as clock, ThreadFailures(pipe.buffer) as threads:
        t0 = time.monotonic()
        try:
            stats = pipe.run(steps, timeout=BATCH_TIMEOUT_S)
        except Exception:
            traceback.print_exc()
            failures.append("train run raised: "
                            + traceback.format_exc().splitlines()[-1])
            stats = []
        wall = time.monotonic() - t0
    print(f"[train] wall={wall!r}s compile_seconds={clock.seconds!r}")
    for s, h in zip(stats, pipe.trainer.history, strict=False):
        print(f"[train] step {s.step}: wait={s.wait_time!r}s "
              f"train={s.train_time!r}s sync={s.sync_time!r}s "
              f"loss={s.loss!r} grad_norm={h.get('grad_norm')!r} "
              f"reward_mean={s.reward_mean!r} staleness_max={s.staleness_max}")
    failures.extend(f"thread failed: {e}" for e in threads.errors)
    if pipe.producer.is_alive():
        failures.append("producer thread still alive after shutdown")
    if len(stats) != steps:
        failures.append(f"{len(stats)} of {steps} train steps completed")
        return pipe
    if not all(math.isfinite(s.loss) for s in stats):
        failures.append(f"non-finite loss: {[s.loss for s in stats]}")
    norms = [h.get("grad_norm", float("nan")) for h in pipe.trainer.history]
    if not all(math.isfinite(g) and g > 0 for g in norms):
        failures.append(f"grad norms not finite and positive: {norms}")

    # weight sync: every replica holds the trainer's current weights, which
    # moved off their initial values
    now = np.asarray(pipe.trainer.get_weights()["final_norm"]["scale"])
    if np.array_equal(now, initial_scale):
        failures.append("trainer weights never changed")
    for i, (e, p) in enumerate(zip(pipe.engines, pipe.proxies, strict=True)):
        held = np.asarray(e.params["final_norm"]["scale"])
        if not np.array_equal(held, now):
            failures.append(f"replica {i} does not hold the synced weights")
        if p.staged_weight_updates < steps:
            failures.append(f"replica {i} applied {p.staged_weight_updates} "
                            f"of {steps} weight syncs")

    buf = pipe.buffer
    left = buf.total_produced - buf.total_consumed - buf.total_evicted
    print(f"[train] samples produced={buf.total_produced} "
          f"consumed={buf.total_consumed} evicted={buf.total_evicted}")
    if buf.total_consumed != steps * BATCH or not 0 <= left <= buf.capacity:
        failures.append(f"sample counts do not add up: produced="
                        f"{buf.total_produced} consumed={buf.total_consumed} "
                        f"evicted={buf.total_evicted} capacity={buf.capacity}")
    return pipe


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    return (f"{device}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def run_one_chip(failures: list) -> None:
    cfg = qwen3_4b()
    with CompileClock() as clock:
        params = get_api(cfg).init(jax.random.PRNGKey(SEED))
        print(f"[logits] depth={cfg.num_layers} params={param_count(params)}")
        try:
            logits_phase(cfg, params, failures)
        except Exception:
            traceback.print_exc()
            failures.append("logits phase raised: "
                            + traceback.format_exc().splitlines()[-1])
    print(f"[logits] compile_seconds={clock.seconds!r}")
    del params
    gc.collect()
    train_run(cfg, failures)
    print(f"[memory] {memory_line(jax.devices()[0])}")


def run_four_chips(failures: list) -> None:
    cfg = qwen3_4b()
    devices = jax.devices()[:4]
    pipe = train_run(cfg, failures, replicas=4)
    for i, e in enumerate(pipe.engines):
        cache_on, params_on = (sorted(map(str, a.devices()))
                               for a in (e.cache.k_pages, e.params["embed"]))
        print(f"[fleet] replica {i}: cache on {cache_on} params on {params_on}")
        if e.cache.k_pages.devices() != {devices[i]}:
            failures.append(f"replica {i} cache not on {devices[i]}")
        if e.params["embed"].devices() != {devices[i]}:
            failures.append(f"replica {i} params not on {devices[i]}")
    for d in devices:
        print(f"[memory] {memory_line(d)}")
    # per replica: the logits comparison on the weights the last sync left
    # on that replica's chip, against the trainer's weights in float32
    weights = pipe.trainer.get_weights()
    held = [(e.params, e.device) for e in pipe.engines]
    del pipe
    gc.collect()
    tokens = compare_tokens(cfg.vocab_size)
    ref = reference_logits(cfg, weights, tokens)
    del weights
    for i, (params, device) in enumerate(held):
        check_paged_paths(cfg, params, tokens, ref, failures,
                          name=f"replica {i}", device=device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica pipeline (one replica "
                         "per chip) and the per-replica logits comparison")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU: JAX's backend is {backend!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    print(f"[chip] {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)} compile_cache={cache_dir}")

    failures: list = []
    if args.chips == 4:
        run_four_chips(failures)
    else:
        run_one_chip(failures)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
