"""Driver ``rl``: the async RLVR loop, as a deployment runs it.

Pipeline -> producer -> (router ->) proxy -> paged engine with the Pallas
kernel -> trainer, wired as ``repro.launch.pipeline.build_rlvr_pipeline``
wires them, with the benchmark's seeded weights and prompts.

Set-up builds the pipeline once and drives it through its first
``warm_steps`` RL steps (every program compiles there: the engine step,
the train step, the fork copy, the weight sync), recording what the
correctness comparison needs: the first train batch's samples, the loss of
each of the first optimizer steps, each leaf's first gradient as the
optimizer got it, the float32 parameters after the last warm step, and
the weights each rollout engine serves once the last warm step's weight
sync is applied (copied to the host).
The same objects then run the window, one ``AsyncController.train`` step
at a time: it starts at a step boundary and ends at the first step
boundary ``--seconds`` after it.

What the program counts reaches the metric readers in the record: in every
run, each counted sample's queue wait and the window's growth of the
engines' and the trainer's ``total_*`` counters (host integers); in a
traced run, also the program's ``roll.*`` span totals and the device idle
by innermost program span (``benchlib.model.ProgramSpans``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import check, reference, traffic
from benchlib import weights as W
from benchlib.harness import (Outcome, live_bytes, memory_peak, now,
                              start_trace)
from benchlib.model import (CompileClock, KernelLengths, ProgramSpans, Spans,
                            counters, delta)
from repro.algos import LossConfig
from repro.core import tracing
from repro.core.async_controller import AsyncController
from repro.core.sample_buffer import SampleBuffer
from repro.core.scheduler import RolloutProducer
from repro.core.types import Rejected
from repro.launch.pipeline import PipelineSettings, make_rollout_fleet
from repro.models import get_api
from repro.quant import core as quant
from repro.train.optimizer import OptConfig
from repro.train.trainer import HostTrainer, TrainerConfig


class TieBreakReward:
    """A reward in [0, 1) from the response's content and the seed.  A
    random-weight policy answers nothing right, so a verifier's reward
    would be 0 everywhere and every GRPO advantage, and with it every
    gradient, 0."""

    def __init__(self, seed: int):
        self.words = [int(w) for w in traffic.seed_words(seed)]

    def __call__(self, sample) -> float:
        h = zlib.crc32(np.asarray(sample.response_tokens, np.int32).tobytes())
        return float(np.random.default_rng(self.words + [h]).random())


class Recorder:
    """The benchmark's hooks on the trainer: the first batches' samples,
    the optimizer state the comparison reads, and each optimizer step's
    real sequence lengths (for the model-FLOP counts)."""

    def __init__(self, trainer, warm: int, b1: float, roots: tuple):
        self.warm, self.b1, self.roots = warm, b1, roots
        self.batches = []          # the first ``warm`` batches of Samples
        self.opt_steps = 0
        self.first_grad = None     # leaf -> norm, from m after step 1
        self.master = None         # host float32 params after step ``warm``
        self.train_lengths = []    # (t, real lengths) per optimizer step
        self._build = trainer.build_batch
        self._step = trainer._train_step
        trainer.build_batch = self.build_batch
        trainer._train_step = self.train_step

    def build_batch(self, samples):
        if len(self.batches) < self.warm:
            self.batches.append(list(samples))
        return self._build(samples)

    def train_step(self, params, rest, mini):
        state, m = self._step(params, rest, mini)
        self.opt_steps += 1
        mask = np.asarray(mini["mask"])
        lengths = [int(np.nonzero(r)[0][-1]) + 1 if r.any() else 0 for r in mask]
        self.train_lengths.append((now(), lengths))
        if self.opt_steps == 1:
            self.first_grad = {n: float(v) / (1 - self.b1) for n, v in
                               leaf_norms(state["opt"]["m"],
                                          self.roots).items()}
        if self.opt_steps == self.warm:
            self.master = jax.device_get(state["opt"]["master"])
        return state, m


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.linalg.norm(a.reshape(a.shape[0], -1), axis=1)
        if a.ndim > 1 and a.shape[0] > 0 else jnp.linalg.norm(a), tree)


def leaf_norms(tree, roots) -> dict:
    """Per-layer leaf norms on the device, named as
    ``weights.per_layer_leaves`` names the leaves (every stack under
    ``roots`` split per layer); only the norms come back."""
    out = {}
    for name, v, stacked in W.named_leaves(jax.device_get(_norms(tree)), roots):
        if stacked:
            for i, x in enumerate(np.atleast_1d(v)):
                out[f"{name}[{i}]"] = float(x)
        else:
            out[name] = float(np.linalg.norm(v))
    return out


def _serve(ctx):
    """Build, warm and time the program; returns only host data, so its
    device state is gone once this returns."""
    mix, c = ctx.mix, ctx.config
    dims = ctx.arch.dims(c)
    mcfg = ctx.arch.program_config(c)
    spans = Spans()
    g, batch, replicas = mix["group_size"], mix["batch"], mix["replicas"]
    loss_cfg, opt = mix["loss"], mix["optimizer"]

    params = W.make(ctx.arch, dims, mcfg.tie_embeddings, ctx.seed,
                    ctx.devices[0])
    api = dataclasses.replace(get_api(mcfg), init=lambda key: params)
    settings = PipelineSettings(
        async_generation_ratio=mix["alpha"], rollout_batch_size=batch,
        num_return_sequences_in_group=g, num_slots=mix["slots"] * replicas,
        num_rollout_replicas=replicas, minibatches=mix["minibatches"],
        max_new_tokens=mix["new_tokens"]["value"],
        max_seq_len=mix["max_seq_len"], page_size=mix["page_size"],
        prefill_chunk=mix["prefill_chunk"],
        learning_rate=opt["learning_rate"],
        seed=int(traffic.seed_words(ctx.seed)[0] >> 1),
        weight_sync=mix["weight_sync"],
        rollout_quant=ctx.control or "off")
    trainer = HostTrainer(
        api, jax.random.PRNGKey(0),
        LossConfig(pg_variant="ppo", epsilon=loss_cfg["clip_eps"],
                   engine_mismatch_cap=loss_cfg["mismatch_cap"]),
        OptConfig(learning_rate=opt["learning_rate"], b1=opt["b1"],
                  b2=opt["b2"], eps=opt["eps"],
                  weight_decay=opt["weight_decay"],
                  grad_clip=opt["grad_clip"],
                  warmup_steps=opt["warmup_steps"]),
        TrainerConfig(max_seq_len=settings.max_seq_len, group_size=g,
                      minibatches=settings.minibatches))
    del params
    engines, proxies, router = make_rollout_fleet(api, trainer.get_weights(),
                                                  settings)
    buffer = SampleBuffer(batch_size=batch, alpha=settings.async_generation_ratio)
    stream = traffic.GroupStream(mix, dims.vocab, ctx.seed)
    producer = RolloutProducer(
        router if router is not None else proxies[0], buffer,
        traffic.replicated_prompts(stream, g), group_size=g,
        max_new_tokens=settings.max_new_tokens,
        reward_fn=TieBreakReward(ctx.seed))
    controller = AsyncController(
        buffer, proxies, trainer.train_on_samples, trainer.get_weights,
        alpha=settings.async_generation_ratio, weight_sync=settings.weight_sync,
        weight_sync_timeout=settings.weight_sync_timeout, router=router)

    built_s = now() - ctx.t_start

    # ---- the benchmark's hooks
    rec = Recorder(trainer, mix["warm_steps"], opt["b1"],
                   W.roots(ctx.arch, dims))
    lengths = []
    for e in engines:
        e.step = spans.wrap("bench.engine_step", e.step)
        lengths.append(KernelLengths(e))
    controller.train_fn = spans.wrap("bench.train_fn", controller.train_fn)
    controller._weight_sync = spans.wrap("bench.weight_sync",
                                         controller._weight_sync)
    buffer.get_batch = spans.wrap("bench.get_batch", buffer.get_batch)
    submitted, done = {}, {}
    submit, publish = producer._submit, producer._publish

    def on_submit(tasks, version):
        t = now()
        for task in tasks:
            submitted[task.task_id] = t
        submit(tasks, version)

    def on_publish(result):
        failed = result.timed_out or isinstance(result, Rejected)
        if not result.aborted or failed:
            done[result.task.task_id] = (now(), failed,
                                         result.task.meta.get("queue_wait_s"))
        publish(result)

    producer._submit, producer._publish = on_submit, on_publish

    # ---- set-up: start, and drive the first steps
    if router is not None:
        router.start()
    else:
        for p in proxies:
            p.start()
    producer.start()
    timeout = mix["step_timeout_s"]
    try:
        controller.train(mix["warm_steps"], timeout=timeout)
        warm_stats = list(controller.stats)
        # the overlapped sync returns once every engine holds the new
        # weights: these are the weights after the last warm step, as served
        synced = [jax.device_get(quant.dequantize_params(e.params))
                  for e in engines]
        synced_after = rec.opt_steps
        setup_s = now() - ctx.t_start

        # ---- the window
        program = ProgramSpans(tracing)
        if ctx.trace:
            for k in lengths:
                k.armed = True
            program.start()
            start_trace(ctx.scratch)
        step_ends = []
        with CompileClock() as clock:
            with spans.span("bench.window"):
                t0 = now()
                at_t0 = counters(engines), counters([trainer])
                while True:
                    with spans.span("bench.controller_step"):
                        controller.train(1, timeout=timeout)
                    step_ends.append(now())
                    if step_ends[-1] - t0 >= ctx.seconds:
                        break
                t1 = step_ends[-1]
                at_t1 = counters(engines), counters([trainer])
        if ctx.trace:
            program.stop()
            jax.profiler.stop_trace()
            for k in lengths:
                k.armed = False
        window_stats = controller.stats[len(warm_stats):]
        peak = memory_peak(ctx.devices)
    finally:
        producer.stop()
        buffer.close()
        producer.join(timeout=30)
        if router is not None:
            router.stop()
        else:
            for p in proxies:
                p.stop()

    # ---- end-to-end numbers, from the host clock
    n_steps = len(window_stats)
    counted = [(d - submitted[tid], failed, wait)
               for tid, (d, failed, wait) in done.items()
               if t0 <= d <= t1 and tid in submitted]
    lat = [math.inf if failed else x for x, failed, _ in counted]
    # a sample never admitted (refused in the queue) has no wait: inf
    waits = [math.inf if w is None else w for _, _, w in counted]
    n_failed = sum(1 for x in lat if math.isinf(x))
    engine_totals = delta(at_t0[0], at_t1[0])
    e2e = {"rl_step_s": (t1 - t0) / n_steps, "sample_p90_s": traffic.p90(lat),
           "setup_s": setup_s}
    record = {
        "kind": "rl", "dims": dims, "window": (t0, t1), "steps": [
            dataclasses.asdict(s) for s in window_stats],
        "engine_step_s": spans.within("bench.engine_step", t0, t1),
        "decode_steps": engine_totals["total_decode_steps"],
        "tokens_decoded": engine_totals["total_tokens_decoded"],
        "decode_context": engine_totals.get("total_decode_context"),
        "engine_totals": engine_totals,
        "trainer_totals": delta(at_t0[1], at_t1[1]),
        "queue_waits": waits,
        "kernel_calls": [(tt, ls, ch) for k in lengths for tt, ls, ch in k.calls],
        "train_lengths": [ls for tt, ls in rec.train_lengths if t0 <= tt <= t1],
        "window_compiles": clock.count,
    }
    trace = None
    if ctx.trace:
        from benchlib import trace as tr
        path = str(next(ctx.scratch.rglob("*.xplane.pb")))
        trace = tr.reduce(path)
        record.update(program.record(path))
    print(f"bench: built_s={built_s!r} setup_s={setup_s!r} "
          f"window_s={t1 - t0!r} steps={n_steps} "
          f"samples={len(lat)} compiles_in_window={clock.count} "
          f"warm_losses={[s.loss for s in warm_stats]!r} "
          f"weight_syncs={[p.staged_weight_updates for p in proxies]} "
          f"synced_after_steps={synced_after}", flush=True)

    first = [(s.prompt_tokens, s.response_tokens, s.logprobs)
             for s in rec.batches[0]]
    progress = {"loss": [s.loss for s in warm_stats][:rec.warm],
                "grad_norm": rec.first_grad, "master": rec.master,
                "synced": synced, "synced_after": synced_after}
    feeds = [reference.build_feed(b, mix) for b in rec.batches]
    return (Outcome(e2e=e2e, record=record, checks={}, attempted=len(lat),
                    failed=n_failed, memory_peak_bytes=peak, trace=trace),
            first, feeds, progress)


def run(ctx) -> Outcome:
    out, first, feeds, progress = _serve(ctx)
    # ---- correctness, once the program's state is freed
    gc.collect()
    live_bytes()
    out.checks = check.served(ctx.config, ctx.arch, ctx.seed, first,
                              ctx.mix["limits"])
    controls = ("fp8", "half_batch") if ctx.control else ()
    out.checks.update(check.training(ctx.config, ctx.arch, ctx.seed, feeds,
                                     progress, ctx.mix, controls=controls))
    return out
