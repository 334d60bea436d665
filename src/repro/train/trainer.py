"""Policy-gradient trainer.

``make_train_step`` builds the pure, pjit-able step used by both the real
trainer and the multi-pod dry-run.  ``HostTrainer`` is the host-side wrapper
the AsyncController drives: it pads Sample batches, computes GRPO advantages
and proximal/reference logprobs, runs (optionally minibatched) train steps,
and serves fresh weights to the LLMProxy on weight sync.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.algos import LossConfig, group_normalized_advantage, rl_loss, token_logprobs
from repro.core.types import Sample
from repro.models.api import ModelAPI
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state


def make_train_state(api: ModelAPI, key) -> Dict[str, Any]:
    params = api.init(key)
    return {"params": params, "opt": init_opt_state(params)}


_CE_CHUNK = 512
# fp32 logits elements one chunk may hold (512 MiB): at batch 16 over a
# 151,936-row vocabulary a 512-position chunk alone would be 5 GB.
_CE_LOGITS_BUDGET = 1 << 27


def _unembed_matrix(api: ModelAPI, params):
    if api.cfg.family == "audio":
        return params["lm_head"]
    from repro.models.transformer import unembedding_matrix
    return unembedding_matrix(params, api.cfg)


def chunked_token_logprobs(features, head, tokens, *, chunk: int = _CE_CHUNK):
    """Fused unembed + gather over sequence chunks (§Perf iter 3).

    Never materializes (B, S, V) logits: each chunk's (B, C, V) logits are
    consumed into (B, C) logprobs and rematerialized in the backward pass.
    The chunk shrinks below ``chunk`` positions where B * C * V would pass
    ``_CE_LOGITS_BUDGET``.
    features: (B, S, D) final-norm hidden states; returns (B, S) logprobs
    aligned with `tokens` (position 0 zero — never a response token).
    """
    b, s, d = features.shape
    chunk = max(1, min(chunk, _CE_LOGITS_BUDGET // (b * head.shape[1])))
    x, tg = features[:, :-1], tokens[:, 1:]
    sc = s - 1
    nc = -(-sc // chunk)
    pad = nc * chunk - sc
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        tg = jnp.pad(tg, ((0, 0), (0, pad)))
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = tg.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(args):
        xi, ti = args
        logits = (xi @ head).astype(jnp.float32)
        return token_logprobs(logits, ti)

    lp = jax.lax.map(jax.checkpoint(body, prevent_cse=False), (xc, tc))
    lp = lp.transpose(1, 0, 2).reshape(b, nc * chunk)[:, :sc]
    return jnp.pad(lp, ((0, 0), (1, 0)))


def _policy_logprobs(api: ModelAPI, params, batch, *, remat, moe_mode):
    """logprobs (B, S) aligned with batch['tokens'] (position t = logprob of
    token t given <t); position 0 is zero (never a response token)."""
    cfg = api.cfg
    features, aux = api.apply(params, batch, remat=remat, moe_mode=moe_mode,
                              return_features=True)
    if cfg.family == "vlm":
        features = features[:, cfg.num_image_tokens:]
    head = _unembed_matrix(api, params)
    return chunked_token_logprobs(features, head, batch["tokens"]), aux


def make_train_step(api: ModelAPI, loss_cfg: LossConfig, opt_cfg: OptConfig,
                    *, remat: bool = True, moe_mode: str = "ep",
                    microbatches: int = 1):
    """Build the pjit-able train step.

    ``microbatches > 1`` runs gradient accumulation inside the step (scan
    over batch slices, fp32 grad accumulator): same numerics for the mean
    loss, 1/m the activation working set — how the MoE configs fit per-chip
    HBM at global batch 256 (§Perf iter 7b).
    """
    def loss_and_grad(params, batch):
        def loss_fn(p):
            logprobs, aux = _policy_logprobs(api, p, batch,
                                             remat=remat, moe_mode=moe_mode)
            return rl_loss(logprobs, batch, loss_cfg, aux)

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(state, batch):
        if microbatches > 1:
            m = microbatches

            def split(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            mbs = jax.tree_util.tree_map(split, batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])

            def body(acc, mb):
                (loss, metrics), g = loss_and_grad(state["params"], mb)
                acc = jax.tree_util.tree_map(
                    lambda a, gi: a + gi.astype(jnp.float32) / m, acc, g)
                return acc, (loss, metrics)

            grads, (losses, metricses) = jax.lax.scan(body, zeros, mbs)
            loss = losses.mean()
            metrics = jax.tree_util.tree_map(jnp.mean, metricses)
        else:
            (loss, metrics), grads = loss_and_grad(state["params"], batch)

        dtypes = jax.tree_util.tree_map(lambda p: p.dtype, state["params"])
        params, opt, opt_metrics = adamw_update(grads, state["opt"], opt_cfg, dtypes)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return {"params": params, "opt": opt}, metrics

    return train_step


def make_logprob_fn(api: ModelAPI, *, moe_mode: str = "ep"):
    def logprob_fn(params, batch):
        lp, _ = _policy_logprobs(api, params, batch, remat=False, moe_mode=moe_mode)
        return lp

    return logprob_fn


# ---------------------------------------------------------------------------
# host-side wrapper: Samples -> padded arrays -> jitted steps
# ---------------------------------------------------------------------------

def _jit_donating_all_but_params(step):
    """jit ``step(state, batch)`` so that every state entry except the
    policy params is donated: the optimizer state (fp32 master, m and v —
    12 of the 14 bytes a parameter costs) is updated in place instead of
    living twice during a step.  The params are not donated, because the
    rollout engines keep reading the tree they were handed until the next
    weight sync.  The jitted function takes ``(params, rest, batch)``."""
    def split_step(params, rest, batch):
        return step(dict(rest, params=params), batch)

    return jax.jit(split_step, donate_argnums=(1,))


@dataclasses.dataclass
class TrainerConfig:
    max_seq_len: int = 64
    group_size: int = 8
    minibatches: int = 1           # gradient_accumulation-style splits
    ppo_epochs: int = 1            # sample reuse E
    adv_estimator: str = "grpo"    # grpo (critic-free, paper default) | gae


class HostTrainer:
    def __init__(self, api: ModelAPI, key, loss_cfg: LossConfig,
                 opt_cfg: OptConfig, tcfg: TrainerConfig, *,
                 ref_params=None):
        self.api = api
        self.cfg = api.cfg
        self.loss_cfg = loss_cfg
        self.tcfg = tcfg
        moe_mode = "dense" if self.cfg.is_moe else "ep"
        if tcfg.adv_estimator == "gae":
            from repro.train.critic import (make_critic_train_state,
                                            make_critic_train_step)
            self.state = make_critic_train_state(api, key)
            self._train_step = _jit_donating_all_but_params(
                make_critic_train_step(api, loss_cfg, opt_cfg,
                                       moe_mode=moe_mode))
        else:
            self.state = make_train_state(api, key)
            # remat: per-layer checkpointing keeps one layer's activations
            # live in the backward pass.  Without it, a 3-layer qwen3-4b
            # step over 16 x 320 tokens holds ~5 GB of attention and MLP
            # activations beside 10 GB of train state on a 16 GB chip.
            self._train_step = _jit_donating_all_but_params(make_train_step(
                api, loss_cfg, opt_cfg, remat=True, moe_mode=moe_mode))
        self.ref_params = ref_params  # frozen copy for KL (None = no KL)
        self._logprob_fn = jax.jit(make_logprob_fn(
            api, moe_mode="dense" if self.cfg.is_moe else "ep"))
        self.steps_done = 0
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------- batching
    def build_batch(self, samples: List[Sample]) -> Dict[str, np.ndarray]:
        s_len = self.tcfg.max_seq_len
        n = len(samples)
        tokens = np.zeros((n, s_len), np.int32)
        mask = np.zeros((n, s_len), np.float32)
        old_lp = np.zeros((n, s_len), np.float32)
        for i, s in enumerate(samples):
            p = np.asarray(s.prompt_tokens, np.int32).ravel()
            r = np.asarray(s.response_tokens, np.int32).ravel()
            lp = np.asarray(s.logprobs, np.float32).ravel()
            p = p[-s_len:]
            r = r[: s_len - len(p)]
            lp = lp[: len(r)]
            tokens[i, : len(p)] = p
            tokens[i, len(p): len(p) + len(r)] = r
            mask[i, len(p): len(p) + len(r)] = 1.0
            old_lp[i, len(p): len(p) + len(r)] = lp

        rewards = np.asarray([s.reward or 0.0 for s in samples], np.float32)
        # GRPO: group-normalize within same-prompt groups; fall back to batch
        # norm when groups are ragged (agentic trajectories).
        gids = [s.group_id for s in samples]
        if n % self.tcfg.group_size == 0 and len(set(gids)) == n // self.tcfg.group_size:
            order = np.argsort(gids, kind="stable")
            inv = np.argsort(order)
            adv_sorted = group_normalized_advantage(
                jnp.asarray(rewards[order]), self.tcfg.group_size)
            seq_adv = np.asarray(adv_sorted)[inv]
        else:
            seq_adv = (rewards - rewards.mean()) / (rewards.std() + 1e-6)
        adv = seq_adv[:, None] * mask

        batch = {
            "tokens": tokens, "mask": mask, "advantages": adv.astype(np.float32),
            "rewards": rewards,
            "old_logprobs": old_lp,
            "prox_logprobs": old_lp.copy(),
            "ref_logprobs": np.zeros_like(old_lp),
            "is_positive": (rewards > 0).astype(np.float32),
        }
        if self.cfg.family == "vlm":
            batch["patches"] = np.zeros(
                (n, self.cfg.num_image_tokens, self.cfg.d_model), np.float32)
        if self.cfg.family == "audio":
            batch["frames"] = np.zeros(
                (n, self.cfg.encoder_frames, self.cfg.d_model), np.float32)
        return batch

    # --------------------------------------------------------------- train
    def train_on_samples(self, samples: List[Sample]) -> Dict[str, float]:
        batch_np = self.build_batch(samples)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}

        # proximal logprobs: the policy at batch-fetch time (before updates)
        if self.loss_cfg.pg_variant == "decoupled_ppo" or self.tcfg.minibatches > 1:
            batch["prox_logprobs"] = self._logprob_fn(self.state["params"], batch)
        if self.loss_cfg.kl_beta and self.ref_params is not None:
            batch["ref_logprobs"] = self._logprob_fn(self.ref_params, batch)

        n = batch["tokens"].shape[0]
        mb = max(1, self.tcfg.minibatches)
        assert n % mb == 0, (n, mb)
        metrics: Dict[str, float] = {}
        for _ in range(self.tcfg.ppo_epochs):
            for j in range(mb):
                sl = slice(j * n // mb, (j + 1) * n // mb)
                mini = {k: v[sl] for k, v in batch.items()}
                rest = {k: v for k, v in self.state.items() if k != "params"}
                self.state, m = self._train_step(self.state["params"], rest,
                                                 mini)
                metrics = {k: float(v) for k, v in m.items()}
        self.steps_done += 1
        metrics["reward_mean"] = float(np.mean([s.reward or 0.0 for s in samples]))
        self.history.append(metrics)
        return metrics

    def get_weights(self):
        return self.state["params"]
