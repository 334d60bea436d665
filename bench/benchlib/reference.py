"""The plain reference: a causal language model in float32 at "highest"
matmul precision, and the GRPO train step it is trained with.

What is shared lives here: the embedding, the final norm and the head,
the layer loop, the loss, AdamW, and the pieces blocks are built from
(``mm``, ``rmsnorm``, ``rope``, ``attention``).  The blocks themselves are
the architecture module's (``bench/arch/<name>.py``: each kind of layer's
``block``, ``weights.layer_kinds``), which every function here that runs
the model takes as ``arch``.

It imports nothing of the program and reads no weight the program holds:
weights come from ``benchlib.weights`` and the seed.  ``cast`` (optional)
rounds both operands of every matmul, the control's lower precision.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights as W

HI = jax.lax.Precision.HIGHEST
Cast = Optional[Callable]
NO_EXTRA_LOSS = np.float32(0.0)     # a block's extra loss where it has none


def fp8_cast(a):
    """Round to float8_e4m3fn with one scale per tensor (its amax at the
    format's largest value): the precision below bfloat16.  The gradient
    passes through unrounded (the rounded values are what the backward
    pass multiplies with; rounding the cotangents unscaled would flush
    them to zero)."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0)
    q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a + jax.lax.stop_gradient(q - a)


def mm(a, b, cast: Cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, T, heads, hd) at positions 0..T-1."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0 / hd))
    ang = np.arange(x.shape[1])[:, None] * inv[None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, cast: Cast = None):
    """Causal GQA softmax attention at positions 0..T-1: q (B, T, heads,
    hd), k and v (B, T, kv, hd), each head of q on kv head ``h // (heads //
    kv)``; returns (B, T, heads * hd)."""
    b, t, heads, hd = q.shape
    kv = k.shape[2]
    q = q.reshape(b, t, kv, heads // kv, hd)
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HI) * hd ** -0.5
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if cast is not None:
        p = cast(p)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HI)
    return o.reshape(b, t, heads * hd)


def head_matrix(top: dict, tied: bool):
    return top["embed"].T if tied else top["lm_head"]


def token_logprobs(xf, head, tokens, cast: Cast = None):
    """(B, T) logprobs aligned with ``tokens``: entry t is log p(tokens[t] |
    tokens[:t]); entry 0 is 0.  Rows one at a time: a (T, V) float32 logits
    block at a time, never (B, T, V)."""
    def row(args):
        x, tok = args
        logits = mm(x[:-1], head, cast)
        lp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(lp, tok[1:, None], -1)[:, 0]
        return jnp.concatenate([jnp.zeros((1,), jnp.float32), picked])
    return jax.lax.map(jax.checkpoint(row), (xf, tokens))


# --------------------------------------------------------------------------
# served tokens: layer by layer, so only one block's weights are held
# --------------------------------------------------------------------------

def served_logprobs(cfg: dict, arch, seed: int, seqs, *, rows: int,
                    cast: Cast = None) -> list:
    """Reference logprobs of every position of each token sequence in
    ``seqs`` (int arrays): ``out[i][t]`` = log p(seqs[i][t] | seqs[i][:t]).
    Sequences are right-padded to one length (causal: padding never
    reaches an earlier position) and run ``rows`` at a time."""
    dims = arch.dims(cfg)
    tied = bool(cfg["tie_word_embeddings"])
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    t = max(len(s) for s in seqs)
    n = -(-len(seqs) // rows) * rows
    toks = np.zeros((n, t), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = toks.reshape(n // rows, rows, t)

    top = W.top(arch, dims, tied, seed)
    with jax.default_matmul_precision("highest"):
        xs = [jnp.take(top["embed"], jnp.asarray(c), axis=0) for c in toks]
        i = 0
        for kind in W.layer_kinds(arch, dims):
            step = jax.jit(functools.partial(kind.block, dims=dims,
                                             theta=theta, eps=eps, cast=cast))
            for _ in range(kind.layers):
                w = W.layer(arch, dims, seed, i)
                xs = [step(w, x, i=np.int32(i))[0] for x in xs]
                del w
                i += 1
        head = head_matrix(top, tied)
        lp_fn = jax.jit(functools.partial(token_logprobs, cast=cast))
        out = [np.asarray(lp_fn(rmsnorm(x, top["final_norm"]["scale"], eps),
                                head, jnp.asarray(c)))
               for x, c in zip(xs, toks, strict=True)]
    lp = np.concatenate(out)
    return [lp[i, :len(s)] for i, s in enumerate(seqs)]


# --------------------------------------------------------------------------
# the train step: GRPO's clipped surrogate and AdamW, as the mix states them
# --------------------------------------------------------------------------

def forward_logprobs(params, tokens, cfg: dict, arch, cast: Cast = None):
    """(token logprobs, the sum over the layers of each block's extra loss):
    each kind's stack scanned in layer order with that kind's block."""
    dims = arch.dims(cfg)
    tied = bool(cfg["tie_word_embeddings"])
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    x = jnp.take(params["embed"], tokens, axis=0)
    extra, first = [], 0
    for kind in W.layer_kinds(arch, dims):
        def body(h, layer, block=kind.block):
            w, i = layer
            return block(w, h, dims, i, theta, eps, cast)

        x, e = jax.lax.scan(jax.checkpoint(body), x,
                            (params[kind.root],
                             jnp.arange(first, first + kind.layers)))
        extra.append(e.sum())
        first += kind.layers
    xf = rmsnorm(x, params["final_norm"]["scale"], eps)
    return (token_logprobs(xf, head_matrix(params, tied), tokens, cast),
            sum(extra[1:], extra[0]))


def grpo_loss(params, batch, cfg: dict, arch, loss_cfg: dict,
              cast: Cast = None):
    """Token-level clipped surrogate, per-sequence 1/|o| averaging, with the
    advantages weighted by min(pi_train / pi_rollout, cap) (stop-gradient),
    plus the blocks' extra loss (a router's auxiliary loss; 0 for dense
    blocks)."""
    lp, extra = forward_logprobs(params, batch["tokens"], cfg, arch, cast)
    old, mask = batch["old_logprobs"], batch["mask"]
    adv = batch["advantages"] * jnp.minimum(
        jnp.exp(jax.lax.stop_gradient(lp) - old), loss_cfg["mismatch_cap"])
    ratio = jnp.exp(lp - old)
    eps = loss_cfg["clip_eps"]
    obj = jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - eps, 1 + eps) * adv)
    per_seq = (obj * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1.0)
    return -per_seq.mean() + extra


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, m, v, scale, lr, bc1, bc2, b1, b2, eps, wd):
    g = g * scale
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
    return p - lr * (upd + wd * p), m2, v2


@jax.jit
def _sq_norm(tree):
    return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(tree))


def train_steps(cfg: dict, arch, seed: int, feeds: list, loss_cfg: dict,
                opt: dict, cast: Cast = None) -> dict:
    """The reference's run of the first ``len(feeds)`` optimizer steps from
    the seed's weights (AdamW with global-norm clipping and linear warmup):
    each step's loss, each leaf's norm of the first (clipped) gradient, and
    the float32 parameters after the last step.  Leaves are split per layer
    (``weights.per_layer_leaves``).  Adam's moments live on the host and
    visit the device one leaf at a time, so the device holds only the
    parameters and one gradient."""
    tied = bool(cfg["tie_word_embeddings"])
    roots = W.roots(arch, arch.dims(cfg))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    W.make(arch, arch.dims(cfg), tied, seed))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    m = [np.zeros(a.shape, np.float32) for a in leaves]
    v = [np.zeros(a.shape, np.float32) for a in leaves]
    vg = jax.jit(jax.value_and_grad(functools.partial(
        grpo_loss, cfg=cfg, arch=arch, loss_cfg=loss_cfg, cast=cast)))
    b1, b2 = opt["b1"], opt["b2"]
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for k, feed in enumerate(feeds, start=1):
            batch = {key: jnp.asarray(feed[key]) for key in
                     ("tokens", "mask", "advantages", "old_logprobs")}
            loss, grads = vg(jax.tree_util.tree_unflatten(treedef, leaves), batch)
            losses.append(float(loss))
            gnorm = float(jnp.sqrt(_sq_norm(grads)))
            scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-9))
            if k == 1:
                grad_norms = {n: x * scale
                              for n, x in leaf_norms(grads, roots).items()}
            grads = jax.tree_util.tree_leaves(grads)
            lr = opt["learning_rate"] * min(1.0, k / opt["warmup_steps"])
            for i in range(len(leaves)):
                leaves[i], mi, vi = _adamw_leaf(
                    leaves[i], grads[i], jnp.asarray(m[i]), jnp.asarray(v[i]),
                    scale, lr, 1 - b1 ** k, 1 - b2 ** k, b1, b2, opt["eps"],
                    opt["weight_decay"])
                grads[i] = None
                m[i], v[i] = np.asarray(mi), np.asarray(vi)
            del grads
    return {"loss": losses, "grad_norm": grad_norms,
            "params": jax.tree_util.tree_unflatten(treedef, leaves)}


def change_norms(cfg: dict, arch, seed: int, params,
                 served: bool = False) -> dict:
    """Each leaf's norm of ``params`` (float32, or host arrays) less the
    seed's initial weights, one leaf on the device at a time.  ``served``
    first rounds each leaf to the type the seed's weights are served in
    (bfloat16 matrices, float32 norm scales)."""
    dims = arch.dims(cfg)
    roots = W.roots(arch, dims)
    init = W.per_layer_leaves(W.make(arch, dims,
                                     bool(cfg["tie_word_embeddings"]), seed),
                              roots)
    now = W.per_layer_leaves(params, roots)
    diff = jax.jit(lambda a, b: jnp.linalg.norm(
        (a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()))

    def leaf(n):
        a = jnp.asarray(now[n])
        if served:
            # rounded in a program of its own: inside the difference's
            # program XLA may keep the excess precision and skip the round
            a = a.astype(init[n].dtype).block_until_ready()
        return float(diff(a, init[n]))

    return {n: leaf(n) for n in init}


def leaf_norms(tree, roots) -> dict:
    return {name: float(jnp.linalg.norm(a.astype(jnp.float32).ravel()))
            for name, a in W.per_layer_leaves(tree, roots).items()}


def build_feed(samples, mix: dict) -> dict:
    """The train step's inputs from a batch of samples, built as the mix
    states: each row the prompt (its last ``max_seq_len`` tokens) then the
    response, right-padded; the mask on response tokens; the behaviour
    logprobs under it; GRPO advantages, each sample's reward less its
    group's mean over the group's standard deviation (+ ``adv_eps``)."""
    s_len, g = mix["max_seq_len"], mix["group_size"]
    n = len(samples)
    tokens = np.zeros((n, s_len), np.int32)
    mask = np.zeros((n, s_len), np.float32)
    old = np.zeros((n, s_len), np.float32)
    for i, smp in enumerate(samples):
        p = np.asarray(smp.prompt_tokens, np.int32).ravel()[-s_len:]
        r = np.asarray(smp.response_tokens, np.int32).ravel()[:s_len - len(p)]
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + len(r)] = r
        mask[i, len(p):len(p) + len(r)] = 1.0
        old[i, len(p):len(p) + len(r)] = np.asarray(smp.logprobs)[:len(r)]
    rewards = np.asarray([smp.reward or 0.0 for smp in samples], np.float64)
    gids = np.asarray([smp.group_id for smp in samples])
    eps = mix["loss"]["adv_eps"]
    adv = np.zeros(n)
    if n % g == 0 and len(set(gids.tolist())) == n // g:
        for gid in set(gids.tolist()):
            sel = gids == gid
            r = rewards[sel]
            adv[sel] = (r - r.mean()) / (r.std() + eps)
    else:
        adv = (rewards - rewards.mean()) / (rewards.std() + eps)
    return {"tokens": tokens, "mask": mask, "old_logprobs": old,
            "advantages": (adv[:, None] * mask).astype(np.float32)}
