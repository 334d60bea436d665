"""Decoder-only LM assembly for all families (dense / moe / ssm / hybrid / vlm).

Layers are *stacked* (leading layer axis) and executed with
``jax.lax.scan`` so that 94-layer configs lower to a single while-loop body
— essential for compile time on the 512-device dry-run.  Hybrid archs
(RecurrentGemma) scan over pattern *groups* plus an unrolled tail.

Entry points:
    init_lm / init_cache
    lm_apply(params, cfg, tokens, ...)          -> (logits, aux)      # train
    lm_prefill(params, cfg, tokens, cache, ...) -> (logits, cache)    # prefill
    lm_decode_step(params, cfg, token, pos, cache) -> (logits, cache) # decode
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, ffn, moe, module, rglru, rwkv6
from repro.models.sharding import constrain_activation
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg: ModelConfig, *, use_moe: bool):
    ks = jax.random.split(key, 2)
    p = {
        "ln1": module.rmsnorm_init(cfg.d_model, cfg.dtype),
        "ln2": module.rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": attention.init_attention(ks[0], cfg),
    }
    if use_moe:
        p["moe"] = moe.init_moe(ks[1], cfg)
    else:
        p["mlp"] = ffn.init_mlp(ks[1], cfg)
    return p


def _init_rglru_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "ln1": module.rmsnorm_init(cfg.d_model, cfg.dtype),
        "ln2": module.rmsnorm_init(cfg.d_model, cfg.dtype),
        "rec": rglru.init_recurrent_block(ks[0], cfg),
        "mlp": ffn.init_mlp(ks[1], cfg),
    }


def _layer_init_fn(cfg: ModelConfig, kind: str):
    if kind == "attn":
        return functools.partial(_init_attn_block, cfg=cfg, use_moe=cfg.is_moe)
    if kind == "rwkv":
        return functools.partial(rwkv6.init_block, cfg=cfg)
    if kind == "rglru":
        return functools.partial(_init_rglru_block, cfg=cfg)
    raise ValueError(kind)


def _stacked_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _hybrid_layout(cfg: ModelConfig):
    pattern = cfg.block_pattern
    n_groups = cfg.num_layers // len(pattern)
    tail = tuple(pattern[: cfg.num_layers - n_groups * len(pattern)])
    return pattern, n_groups, tail


def init_lm(key, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    # A tied table is also the output head, so it starts at the head's
    # scale, 1/sqrt(d_model) (dense_init's for lm_head; Qwen3's 0.02 at
    # d_model 2560).  At unit scale the input token's own logit (~d_model)
    # would dwarf every other: a random-weight policy would only repeat
    # its input, with a saturated softmax and zero policy gradient.
    embed_scale = cfg.d_model ** -0.5 if cfg.tie_embeddings else 1.0
    params: dict[str, Any] = {
        "embed": module.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt,
                                   scale=embed_scale),
        "final_norm": module.rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = module.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)

    if cfg.family in ("dense", "moe", "vlm"):
        params["blocks"] = _stacked_init(ks[2], cfg.num_layers, _layer_init_fn(cfg, "attn"))
    elif cfg.family == "ssm":
        params["blocks"] = _stacked_init(ks[2], cfg.num_layers, _layer_init_fn(cfg, "rwkv"))
    elif cfg.family == "hybrid":
        pattern, n_groups, tail = _hybrid_layout(cfg)
        gk = jax.random.split(ks[2], len(pattern))
        params["blocks"] = {
            f"{i}_{kind}": _stacked_init(gk[i], n_groups, _layer_init_fn(cfg, kind))
            for i, kind in enumerate(pattern)
        }
        tk = jax.random.split(ks[3], max(1, len(tail)))
        params["tail"] = [
            _layer_init_fn(cfg, kind)(tk[i]) for i, kind in enumerate(tail)
        ]
    else:
        raise ValueError(f"init_lm does not handle family {cfg.family}")
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _stack_cache(make_one, n: int):
    one = make_one()
    return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.family in ("dense", "moe", "vlm"):
        return _stack_cache(lambda: attention.init_kv_cache(cfg, batch, max_len), cfg.num_layers)
    if cfg.family == "ssm":
        return _stack_cache(lambda: rwkv6.init_rwkv_state(cfg, batch), cfg.num_layers)
    if cfg.family == "hybrid":
        pattern, n_groups, tail = _hybrid_layout(cfg)

        def one(kind):
            if kind == "attn":
                return lambda: attention.init_kv_cache(cfg, batch, max_len)
            return lambda: rglru.init_rglru_state(cfg, batch)

        cache = {
            f"{i}_{kind}": _stack_cache(one(kind), n_groups) for i, kind in enumerate(pattern)
        }
        cache["tail"] = [one(kind)() for kind in tail]
        return cache
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# per-layer apply (three modes: full, prefill, decode)
# ---------------------------------------------------------------------------

_ZERO_AUX = {"load_balance_loss": jnp.zeros((), jnp.float32),
             "router_z_loss": jnp.zeros((), jnp.float32)}


def _attn_block_apply(p, cfg: ModelConfig, x, positions, *, moe_mode: str):
    x = constrain_activation(x)
    y = attention.self_attention(p["attn"], cfg, module.rmsnorm(p["ln1"], x, cfg.norm_eps), positions)
    x = x + y
    h = module.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        y, aux = moe.moe_apply(p["moe"], cfg, h, mode=moe_mode)
    else:
        y, aux = ffn.mlp(p["mlp"], cfg, h), _ZERO_AUX
    return x + y, aux


def _attn_block_prefill(p, cfg: ModelConfig, x, positions, cache, *, moe_mode: str,
                        valid=None):
    y, cache = attention.prefill_attention(p["attn"], cfg, module.rmsnorm(p["ln1"], x, cfg.norm_eps),
                                           positions, cache, valid=valid)
    x = x + y
    h = module.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe.moe_apply(p["moe"], cfg, h, mode=moe_mode)
    else:
        y = ffn.mlp(p["mlp"], cfg, h)
    return x + y, cache


def _attn_block_decode(p, cfg: ModelConfig, x, pos, cache, *, moe_mode: str):
    y, cache = attention.decode_attention(p["attn"], cfg, module.rmsnorm(p["ln1"], x, cfg.norm_eps),
                                          pos, cache)
    x = x + y
    h = module.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.is_moe:
        y, _ = moe.moe_apply(p["moe"], cfg, h, mode=moe_mode)
    else:
        y = ffn.mlp(p["mlp"], cfg, h)
    return x + y, cache


def _rglru_block_apply(p, cfg: ModelConfig, x, state, *, decode: bool):
    if not decode:
        x = constrain_activation(x)
    fn = rglru.recurrent_step if decode else rglru.recurrent_block
    y, state = fn(p["rec"], cfg, module.rmsnorm(p["ln1"], x, cfg.norm_eps), state)
    x = x + y
    x = x + ffn.mlp(p["mlp"], cfg, module.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x, state


# ---------------------------------------------------------------------------
# trunk apply
# ---------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens, prefix_embeds):
    x = params["embed"][tokens]
    if cfg.family == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)  # gemma embed scale
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


def _unembed(params, cfg: ModelConfig, x):
    x = module.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


def _default_positions(b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))


def lm_apply(params, cfg: ModelConfig, tokens, *, positions=None,
             prefix_embeds=None, remat: bool = False, moe_mode: str = "ep",
             return_features: bool = False):
    """Full-sequence causal forward.

    Returns (logits fp32, aux dict) — or, with ``return_features``, the
    final-norm hidden states (B, S, D) instead of logits, so the caller can
    fuse the unembedding with the loss (chunked cross-entropy: materializing
    (B, S, V) fp32 at 4k x 256k vocab costs ~1 TiB global — §Perf iter 3)."""
    x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    if positions is None:
        positions = _default_positions(b, s)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(h, lp):
            h2, aux = _attn_block_apply(lp, cfg, h, positions, moe_mode=moe_mode)
            return h2, aux
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, auxs = jax.lax.scan(body, x, params["blocks"])
        aux = jax.tree_util.tree_map(jnp.mean, auxs)
    elif cfg.family == "ssm":
        state0 = init_cache(cfg, b, s)

        def body(h, inp):
            lp, st = inp
            h2, _ = rwkv6.block(lp, cfg, constrain_activation(h), st)
            return h2, None
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, (params["blocks"], state0))
        aux = dict(_ZERO_AUX)
    elif cfg.family == "hybrid":
        pattern, n_groups, tail = _hybrid_layout(cfg)
        states = init_cache(cfg, b, s)

        def group_body(h, inp):
            for i, kind in enumerate(pattern):
                lp = inp[f"{i}_{kind}"]
                if kind == "attn":
                    h, _ = _attn_block_apply(lp, cfg, h, positions, moe_mode=moe_mode)
                else:
                    h, _ = _rglru_block_apply(lp, cfg, h, inp[f"state_{i}"], decode=False)
            return h, None

        xs = {f"{i}_{kind}": params["blocks"][f"{i}_{kind}"] for i, kind in enumerate(pattern)}
        xs.update({f"state_{i}": states[f"{i}_{kind}"]
                   for i, kind in enumerate(pattern) if kind != "attn"})
        gb = jax.checkpoint(group_body, prevent_cse=False) if remat else group_body
        x, _ = jax.lax.scan(gb, x, xs)
        for tp, st, kind in zip(params["tail"], states["tail"], tail,
                                strict=True):
            if kind == "attn":
                x, _ = _attn_block_apply(tp, cfg, x, positions, moe_mode=moe_mode)
            else:
                x, _ = _rglru_block_apply(tp, cfg, x, st, decode=False)
        aux = dict(_ZERO_AUX)
    else:
        raise ValueError(cfg.family)

    if return_features:
        return module.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux
    return _unembed(params, cfg, x), aux


def unembedding_matrix(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _last_position_logits(params, cfg: ModelConfig, x, valid):
    """Unembed ONLY each row's last real position -> (B, V) fp32.

    Serving prefill needs just the next-token distribution; materializing
    (B, S, V) fp32 logits at 32k x 256k vocab is ~1 TiB and was the dominant
    memory+collective term of every prefill combo (EXPERIMENTS.md §Perf
    iter 1)."""
    b, s, _ = x.shape
    if valid is None:
        last = jnp.full((b,), s - 1, jnp.int32)
    else:
        last = jnp.maximum(valid.sum(axis=1).astype(jnp.int32) - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    x_last = module.rmsnorm(params["final_norm"], x_last[:, None, :], cfg.norm_eps)[:, 0]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x_last @ head).astype(jnp.float32)


def lm_prefill(params, cfg: ModelConfig, tokens, cache, *, positions=None,
               prefix_embeds=None, moe_mode: str = "ep", valid=None):
    """Causal forward that fills the cache.

    Returns (last-position logits (B, V) fp32, cache).

    `valid` (B, S_tokens) marks real (non-pad) token positions; only
    meaningful for attention families (recurrent state ingests every
    position, so recurrent archs must prefill exact-length prompts)."""
    x = _embed(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    if positions is None:
        positions = _default_positions(b, s)
    if valid is not None and cfg.family == "vlm" and valid.shape[1] != s:
        valid = jnp.concatenate(
            [jnp.ones((b, s - valid.shape[1]), bool), valid], axis=1)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(h, inp):
            lp, c = inp
            h2, c2 = _attn_block_prefill(lp, cfg, h, positions, c, moe_mode=moe_mode,
                                         valid=valid)
            return h2, c2
        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif cfg.family == "ssm":
        def body(h, inp):
            lp, st = inp
            h2, st2 = rwkv6.block(lp, cfg, h, st)
            return h2, st2
        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif cfg.family == "hybrid":
        pattern, n_groups, tail = _hybrid_layout(cfg)

        def group_body(h, inp):
            outs = {}
            for i, kind in enumerate(pattern):
                lp = inp[f"{i}_{kind}"]
                if kind == "attn":
                    h, c2 = _attn_block_prefill(lp, cfg, h, positions, inp[f"cache_{i}"],
                                                moe_mode=moe_mode)
                else:
                    h, c2 = _rglru_block_apply(lp, cfg, h, inp[f"cache_{i}"], decode=False)
                outs[f"cache_{i}"] = c2
            return h, outs

        xs = {f"{i}_{kind}": params["blocks"][f"{i}_{kind}"] for i, kind in enumerate(pattern)}
        xs.update({f"cache_{i}": cache[f"{i}_{kind}"] for i, kind in enumerate(pattern)})
        x, new_stacked = jax.lax.scan(group_body, x, xs)
        new_cache = {f"{i}_{kind}": new_stacked[f"cache_{i}"] for i, kind in enumerate(pattern)}
        new_tail = []
        for tp, st, kind in zip(params["tail"], cache["tail"], tail,
                                strict=True):
            if kind == "attn":
                x, st2 = _attn_block_prefill(tp, cfg, x, positions, st, moe_mode=moe_mode)
            else:
                x, st2 = _rglru_block_apply(tp, cfg, x, st, decode=False)
            new_tail.append(st2)
        new_cache["tail"] = new_tail
        cache = new_cache
    else:
        raise ValueError(cfg.family)

    vlm_valid = valid
    if cfg.family == "vlm" and valid is not None and valid.shape[1] != x.shape[1]:
        b = x.shape[0]
        vlm_valid = jnp.concatenate(
            [jnp.ones((b, x.shape[1] - valid.shape[1]), bool), valid], axis=1)
    return _last_position_logits(params, cfg, x, vlm_valid), cache


def lm_decode_step(params, cfg: ModelConfig, token, pos, cache, *,
                   prefix_embeds=None, moe_mode: str = "ep"):
    """One-token decode. token: (B,) int32; pos: (B,) int32.

    Returns (logits (B, V) fp32, new cache).
    """
    x = params["embed"][token][:, None, :]  # (B,1,D)
    if cfg.family == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(h, inp):
            lp, c = inp
            h2, c2 = _attn_block_decode(lp, cfg, h, pos, c, moe_mode=moe_mode)
            return h2, c2
        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif cfg.family == "ssm":
        def body(h, inp):
            lp, st = inp
            h2, st2 = rwkv6.block(lp, cfg, h, st)
            return h2, st2
        x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    elif cfg.family == "hybrid":
        pattern, n_groups, tail = _hybrid_layout(cfg)

        def group_body(h, inp):
            outs = {}
            for i, kind in enumerate(pattern):
                lp = inp[f"{i}_{kind}"]
                if kind == "attn":
                    h, c2 = _attn_block_decode(lp, cfg, h, pos, inp[f"cache_{i}"],
                                               moe_mode=moe_mode)
                else:
                    h, c2 = _rglru_block_apply(lp, cfg, h, inp[f"cache_{i}"], decode=True)
                outs[f"cache_{i}"] = c2
            return h, outs

        xs = {f"{i}_{kind}": params["blocks"][f"{i}_{kind}"] for i, kind in enumerate(pattern)}
        xs.update({f"cache_{i}": cache[f"{i}_{kind}"] for i, kind in enumerate(pattern)})
        x, new_stacked = jax.lax.scan(group_body, x, xs)
        new_cache = {f"{i}_{kind}": new_stacked[f"cache_{i}"] for i, kind in enumerate(pattern)}
        new_tail = []
        for tp, st, kind in zip(params["tail"], cache["tail"], tail,
                                strict=True):
            if kind == "attn":
                x, st2 = _attn_block_decode(tp, cfg, x, pos, st, moe_mode=moe_mode)
            else:
                x, st2 = _rglru_block_apply(tp, cfg, x, st, decode=True)
            new_tail.append(st2)
        new_cache["tail"] = new_tail
        cache = new_cache
    else:
        raise ValueError(cfg.family)

    return _unembed(params, cfg, x)[:, 0, :], cache
