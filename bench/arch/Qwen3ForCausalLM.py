"""Qwen3 dense (``Qwen3ForCausalLM``): GQA attention with an RMSNorm on
each head's q and k, half-split RoPE, a SwiGLU MLP, RMSNorm before each.

An architecture module: what ``harness.load_arch`` finds for a
configuration whose ``architectures[0]`` names it.  Only
``program_config`` imports the program, and only when it is called.
"""
from __future__ import annotations

import jax

from benchlib import reference as R
from benchlib.counts import Dims


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file (Hugging Face
    key names)."""
    from repro.models.config import ModelConfig
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (hidden_act silu) configurations")
    return ModelConfig(
        arch_id=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"], qk_norm=True,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        mlp_activation="swiglu", tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"])


def dims(c: dict) -> Dims:
    return Dims(d_model=c["hidden_size"], layers=c["num_hidden_layers"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"])


def layer_specs(d: Dims):
    """(path, shape, kind, scale) of one transformer block."""
    hd = d.head_dim
    return [
        (("ln1", "scale"), (d.d_model,), "norm", None),
        (("ln2", "scale"), (d.d_model,), "norm", None),
        (("attn", "wq"), (d.d_model, d.heads * hd), "mat", d.d_model ** -0.5),
        (("attn", "wk"), (d.d_model, d.kv_heads * hd), "mat", d.d_model ** -0.5),
        (("attn", "wv"), (d.d_model, d.kv_heads * hd), "mat", d.d_model ** -0.5),
        (("attn", "wo"), (d.heads * hd, d.d_model), "mat", (d.heads * hd) ** -0.5),
        (("attn", "q_norm"), (hd,), "norm", None),
        (("attn", "k_norm"), (hd,), "norm", None),
        (("mlp", "wi_gate"), (d.d_model, d.d_ff), "mat", d.d_model ** -0.5),
        (("mlp", "wi_up"), (d.d_model, d.d_ff), "mat", d.d_model ** -0.5),
        (("mlp", "wo"), (d.d_ff, d.d_model), "mat", d.d_ff ** -0.5),
    ]


def top_specs(d: Dims, tied: bool):
    specs = [(("embed",), (d.vocab, d.d_model), "mat",
              d.d_model ** -0.5 if tied else 1.0),
             (("final_norm", "scale"), (d.d_model,), "norm", None)]
    if not tied:
        specs.append((("lm_head",), (d.d_model, d.vocab), "mat",
                      d.d_model ** -0.5))
    return specs


def block(w, x, dims: Dims, i, theta: float, eps: float, cast: R.Cast = None):
    """Layer ``i`` over x: (B, T, D) at positions 0..T-1; no extra loss."""
    b, t, _ = x.shape
    hd, kv = dims.head_dim, dims.kv_heads
    h = R.rmsnorm(x, w["ln1"]["scale"], eps)
    a = w["attn"]
    q = R.mm(h, a["wq"], cast).reshape(b, t, dims.heads, hd)
    k = R.mm(h, a["wk"], cast).reshape(b, t, kv, hd)
    v = R.mm(h, a["wv"], cast).reshape(b, t, kv, hd)
    q = R.rope(R.rmsnorm(q, a["q_norm"], eps), theta)
    k = R.rope(R.rmsnorm(k, a["k_norm"], eps), theta)
    x = x + R.mm(R.attention(q, k, v, cast), a["wo"], cast)
    h = R.rmsnorm(x, w["ln2"]["scale"], eps)
    m = w["mlp"]
    y = jax.nn.silu(R.mm(h, m["wi_gate"], cast)) * R.mm(h, m["wi_up"], cast)
    return x + R.mm(y, m["wo"], cast), R.NO_EXTRA_LOSS
