"""Qwen3-4B [hf:Qwen/Qwen3-4B, config.json]: dense, GQA kv=8, qk_norm,
tied input/output embeddings (``tie_word_embeddings: true``)."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    arch_id="qwen3-4b",
    family="dense",
    num_layers=36,
    d_model=2560,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=9728,
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1_000_000.0,
    mlp_activation="swiglu",
    tie_embeddings=True,
)
