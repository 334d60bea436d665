"""Per-architecture smoke tests (deliverable f) + prefill/decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny
from repro.algos import LossConfig
from repro.configs import REGISTRY, list_archs
from repro.models import get_api
from repro.train.optimizer import OptConfig
from repro.train import trainer as trainer_mod
from repro.train.trainer import make_train_state, make_train_step

ALL_ARCHS = list_archs()


def make_batch(cfg, b, s, key):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patches"] = (jax.random.normal(
            jax.random.fold_in(key, 1), (b, cfg.num_image_tokens, cfg.d_model))
            * 0.1).astype(jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = (jax.random.normal(
            jax.random.fold_in(key, 1), (b, cfg.encoder_frames, cfg.d_model))
            * 0.1).astype(jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward(arch, rng_key):
    """Reduced variant of the same family: one forward, shapes + finiteness."""
    cfg = REGISTRY[arch].smoke()
    assert cfg.num_layers <= max(2, len(cfg.block_pattern or ())) and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.num_experts <= 4
    api = get_api(cfg)
    params = api.init(rng_key)
    b, s = 2, 16
    batch = make_batch(cfg, b, s, jax.random.fold_in(rng_key, 7))
    logits, aux = api.apply(params, batch)
    expect_s = s + (cfg.num_image_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (b, expect_s, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.slow
@pytest.mark.timeout(120)
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch, rng_key):
    """One RL train step on the reduced variant: finite loss, params move."""
    cfg = tiny(arch)
    api = get_api(cfg)
    state = make_train_state(api, rng_key)
    step = make_train_step(api, LossConfig(pg_variant="ppo"),
                           OptConfig(learning_rate=1e-2, warmup_steps=1),
                           remat=True, moe_mode="dense" if cfg.is_moe else "ep")
    b, s = 2, 16
    key = jax.random.fold_in(rng_key, 3)
    batch = make_batch(cfg, b, s, key)
    tok_s = batch["tokens"].shape[1]
    mask = jnp.zeros((b, tok_s)).at[:, tok_s // 2:].set(1.0)
    lp = -jnp.abs(jax.random.normal(key, (b, tok_s)))
    batch.update(mask=mask, advantages=mask * 0.5, old_logprobs=lp,
                 prox_logprobs=lp, ref_logprobs=lp,
                 is_positive=jnp.ones((b,)))
    new_state, metrics = jax.jit(step)(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    before = jax.tree_util.tree_leaves(state["params"])[0]
    after = jax.tree_util.tree_leaves(new_state["params"])[0]
    assert not np.allclose(np.asarray(before, np.float32),
                           np.asarray(after, np.float32))


@pytest.mark.slow
@pytest.mark.timeout(120)
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_full(arch, rng_key):
    """Engine paths == teacher-forcing forward, token by token."""
    cfg = tiny(arch)
    api = get_api(cfg)
    params = api.init(jax.random.fold_in(rng_key, hash(arch) % 1000))
    b, s = 2, 12
    batch = make_batch(cfg, b, s, jax.random.fold_in(rng_key, 11))
    mm = "dense" if cfg.is_moe else "ep"
    full, _ = api.apply(params, batch, moe_mode=mm)
    off = cfg.num_image_tokens if cfg.family == "vlm" else 0

    p = s - 4
    cache = api.init_cache(b, s + 4)
    lp, cache = api.prefill(params, dict(batch, tokens=batch["tokens"][:, :p]),
                            cache, moe_mode=mm)
    assert lp.shape == (b, cfg.vocab_size)  # last-position logits only
    np.testing.assert_allclose(np.asarray(lp),
                               np.asarray(full[:, off + p - 1]),
                               rtol=2e-2, atol=2e-2)
    for t in range(p, s):
        lg, cache = api.decode_step(params, batch["tokens"][:, t],
                                    jnp.full((b,), t + off, jnp.int32), cache,
                                    moe_mode=mm)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, off + t]),
                                   rtol=2e-2, atol=2e-2)


def test_sliding_window_restricts_attention(rng_key):
    """SWA arch must differ from full attention beyond the window."""
    cfg = tiny("h2o-danube-3-4b", sliding_window=4)
    cfg_full = dataclasses.replace(cfg, sliding_window=None)
    api, api_full = get_api(cfg), get_api(cfg_full)
    params = api.init(rng_key)
    batch = make_batch(cfg, 1, 16, rng_key)
    lw, _ = api.apply(params, batch)
    lf, _ = api_full.apply(params, batch)
    # first `window` positions identical, later positions diverge
    np.testing.assert_allclose(np.asarray(lw[:, :4]), np.asarray(lf[:, :4]),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(lw[:, -1] - lf[:, -1]).max()) > 1e-4


def test_moe_capacity_vs_dense_agree_with_headroom(rng_key):
    cfg = tiny("dbrx-132b", capacity_factor=8.0)
    api = get_api(cfg)
    params = api.init(rng_key)
    batch = make_batch(cfg, 2, 16, rng_key)
    ld, _ = api.apply(params, batch, moe_mode="dense")
    le, _ = api.apply(params, batch, moe_mode="ep")
    np.testing.assert_allclose(np.asarray(ld), np.asarray(le), atol=1e-2)


@pytest.mark.parametrize("budget", [7 * 64, 1 << 27])
def test_chunked_logprobs_under_a_logits_budget(budget, monkeypatch, rng_key):
    """Whatever chunk the logits budget leaves (1 position at the small
    budget, the full 512 at the default), the chunked logprobs equal the
    full-logits ones."""
    from repro.algos import token_logprobs
    b, s, d, v = 7, 13, 16, 64
    kx, kh, kt = jax.random.split(rng_key, 3)
    feats = jax.random.normal(kx, (b, s, d))
    head = jax.random.normal(kh, (d, v))
    tokens = jax.random.randint(kt, (b, s), 0, v)
    monkeypatch.setattr(trainer_mod, "_CE_LOGITS_BUDGET", budget)
    got = trainer_mod.chunked_token_logprobs(feats, head, tokens)
    want = token_logprobs(feats[:, :-1] @ head, tokens[:, 1:])
    np.testing.assert_allclose(np.asarray(got[:, 1:]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[:, 0]).any()


@pytest.mark.parametrize("tied", [True, False])
def test_embedding_init_scale(tied, rng_key):
    """A tied table doubles as the output head, so it starts at the head's
    1/sqrt(d_model) scale; an untied input table keeps unit scale."""
    cfg = dataclasses.replace(tiny("qwen3-4b", d_model=256),
                              tie_embeddings=tied)
    params = get_api(cfg).init(rng_key)
    assert ("lm_head" in params) is not tied
    rms = float(jnp.sqrt(jnp.mean(params["embed"].astype(jnp.float32) ** 2)))
    truncnorm_std = 0.88  # truncated_normal(-2, 2)
    want = truncnorm_std * (cfg.d_model ** -0.5 if tied else 1.0)
    assert abs(rms - want) < 0.05 * want


def test_host_trainer_donates_optimizer_state_not_params(rng_key):
    """The train step updates the optimizer state in place, but the params
    handed to the rollout engines stay readable until the next sync."""
    from repro.core.types import Sample
    from repro.train.trainer import HostTrainer, TrainerConfig
    api = get_api(tiny("qwen3-4b", vocab_size=32))
    trainer = HostTrainer(api, rng_key, LossConfig("tis"),
                          OptConfig(learning_rate=1e-2, warmup_steps=1),
                          TrainerConfig(max_seq_len=16, group_size=2))
    handed_out = trainer.get_weights()
    opt_before = trainer.state["opt"]
    rng = np.random.default_rng(0)
    samples = [Sample(sample_id=i, prompt_id=i // 2, replica_idx=0,
                      prompt_tokens=rng.integers(1, 32, 4),
                      response_tokens=rng.integers(1, 32, 6),
                      logprobs=-rng.random(6), reward=float(i % 2),
                      group_id=i // 2) for i in range(4)]
    metrics = trainer.train_on_samples(samples)
    assert np.isfinite(metrics["loss"])
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(opt_before))
    for old, new in zip(jax.tree_util.tree_leaves(handed_out),
                        jax.tree_util.tree_leaves(trainer.get_weights()),
                        strict=True):
        assert not old.is_deleted()
        assert old.shape == new.shape


def test_moe_load_balance_loss_bounds(rng_key):
    cfg = tiny("qwen3-moe-235b-a22b")
    api = get_api(cfg)
    params = api.init(rng_key)
    batch = make_batch(cfg, 2, 32, rng_key)
    _, aux = api.apply(params, batch, moe_mode="ep")
    # E * sum(f_e * P_e) >= 1 with equality at perfect balance
    assert float(aux["load_balance_loss"]) >= 0.99
