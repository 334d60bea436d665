"""Tier-1 smoke: the default pipeline rolls out through the paged engine.

The heavyweight end-to-end training runs live in test_system.py (slow
tier); this file keeps a fast blocking check that `launch/pipeline.py`
builds the PAGED engine by default for attention families, the producer's
group submissions flow end-to-end, and the slot engine stays selectable.
"""
import jax
import numpy as np
import pytest

from conftest import tiny
from repro.launch.pipeline import (PipelineSettings, build_rlvr_pipeline,
                                   make_rollout_engine, make_rollout_fleet)
from repro.models import get_api
from repro.rollout.engine import DecodeEngine
from repro.rollout.paged_engine import PagedDecodeEngine, platform_attn_impl

pytestmark = pytest.mark.timeout(240)

MODEL = tiny("qwen3-4b", vocab_size=32)


def test_default_pipeline_is_paged_and_rolls_out():
    s = PipelineSettings(async_generation_ratio=1, rollout_batch_size=4,
                         num_return_sequences_in_group=2, num_slots=4,
                         max_new_tokens=4, max_seq_len=32, page_size=8,
                         prefill_chunk=8)
    pipe = build_rlvr_pipeline(MODEL, s)
    assert isinstance(pipe.engine, PagedDecodeEngine)
    pipe.proxy.start()
    pipe.producer.start()
    try:
        batch = pipe.buffer.get_batch(4, timeout=120)
    finally:
        pipe.shutdown()
    assert len(batch) == 4
    for b in batch:
        assert len(np.asarray(b.response_tokens)) > 0
        assert b.reward is not None
        assert len(np.asarray(b.logprobs)) == len(np.asarray(b.response_tokens))
    # the producer submitted GRPO groups, the engine forked them (COW)
    assert pipe.engine.total_groups_forked >= 1
    pipe.engine.audit_pages()


def test_engine_selection():
    api = get_api(MODEL)
    params = api.init(jax.random.PRNGKey(0))
    assert isinstance(make_rollout_engine(api, params, PipelineSettings()),
                      PagedDecodeEngine)
    assert isinstance(
        make_rollout_engine(api, params,
                            PipelineSettings(rollout_engine="slot")),
        DecodeEngine)
    with pytest.raises(ValueError, match="rollout_engine"):
        make_rollout_engine(api, params,
                            PipelineSettings(rollout_engine="bogus"))


@pytest.mark.parametrize("platform,impl", [("tpu", "kernel"), ("cpu", "ref"),
                                           ("gpu", "ref")])
def test_decode_attention_follows_the_platform(platform, impl):
    class Device:
        pass

    dev = Device()
    dev.platform = platform
    assert platform_attn_impl(dev) == impl


def test_engine_attention_default_and_validation():
    api = get_api(MODEL)
    params = api.init(jax.random.PRNGKey(0))
    eng = make_rollout_engine(api, params, PipelineSettings())
    assert eng.attn_impl == platform_attn_impl() == "ref"  # tests run on CPU
    with pytest.raises(ValueError, match="attn_impl"):
        PagedDecodeEngine(api, params, attn_impl="bogus")


@pytest.mark.parametrize("replicas", [1, 3])
def test_fleet_places_each_replica_on_its_device(replicas):
    """Replica i lives on jax.devices()[i % n] — params, page pool and
    tables — and a weight sync lands the new tree on that device."""
    api = get_api(MODEL)
    params = api.init(jax.random.PRNGKey(0))
    s = PipelineSettings(num_slots=2 * replicas, num_rollout_replicas=replicas,
                         max_seq_len=32, page_size=8, prefill_chunk=8)
    engines, _, _ = make_rollout_fleet(api, params, s)
    devices = jax.devices()
    assert len(engines) == replicas
    for i, e in enumerate(engines):
        want = {devices[i % len(devices)]}
        e.update_weights(jax.tree_util.tree_map(lambda a: a + 0, params))
        for tree in (e.params, e.cache, e.block_tables, e.pos):
            assert all(a.devices() == want
                       for a in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(from_env, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing sets a directory in code;
    without it the cache goes to the checkout's git-ignored .jax_cache/."""
    from repro.launch import compile_cache
    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert updates == []
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert updates == [("jax_compilation_cache_dir", path)]
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro").is_dir()
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()


def test_engine_selection_recurrent_family_falls_back_to_slot():
    cfg = tiny("rwkv6-3b", vocab_size=32)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0))
    assert isinstance(make_rollout_engine(api, params, PipelineSettings()),
                      DecodeEngine)
