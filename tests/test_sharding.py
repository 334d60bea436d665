"""Sharding rules + single-device lower/compile of the sharded step functions.

The full 512-device dry-run runs via `python -m repro.launch.dryrun` (it must
own XLA_FLAGS before jax init); these tests validate the same plumbing on the
1-device mesh so pytest exercises build_combo end to end.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from conftest import tiny
from repro.models import get_api, sharding as shd


def fake_mesh(data=4, model=4):
    """Abstract mesh for spec computation only (no devices needed)."""
    devs = np.empty((data, model), dtype=object)
    for i in range(data):
        for j in range(model):
            devs[i, j] = jax.devices()[0]
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_param_rules_hit_expected_axes():
    cfg = tiny("qwen3-8b", d_model=128, num_heads=8, head_dim=16,
               num_kv_heads=4, d_ff=256, vocab_size=256)
    api = get_api(cfg)
    params = jax.eval_shape(api.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = shd.param_specs(params, mesh)
    flat = {shd._path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert flat["blocks/attn/wq"] == P(None, "data", "model")
    assert flat["blocks/attn/wo"] == P(None, "model", "data")
    assert flat["blocks/mlp/wi_gate"] == P(None, "data", "model")
    assert flat["embed"] == P("model", "data")
    assert flat["lm_head"] == P("data", "model")
    assert flat["final_norm/scale"] == P()


def test_param_rules_moe_expert_parallel():
    cfg = tiny("qwen3-moe-235b-a22b", d_model=128, num_heads=8, head_dim=16,
               num_kv_heads=4, num_experts=4, moe_d_ff=64, vocab_size=256)
    api = get_api(cfg)
    params = jax.eval_shape(api.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = shd.param_specs(params, mesh)
    flat = {shd._path_str(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(specs)[0]}
    assert flat["blocks/moe/w_gate"] == P(None, "model", "data", None)
    assert flat["blocks/moe/w_down"] == P(None, "model", "data", None)


def test_divisibility_fallback_replicates():
    """head_dim 120 (danube) etc: dims not divisible by the mesh axis size
    must silently fall back to replication, not crash."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sizes = {"data": 16, "model": 16}
    spec = shd._spec_for("blocks/attn/wq", (3, 120), sizes)
    assert spec == P(None, None)  # 3 % 16 != 0, 120 % 16 != 0


def _abstract_mesh(data=16, model=16):
    from jax.sharding import AbstractMesh
    return AbstractMesh((data, model), ("data", "model"))


def test_cache_specs_batch_and_feature_sharded():
    cfg = tiny("qwen3-8b", num_kv_heads=2, head_dim=16)
    api = get_api(cfg)
    mesh = _abstract_mesh()
    cache = jax.eval_shape(lambda: api.init_cache(16, 64))
    specs = shd.cache_specs(cache, mesh)
    k_spec = specs.k
    assert k_spec[1] in ("data", ("data",))  # batch axis
    # largest remaining axis (the sequence axis) gets the model TP shard
    # (§Perf iter 2: head_dim sharding forced GQA-reshape resharding)
    assert k_spec[2] == "model"
    assert specs.pos[1] in ("data", ("data",))
    assert specs.pos[-1] is None   # int32 positions never TP-sharded


def test_cache_specs_long_context_fallback():
    """B=1: batch unshardable -> sequence axis sharded over data."""
    cfg = tiny("h2o-danube-3-4b", sliding_window=None, num_kv_heads=2, head_dim=16)
    api = get_api(cfg)
    mesh = _abstract_mesh()
    cache = jax.eval_shape(lambda: api.init_cache(1, 512))
    specs = shd.cache_specs(cache, mesh)
    assert specs.k[1] is None
    assert specs.k[2] == "data"  # context-parallel over data
    assert "model" in specs.k    # plus a TP axis elsewhere


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-4b", "train_4k"),
    ("qwen3-moe-235b-a22b", "decode_32k"),
    ("rwkv6-3b", "long_500k"),
    ("seamless-m4t-medium", "prefill_32k"),
])
def test_build_combo_lowers_on_unit_mesh(arch, shape, monkeypatch):
    """build_combo must lower+compile on the degenerate 1x1 mesh with tiny
    shape overrides (full-size validation is the dryrun launcher's job)."""
    import dataclasses

    from repro.configs import SHAPES
    from repro.launch import dryrun

    cfg = tiny(arch)
    sh = dataclasses.replace(SHAPES[shape], seq_len=64, global_batch=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn, args, in_shard, out_shard, donate = dryrun.build_combo(cfg, sh, mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard,
                           donate_argnums=donate).lower(*args).compile()
    ca = compiled.cost_analysis()
    assert ca.get("flops", 0) > 0


def test_activation_sharding_hook_noop_without_spec():
    x = jnp.ones((2, 4, 8))
    shd.set_activation_sharding(None)
    assert shd.constrain_activation(x) is x
