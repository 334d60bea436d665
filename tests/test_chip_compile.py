"""Compile the main path's Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel's body as plain JAX, so it never meets the
chip compiler's tiling and VMEM rules.  These tests compile for a v5e
chip that is described, not attached (the TPU compiler ships with
libtpu), at qwen3-4b decode widths: a refused block shape fails here,
before any chip time is spent.  Nothing runs, so nothing is timed.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and every xdist worker imports
every test file.
"""
import dataclasses
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models import get_api, paged

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "bench"))
from benchlib.readings import KERNEL_OP  # noqa: E402

# qwen3-4b decode at the paged engine's shapes: 16 slots, GQA 32/8 heads of
# 128, 16-token pages, a 1,024-page pool and 64 pages (1,024 tokens) per
# sequence.  The kernel test adds the head shapes of other families.
B, H, KV, D = 16, 32, 8, 128
PAGE, NUM_PAGES, PAGES_PER_SEQ = 16, 1024, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("h,kv,d", [
    (H, KV, D),      # qwen3-4b: 128 key rows a page
    (64, 4, 128),    # qwen3-moe-235b-a22b: G = 16, 64 key rows a page
    (32, 8, 120),    # h2o-danube-3-4b: a head narrower than the lanes
    (16, 1, 256),    # recurrentgemma-9b: MQA, 16 key rows a page
])
@pytest.mark.parametrize("kv_quant", ["off", "int8"])
def test_paged_decode_attention_compiles_for_v5e(one_chip, kv_quant, h, kv,
                                                 d):
    quantized = kv_quant == "int8"
    page_dtype = jnp.int8 if quantized else jnp.bfloat16
    pages = (NUM_PAGES, PAGE, kv, d)
    args = (_sds(one_chip, (B, h, d), jnp.bfloat16),
            _sds(one_chip, pages, page_dtype),
            _sds(one_chip, pages, page_dtype),
            _sds(one_chip, (B, PAGES_PER_SEQ), jnp.int32),
            _sds(one_chip, (B,), jnp.int32))
    scales = {}
    if quantized:
        scales = {k: _sds(one_chip, pages[:-1], jnp.float32)
                  for k in ("k_scales", "v_scales")}
    compiled = paged_decode_attention.lower(*args, **scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_attention_keeps_its_name_on_the_v5e(one_chip):
    """The trace names each op by its HLO instruction; the kernel's
    roofline reader finds the kernel by this name."""
    pages = (NUM_PAGES, PAGE, KV, D)
    compiled = paged_decode_attention.lower(
        _sds(one_chip, (B, H, D), jnp.bfloat16),
        _sds(one_chip, pages, jnp.bfloat16),
        _sds(one_chip, pages, jnp.bfloat16),
        _sds(one_chip, (B, PAGES_PER_SEQ), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile()
    names = re.findall(r"%([\w-]+)\.\d+ = [^\n]* custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"',
                       compiled.as_text())
    assert names == [KERNEL_OP] == ["paged_decode_attention"]


@pytest.mark.parametrize("kv_quant", ["off", "int8"])
def test_paged_decode_step_compiles_for_v5e(one_chip, kv_quant):
    """One layer of the engine's decode step, kernel inside the layer scan,
    at qwen3-4b's published widths (tied 151,936-row vocabulary)."""
    cfg = dataclasses.replace(REGISTRY["qwen3-4b"], num_layers=1)
    api = get_api(cfg)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: paged.init_paged_cache(cfg, NUM_PAGES, PAGE,
                                       kv_quant=kv_quant)))

    def step(params, token, pos, cache, tables):
        return api.decode_paged(params, token, pos, cache, tables,
                                attn_impl="kernel")

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B,), jnp.int32), cache,
        _sds(one_chip, (B, PAGES_PER_SEQ), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
