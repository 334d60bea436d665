"""AdamW in pure JAX with fp32 master weights and global-norm clipping."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    learning_rate: float = 1e-6   # paper appendix A.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0     # paper appendix A.1
    grad_clip: float = 1.0
    warmup_steps: int = 20        # paper appendix A.1


def init_opt_state(params) -> Dict[str, Any]:
    # copy=True: an fp32 leaf's astype would alias the param's buffer, and
    # the trainer donates the optimizer state but not the params.
    f32 = lambda p: jnp.array(p, jnp.float32, copy=True)
    return {
        "step": jnp.zeros((), jnp.int32),
        "master": jax.tree_util.tree_map(f32, params),
        "m": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "v": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
    }


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(tree)))


def adamw_update(grads, opt_state, cfg: OptConfig, param_dtypes=None):
    """Returns (new_params_in_model_dtype, new_opt_state, metrics).

    param_dtypes: tree of jnp dtypes matching params (norm scales stay fp32,
    weights bf16). Defaults to bf16 everywhere if not given.
    """
    step = opt_state["step"] + 1
    lr = cfg.learning_rate * jnp.minimum(1.0, step / max(cfg.warmup_steps, 1))

    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) if cfg.grad_clip else 1.0
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    if param_dtypes is None:
        param_dtypes = jax.tree_util.tree_map(lambda _: jnp.bfloat16,
                                              opt_state["master"])

    # single fused per-leaf pass: chaining whole-tree tree_maps keeps ~6 fp32
    # param-sized trees live simultaneously (§Perf iter 7c — dozens of GiB at
    # 235B scale); per-leaf chains let XLA free each intermediate immediately.
    def upd_leaf(p_master, m_, v_, g, dt):
        gf = g.astype(jnp.float32) * scale
        m2 = b1 * m_ + (1 - b1) * gf
        v2 = b2 * v_ + (1 - b2) * jnp.square(gf)
        new_master = p_master - lr * ((m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps)
                                      + cfg.weight_decay * p_master)
        return {"master": new_master, "m": m2, "v": v2,
                "param": new_master.astype(dt)}

    fused = jax.tree_util.tree_map(
        upd_leaf, opt_state["master"], opt_state["m"], opt_state["v"], grads,
        param_dtypes, is_leaf=lambda x: isinstance(x, jnp.dtype) or hasattr(x, "shape"))

    def pick(key):
        return jax.tree_util.tree_map(lambda d: d[key], fused,
                                      is_leaf=lambda x: isinstance(x, dict)
                                      and "master" in x)

    new_state = {"step": step, "master": pick("master"),
                 "m": pick("m"), "v": pick("v")}
    return pick("param"), new_state, {"grad_norm": gnorm, "lr": lr}
