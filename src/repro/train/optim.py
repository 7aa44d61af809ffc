"""AdamW + cosine schedule on the flat ZeRO shards.

Optimizer states live in exactly the parameter storage sharding
(P(None,'model','data')), i.e. ZeRO-1/3: each rank updates only its flat
shard. All math is elementwise, so it runs unchanged inside shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moment_dtype: str = "float32"   # bf16 halves optimizer memory (noted
                                    # in DESIGN for the 314B/400B configs)


def lr_schedule(cfg: OptimConfig, step: jnp.ndarray) -> jnp.ndarray:
    s = step.astype(jnp.float32)
    warm = s / jnp.maximum(cfg.warmup_steps, 1)
    t = (s - cfg.warmup_steps) / jnp.maximum(
        cfg.total_steps - cfg.warmup_steps, 1)
    t = jnp.clip(t, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * t))
    return cfg.lr * jnp.where(s < cfg.warmup_steps, warm, cos)


def init_opt_state(params: Any, cfg: OptimConfig,
                   grad_ef: bool = False, qgrad_ef: bool = False,
                   fsdp: int = 1) -> Dict[str, Any]:
    dt = jnp.dtype(cfg.moment_dtype)
    # each state leaf is made where its parameter lives (sharded over
    # the mesh), never whole on the default device
    where = lambda p: getattr(p, "sharding", None)
    zeros = lambda p: jnp.zeros(p.shape, dt, device=where(p))
    state = {"m": jax.tree_util.tree_map(zeros, params),
             "v": jax.tree_util.tree_map(zeros, params),
             "step": jnp.zeros((), jnp.int32)}
    if grad_ef:
        # error-feedback residual for the compressed grad AllReduce:
        # lives with the optimizer state (same ZeRO sharding as the
        # grads it corrects), donated and checkpointed alongside m/v
        ef = lambda p: jnp.zeros(p.shape, jnp.float32, device=where(p))
        state["ef"] = jax.tree_util.tree_map(ef, params)
    if qgrad_ef:
        # error-feedback residual for the quantized gradient RS over
        # ``data``: the residual lives at the RS *input* shape — the
        # full flat length, i.e. fsdp x the stored shard — with dim2
        # sharded over ``data`` so the per-rank view matches the
        # full-length delta gradients (see train_step.py)
        qef = lambda p: jnp.zeros(
            (p.shape[0], p.shape[1], p.shape[2] * fsdp), jnp.float32,
            device=where(p))
        state["qef"] = jax.tree_util.tree_map(qef, params)
    return state


def global_grad_norm(grads: Any) -> jnp.ndarray:
    """Local-shard sum of squares; caller psums across the mesh."""
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree_util.tree_leaves(grads))
    return sq


def adamw_update(params: Any, grads: Any, state: Dict, cfg: OptimConfig,
                 grad_norm: jnp.ndarray):
    """One AdamW step on the local shards. grad_norm: global L2 norm."""
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(grad_norm, 1e-12))
    dt = jnp.dtype(cfg.moment_dtype)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32) * clip
        m2 = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * gf
        v2 = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * gf * gf
        mh = m2 / (1 - cfg.b1 ** step.astype(jnp.float32))
        vh = v2 / (1 - cfg.b2 ** step.astype(jnp.float32))
        pf = p.astype(jnp.float32)
        pf = pf - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                        + cfg.weight_decay * pf)
        return pf.astype(p.dtype), m2.astype(dt), v2.astype(dt)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, lr
