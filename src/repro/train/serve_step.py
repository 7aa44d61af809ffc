"""Serving: prefill (full-sequence forward) and single-token decode.

Decode carries per-block caches (ring-buffer KV for attention, recurrent
state for RG-LRU / xLSTM). The model-axis activation AllReduces run
through the paper's quantized two-step — the TTFT site of Fig. 2.

Cache sharding: batch dims follow the (pod, data) batch sharding;
rank-distinct dims (sharded kv heads, LRU channels, LSTM heads) carry the
``model`` axis; replicated-kv caches and slot tables replicate.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.policy import CommPolicy
from repro.models.config import ModelConfig
from repro.models.layers import vocab_parallel_logits
from repro.models.model import (forward, greedy_next_token, init_caches,
                                param_groups)
from repro.parallel.plan import ShardingPlan
from repro.parallel.shardings import STORE_SPEC, store_spec
from repro.train.train_step import batch_spec


def _head(hidden, unemb, cfg: ModelConfig, plan: ShardingPlan,
          logits: bool):
    """Greedy next token (B,), plus with ``logits`` the last-position
    vocab-parallel logits (B, v_loc); padded vocab columns are -inf."""
    with jax.named_scope("head"):
        nt = greedy_next_token(hidden, unemb, cfg, plan)
        if not logits:
            return nt
        lg = vocab_parallel_logits(hidden[:, -1], unemb, cfg.logit_softcap)
        col = (jnp.arange(plan.v_loc)[None, :]
               + jax.lax.axis_index("model") * plan.v_loc)
        return nt, jnp.where(col < cfg.vocab, lg, -jnp.inf)


def _logit_spec(bspec) -> P:
    return P(bspec[0] if len(bspec) else None, "model")


def make_prefill(cfg: ModelConfig, plan: ShardingPlan, policy: CommPolicy,
                 mesh, global_batch: int,
                 window_override: Optional[int] = None,
                 logits: bool = False):
    """Full-sequence forward -> next token at the last position (B,).

    With ``logits`` it returns ``(token, logits)``: the last-position
    logits (B, tp * v_loc), vocab sharded over ``model``.
    """
    dtype = jnp.dtype(cfg.dtype)
    bspec = batch_spec(global_batch, mesh)

    def prefill(store, batch):
        hidden, unemb, _, _ = forward(
            store, batch["tokens"], cfg, plan, policy,
            enc_embeds=batch.get("enc_embeds"),
            window_override=window_override, dtype=dtype, serving=True)
        return _head(hidden, unemb, cfg, plan, logits)

    bs = {"tokens": bspec}
    if cfg.is_enc_dec or cfg.has_cross:
        bs["enc_embeds"] = bspec
    out_specs = (bspec, _logit_spec(bspec)) if logits else bspec
    sm = jax.shard_map(prefill, mesh=mesh,
                       in_specs=(store_spec(plan), bs),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(sm)


def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_sharded(global_batch: int, mesh) -> bool:
    bspec = batch_spec(global_batch, mesh)
    return len(bspec) > 0 and bspec[0] is not None


def _local_batch(global_batch: int, mesh) -> int:
    if not _batch_sharded(global_batch, mesh):
        return global_batch
    size = 1
    for a in _dp_axes(mesh):
        size *= mesh.shape[a]
    if global_batch % size != 0:
        # The cache tree shards its batch dims over ALL dp axes
        # (decode_cache_specs), so a batch that train's batch_spec
        # would merely shard over ``data`` cannot be served: the old
        # floor division silently dropped the remainder rows.
        raise ValueError(
            f"global_batch={global_batch} does not divide the serving "
            f"(pod x data) slice count {size} "
            f"(mesh {dict(mesh.shape)}); pad the batch or shrink the "
            f"dp axes — floor division would silently drop "
            f"{global_batch % size} row(s)")
    return global_batch // size


def _cache_leaf_rule(path, leaf, cfg, plan, bspec_axes, stacked_group):
    """-> (model_dim or None, batch_dim or None) for one cache leaf."""
    keys = [getattr(p, "key", None) or getattr(p, "idx", None)
            for p in path]
    name = keys[-1]
    sub = keys[-2] if len(keys) >= 2 else None
    stacked = keys[0] == "pattern"
    off = 1 if stacked else 0
    if name == "pos":
        return None, None
    if name == "slot_pos":
        # sequence-sharded ring (replicate kv mode): table is sharded
        return (off if plan.kv_mode != "shard" else None), None
    bdim = off
    if sub == "kv":                      # k / v: heads sharded (shard
        # mode) or ring positions sharded (replicate mode)
        mdim = off + 2 if plan.kv_mode == "shard" else off + 1
    elif sub == "rg":                    # h (B,W) / conv (B,cw-1,W)
        mdim = off + (2 if name == "conv" else 1)
    else:                                # st: lstm states, head dim 1
        mdim = off + 1
    return mdim, bdim


def decode_cache_specs(cfg: ModelConfig, plan: ShardingPlan, mesh,
                       global_batch: int, cache_len: int):
    """Global (ShapeDtypeStructs, PartitionSpecs) for the cache tree."""
    b_loc = _local_batch(global_batch, mesh)
    b_shard = _batch_sharded(global_batch, mesh)
    dp = _dp_axes(mesh)
    dtype = jnp.dtype(cfg.dtype)
    shapes = jax.eval_shape(
        lambda: init_caches(cfg, plan, b_loc, cache_len, dtype))

    def spec_of(path, leaf):
        mdim, bdim = _cache_leaf_rule(path, leaf, cfg, plan, dp, None)
        spec = [None] * leaf.ndim
        if mdim is not None:
            spec[mdim] = "model"
        if bdim is not None and b_shard:
            spec[bdim] = dp
        return P(*spec)

    def glob_of(path, leaf):
        mdim, bdim = _cache_leaf_rule(path, leaf, cfg, plan, dp, None)
        shape = list(leaf.shape)
        if mdim is not None:
            shape[mdim] *= plan.tp
        if bdim is not None and b_shard:
            shape[bdim] = global_batch
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    specs = jax.tree_util.tree_map_with_path(spec_of, shapes)
    gshapes = jax.tree_util.tree_map_with_path(glob_of, shapes)
    return gshapes, specs


def make_decode_step(cfg: ModelConfig, plan: ShardingPlan,
                     policy: CommPolicy, mesh, global_batch: int,
                     cache_len: int,
                     window_override: Optional[int] = None,
                     logits: bool = False):
    """serve_step: (store, caches, batch) -> (next (B,), new caches).

    With ``logits`` the first output is ``(next, logits)`` as in
    :func:`make_prefill`.
    """
    dtype = jnp.dtype(cfg.dtype)
    bspec = batch_spec(global_batch, mesh)
    _, cache_specs = decode_cache_specs(cfg, plan, mesh, global_batch,
                                        cache_len)

    def step(store, caches, batch):
        hidden, unemb, _, new_caches = forward(
            store, batch["tokens"], cfg, plan, policy,
            enc_embeds=batch.get("enc_embeds"), caches=caches,
            window_override=window_override, dtype=dtype, serving=True)
        return _head(hidden, unemb, cfg, plan, logits), new_caches

    bs = {"tokens": bspec}
    if cfg.is_enc_dec or cfg.has_cross:
        bs["enc_embeds"] = bspec
    head_spec = (bspec, _logit_spec(bspec)) if logits else bspec
    sm = jax.shard_map(step, mesh=mesh,
                       in_specs=(store_spec(plan), cache_specs, bs),
                       out_specs=(head_spec, cache_specs), check_vma=False)
    return jax.jit(sm, donate_argnums=(1,))


def make_cache_init(cfg: ModelConfig, plan: ShardingPlan, mesh,
                    global_batch: int, cache_len: int):
    """jit'd global cache initializer (per-rank init via shard_map)."""
    b_loc = _local_batch(global_batch, mesh)
    dtype = jnp.dtype(cfg.dtype)
    _, cache_specs = decode_cache_specs(cfg, plan, mesh, global_batch,
                                        cache_len)

    def init():
        return init_caches(cfg, plan, b_loc, cache_len, dtype)

    sm = jax.shard_map(init, mesh=mesh, in_specs=(),
                       out_specs=cache_specs, check_vma=False)
    return jax.jit(sm)
