"""Training step: forward/backward inside shard_map + ZeRO update.

Gradient communication map (all sites use the paper's machinery):

  within pod   reduce-scatter over ``data`` (sums DP grads and lands
               them ZeRO-sharded; this plays the "partial ReduceScatter
               inside the fast domain" role of the paper's hierarchical
               scheme). Exact by default — the FSDP gather's VJP. With
               a ``qgrad_rs`` policy the RS instead runs *explicitly*
               after ``value_and_grad`` through
               ``collectives.quantized_reduce_scatter[_ef]``: the
               backward taps full-length per-rank gradients via zero
               "delta" inputs added to the gathered weights
               (``shardings.gather_param``), so the compressed sync can
               thread an error-feedback residual pytree (optimizer
               state ``"qef"``) — something a ``custom_vjp`` can never
               do — and 4/2-bit qgrad converges instead of drifting.
  across pods  quantized two-step AllReduce over ``pod`` on the sharded
               flat grads (only 1/fsdp of the volume crosses the slow
               bridge — the Table 5 saving, realized structurally),
               with its own EF residual (``"ef"``) when ``grad_ef``.
  model axis   replicated-stored params (norms, biases, routers,
               replicated kv projections) get an exact psum to keep the
               TP copies in sync (Megatron's LN-grad all-reduce)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.collectives import (compressed_psum, compressed_psum_ef,
                                    quantized_reduce_scatter,
                                    quantized_reduce_scatter_ef)
from repro.core.comm_config import CommConfig, NO_COMPRESSION
from repro.core.policy import CommPolicy
from repro.models.config import ModelConfig
from repro.models.model import forward, lm_loss, param_groups
from repro.parallel.plan import ShardingPlan
from repro.parallel.shardings import STORE_SPEC
from repro.train.optim import (OptimConfig, adamw_update, global_grad_norm,
                               init_opt_state)


def batch_spec(global_batch: int, mesh) -> P:
    """Shard the batch over (pod, data) when divisible, else replicate."""
    names = mesh.axis_names
    dp = [a for a in ("pod", "data") if a in names]
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if global_batch % size == 0:
        return P(tuple(dp))
    if "data" in dp and global_batch % mesh.shape["data"] == 0:
        return P(("data",))
    return P()


def _replicated_mask(cfg: ModelConfig, plan: ShardingPlan) -> Dict:
    """Pytree of bools: which stored params are TP-replicated copies."""
    groups = param_groups(cfg, plan)
    return {g: {n: (sp.tp_dim is None and sp.moe_fold is None)
                for n, sp in specs.items()}
            for g, (k, specs) in groups.items()}


def make_loss_fn(cfg: ModelConfig, plan: ShardingPlan, policy: CommPolicy,
                 multi_pod: bool, n_micro: int = 1,
                 aux_weight: float = 0.01):
    """Per-rank (store_views, batch) -> (seed_loss, raw_loss)."""
    dtype = jnp.dtype(cfg.dtype)

    def one_micro(views, deltas, tokens, labels, enc_embeds):
        hidden, unemb, aux, _ = forward(
            views, tokens, cfg, plan, policy,
            enc_embeds=enc_embeds, grad_deltas=deltas, dtype=dtype)
        return lm_loss(hidden, unemb, labels, cfg, plan, aux, aux_weight)

    def loss_fn(views, deltas, batch):
        denom = jax.lax.axis_size("model") * jax.lax.axis_size("data")
        if multi_pod:
            denom *= jax.lax.axis_size("pod")
        tokens, labels = batch["tokens"], batch["labels"]
        enc = batch.get("enc_embeds")
        if n_micro == 1:
            raw = one_micro(views, deltas, tokens, labels, enc)
        else:
            b = tokens.shape[0]
            assert b % n_micro == 0, (b, n_micro)
            mb = b // n_micro
            raw = jnp.zeros((), jnp.float32)
            for i in range(n_micro):
                sl = lambda a: lax.dynamic_slice_in_dim(a, i * mb, mb, 0) \
                    if a is not None else None
                raw += one_micro(views, deltas, sl(tokens), sl(labels),
                                 sl(enc))
            raw = raw / n_micro
        return raw / denom, raw

    return loss_fn


def pod_grad_config(policy: CommPolicy) -> CommConfig:
    """The grad-site config for the cross-pod sync, resolver-routed.

    The pod sync runs on already-reduce-scattered flat shards over the
    SINGLE ``pod`` axis, while the hierarchical schemes address an
    (inner, outer) axis *pair* — that two-axis/one-axis mismatch is why
    a hardcoded ``scheme="two_step"`` override used to live here. The
    single-axis dispatch in ``collectives._flat_all_reduce`` now handles
    it: ``"hierarchical"`` degenerates to the two-step it is on one
    axis, and ``"hier_pp"`` keeps its pipelined schedule by batching
    microchunks through one two-step — so the resolved config passes
    through unchanged and ``hier_pp`` grad policies stay pipelined
    across the pod bridge.

    A ``bridge``-site config, when set, overrides the grad site here —
    the SDP4Bit-style mixed-tier split: the slow pod hop runs at its
    own width (typically framed, core/frame.py) while the in-pod grad
    machinery keeps the grad site's raw config. Both sites are resolved
    unconditionally so the recording-policy trace lane sees them.
    """
    bridge = policy.resolve("bridge")
    grad = policy.resolve("grad")
    if bridge is not None:
        return bridge
    return grad or NO_COMPRESSION


def _grad_ef_eligible(policy: CommPolicy, multi_pod: bool) -> bool:
    """THE pod-EF predicate: ``init_train_state`` (via ``wants_grad_ef``)
    and ``make_train_step_fn``'s ``use_ef=None`` fallback both call this,
    so the opt-state tree and the step function can never disagree on
    whether the ``"ef"`` residual pytree exists."""
    return bool(policy.grad_ef and multi_pod
                and pod_grad_config(policy).enabled)


def wants_grad_ef(policy: CommPolicy, mesh) -> bool:
    """Whether this (policy, mesh) pair carries an EF residual: the
    grad site must be enabled+compressed on a multi-pod mesh (the only
    place the quantized grad AR runs) and the policy must ask for it."""
    return _grad_ef_eligible(policy, "pod" in mesh.axis_names)


def qgrad_rs_config(policy: CommPolicy) -> CommConfig:
    """The qgrad_rs-site config for the sharded-DP gradient RS."""
    return policy.resolve("qgrad_rs") or NO_COMPRESSION


def _qgrad_active(policy: CommPolicy, plan: ShardingPlan) -> bool:
    """Whether the explicit quantized gradient RS replaces the exact
    VJP reduce-scatter. Mesh-independent (derived from the plan at
    construction), so the step function, opt state and shard_map specs
    always agree."""
    cfg = qgrad_rs_config(policy)
    return bool(cfg.enabled and cfg.scheme != "nccl" and plan.fsdp > 1)


def wants_qgrad_ef(policy: CommPolicy, plan: ShardingPlan) -> bool:
    """Whether the qgrad RS carries its EF residual pytree (``"qef"``):
    the site must be active and the policy must ask for EF. Pass this
    to ``init_train_state`` — same single-predicate discipline as
    ``wants_grad_ef``."""
    return _qgrad_active(policy, plan) and bool(policy.grad_ef)


def make_train_step_fn(cfg: ModelConfig, plan: ShardingPlan,
                       policy: CommPolicy, opt_cfg: OptimConfig,
                       multi_pod: bool, n_micro: int = 1,
                       use_ef: Optional[bool] = None):
    """The per-rank train step to run under shard_map.

    ``use_ef`` must equal ``wants_grad_ef(policy, mesh)`` of the mesh
    the step runs on (make_train_step passes it) so the returned opt
    tree matches the shard_map specs; None derives it from multi_pod.
    """
    rep_mask = None  # built lazily (needs specs only)
    loss_fn = make_loss_fn(cfg, plan, policy, multi_pod, n_micro)
    pod_cfg = pod_grad_config(policy)
    # resolved unconditionally so the recording-policy trace lane sees
    # the qgrad_rs site even when it ends up inactive on this plan
    qgrad_cfg = qgrad_rs_config(policy)
    use_qgrad = _qgrad_active(policy, plan)
    use_qgrad_ef = use_qgrad and bool(policy.grad_ef)
    if use_ef is None:
        use_ef = _grad_ef_eligible(policy, multi_pod)

    def step(store, opt_state, batch):
        if use_qgrad:
            # Zero full-flat-length deltas added to the gathered
            # (stop-gradiented) weights: grads w.r.t. them are the
            # full per-rank gradients, BEFORE any reduce-scatter —
            # the explicit quantized+EF RS below replaces the VJP's.
            deltas = jax.tree_util.tree_map(
                lambda v: jnp.zeros(
                    (v.shape[0], v.shape[1], v.shape[2] * plan.fsdp),
                    v.dtype), store)
            (seed_loss, raw), grads = jax.value_and_grad(
                loss_fn, argnums=1, has_aux=True)(store, deltas, batch)
        else:
            (seed_loss, raw), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(store, None, batch)

        # --- model-axis sync for TP-replicated copies (exact psum) ---
        mask = _replicated_mask(cfg, plan)
        grads = {g: {n: (lax.psum(gr, "model") if mask[g][n] else gr)
                     for n, gr in gg.items()}
                 for g, gg in grads.items()}

        # --- within-pod sync: quantized (optionally EF) RS over
        #     ``data`` on the full-length delta grads, landing them
        #     ZeRO-sharded exactly where the VJP's exact psum_scatter
        #     would have (out-of-VJP so the residual can thread). ---
        new_qef = None
        if use_qgrad:
            flat_g, tdef = jax.tree_util.tree_flatten(grads)
            flat_g = [gr.astype(jnp.float32) for gr in flat_g]
            if use_qgrad_ef:
                flat_e = tdef.flatten_up_to(opt_state["qef"])
                outs = [quantized_reduce_scatter_ef(gr, e, "data",
                                                    qgrad_cfg)
                        for gr, e in zip(flat_g, flat_e)]
                grads = tdef.unflatten([o[0] for o in outs])
                new_qef = tdef.unflatten([o[1] for o in outs])
            else:
                grads = tdef.unflatten(
                    [quantized_reduce_scatter(gr, "data", qgrad_cfg)
                     for gr in flat_g])

        # --- cross-pod sync: the paper's quantized two-step AR on the
        #     already-RS'd flat shards (hierarchical scheme, realized).
        #     With grad_ef the residual pytree (optimizer state, ZeRO-
        #     sharded like the grads) re-injects last step's local
        #     quantization error before compressing (EF21-style). ---
        new_ef = None
        if multi_pod:
            if use_ef:
                flat_g, tdef = jax.tree_util.tree_flatten(grads)
                flat_e = tdef.flatten_up_to(opt_state["ef"])
                outs = [compressed_psum_ef(gr, e, ("pod",), pod_cfg)
                        for gr, e in zip(flat_g, flat_e)]
                grads = tdef.unflatten([o[0] for o in outs])
                new_ef = tdef.unflatten([o[1] for o in outs])
            else:
                grads = jax.tree_util.tree_map(
                    lambda gr: compressed_psum(gr, ("pod",), pod_cfg),
                    grads)

        sq = global_grad_norm(grads)
        sq = lax.psum(lax.psum(sq, "data"), "model")
        if multi_pod:
            sq = lax.psum(sq, "pod")
        gnorm = jnp.sqrt(sq)

        new_store, new_opt, lr = adamw_update(store, grads, opt_state,
                                              opt_cfg, gnorm)
        if new_ef is not None:
            new_opt["ef"] = new_ef
        if new_qef is not None:
            new_opt["qef"] = new_qef
        loss_rep = lax.pmean(raw, "data")
        if multi_pod:
            loss_rep = lax.pmean(loss_rep, "pod")
        metrics = {"loss": loss_rep, "grad_norm": gnorm, "lr": lr}
        return new_store, new_opt, metrics

    return step


def make_train_step(cfg: ModelConfig, plan: ShardingPlan,
                    policy: CommPolicy, opt_cfg: OptimConfig, mesh,
                    global_batch: int, n_micro: int = 1):
    """jit(shard_map(step)) over the production mesh."""
    multi_pod = "pod" in mesh.axis_names
    use_ef = wants_grad_ef(policy, mesh)
    step = make_train_step_fn(cfg, plan, policy, opt_cfg, multi_pod,
                              n_micro, use_ef=use_ef)
    bspec = batch_spec(global_batch, mesh)
    store_spec = jax.tree_util.tree_map(lambda _: STORE_SPEC,
                                        param_groups(cfg, plan))
    bs = {"tokens": bspec, "labels": bspec}
    if cfg.is_enc_dec or cfg.has_cross:
        bs["enc_embeds"] = bspec
    metric_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
    opt_spec = {"m": STORE_SPEC, "v": STORE_SPEC, "step": P()}
    if use_ef:
        opt_spec["ef"] = STORE_SPEC    # EF residual, sharded like grads
    if wants_qgrad_ef(policy, plan):
        # qgrad EF residual: full-flat-length leaves, dim2 over ``data``
        # (per-rank view matches the full-length delta grads)
        opt_spec["qef"] = STORE_SPEC

    sm = jax.shard_map(
        step, mesh=mesh,
        in_specs=(STORE_SPEC, opt_spec, bs),
        out_specs=(STORE_SPEC, opt_spec, metric_spec),
        check_vma=False)
    return jax.jit(sm, donate_argnums=(0, 1))


def init_train_state(store, opt_cfg: OptimConfig, grad_ef: bool = False,
                     qgrad_ef: bool = False, fsdp: int = 1):
    """Optimizer state; ``grad_ef`` adds the zero pod-EF residual pytree
    (pass ``wants_grad_ef(policy, mesh)``), ``qgrad_ef`` the zero qgrad
    residual pytree (pass ``wants_qgrad_ef(policy, plan)`` and
    ``plan.fsdp``) so state and step always agree."""
    return init_opt_state(store, opt_cfg, grad_ef=grad_ef,
                          qgrad_ef=qgrad_ef, fsdp=fsdp)
