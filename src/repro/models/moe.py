"""Mixture-of-Experts with expert-parallel quantized dispatch.

EP mapping (see ShardingPlan): the model axis factorizes ``tp = ep*etp``;
rank ``m = ep_idx*etp + tp_idx`` owns ``e_loc = E/ep`` experts, each
TP-sharded ``etp`` ways. Token routing is capacity-based sort-free
(one-hot cumsum positions), the dispatch All2All payload is quantized
with the paper's wire codec (Table 2/8/10 site), the combine path stays
BF16 (paper-faithful, following DeepSeek-V3), and the within-expert
partial sums use the quantized TP AllReduce when ``etp > 1``.

With ``policy.a2a.scheme == "fused"`` (``with_scheme(policy, "fused")``
/ the launch CLIs' ``--comm-scheme fused``) the dispatch rides the
fused A2A path instead of codec around ``lax.all_to_all``: the
(ep, e_loc*cap, d) dispatch buffer maps onto (tp, m, d) per-peer
blocks. On TPU with the A2A spanning the whole model axis that is the
single-kernel RDMA push (``repro.kernels.rdma_all2all``); when the
dispatch uses ``axis_index_groups`` (``ep < tp`` or ``etp > 1``, the
RDMA addressing doesn't cover subgroups) it is the fused kernel bodies
with an XLA hop (``repro.kernels.emulate``). Either way bit-identical
to the XLA path (tests/_multidev_script.py ``fused_a2a``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.collectives import compressed_psum, dispatch_all_to_all
from repro.core.comm_config import NO_COMPRESSION
from repro.core.policy import CommPolicy
from repro.models.config import ModelConfig
from repro.models.layers import gelu
from repro.parallel.plan import ShardingPlan
from repro.parallel.shardings import ParamSpec


def moe_specs(cfg: ModelConfig, plan: ShardingPlan,
              prefix: str = "moe_") -> Dict[str, ParamSpec]:
    m = cfg.moe
    d = cfg.d_model
    s = {
        prefix + "router": ParamSpec((d, m.n_experts)),
        prefix + "w1": ParamSpec((m.n_experts, d, m.d_ff), moe_fold="in"),
        prefix + "w2": ParamSpec((m.n_experts, m.d_ff, d), moe_fold="out",
                                 init="zeros"),
    }
    if cfg.act in ("swiglu", "geglu"):
        s[prefix + "w3"] = ParamSpec((m.n_experts, d, m.d_ff),
                                     moe_fold="in")
    return s


def capacity(tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = -(-int(tokens * m.top_k * m.capacity_factor) // m.n_experts)
    if c >= 8:
        return -(-c // 8) * 8
    return max(1, c)   # decode: a floor of 8 would inflate the A2A 8x


def moe_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
              plan: ShardingPlan, policy: CommPolicy,
              prefix: str = "moe_",
              layer: Optional[int] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) replicated over the model axis -> (out, aux_loss).

    ``layer`` is the global block index; the dispatch payload width and
    the within-expert psum both come from the policy engine's
    ``(site, layer)`` resolution, so depth-scheduled policies can run
    e.g. INT8 dispatch on the edge MoE layers and INT4 in the middle.
    """
    a2a_cfg = policy.resolve("a2a", layer) or NO_COMPRESSION
    tp_cfg = policy.resolve("tp", layer) or NO_COMPRESSION
    m = cfg.moe
    mp = plan.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    # ---- EP token slicing (beyond-paper; see CommPolicy.ep_slice) ----
    # x is replicated across the model axis, so without slicing every
    # ep-group rank dispatches the SAME tokens and each expert computes
    # them ep times. Slice tokens 1/ep per rank; all-gather outputs.
    ep_slice = policy.ep_slice and mp.ep > 1
    t_orig = t
    if ep_slice:
        ts = -(-t // mp.ep)                      # ceil
        t_pad = ts * mp.ep
        if t_pad != t:
            xt = jnp.pad(xt, ((0, t_pad - t), (0, 0)))
        ep_idx = lax.axis_index("model") // mp.etp
        xt = lax.dynamic_slice_in_dim(xt, ep_idx * ts, ts, 0)
        t = ts

    # ---- routing (f32) ----
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p[prefix + "router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, m.top_k)                # (T,k)
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style): E * sum_e f_e * p_e
    route_frac = jnp.mean(
        jax.nn.one_hot(topi, m.n_experts, dtype=jnp.float32), axis=(0, 1))
    prob_frac = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(route_frac * prob_frac)

    # ---- capacity positions (one-hot cumsum; deterministic, sort-free) --
    r = t * m.top_k
    re = topi.reshape(r)
    rw = topv.reshape(r)
    onehot = jax.nn.one_hot(re, m.n_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)
    pos = jnp.take_along_axis(pos, re[:, None], axis=1)[:, 0]  # (R,)
    cap = capacity(t, cfg)
    keep = pos < cap
    tok_idx = jnp.arange(r) // m.top_k

    # ---- build dispatch buffer (E, cap, d) and EP-exchange ----
    src = jnp.take(xt, tok_idx, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((m.n_experts, cap, d), x.dtype)
    buf = buf.at[re, jnp.where(keep, pos, cap - 1)].add(
        src, mode="drop")
    buf = buf.reshape(mp.ep, mp.e_loc * cap, d)
    groups = mp.ep_groups if mp.ep < plan.tp or mp.etp > 1 else None
    recv = dispatch_all_to_all(buf, "model", a2a_cfg, groups)

    # ---- expert FFN (my e_loc experts, etp-sharded hidden) ----
    tok = recv.reshape(mp.ep, mp.e_loc, cap, d)
    tok = tok.transpose(1, 0, 2, 3).reshape(mp.e_loc, mp.ep * cap, d)
    h = jnp.einsum("etd,edf->etf", tok, p[prefix + "w1"])
    if cfg.act in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.act == "swiglu" else gelu
        h = act(h) * jnp.einsum("etd,edf->etf", tok, p[prefix + "w3"])
    else:
        h = gelu(h)
    y = jnp.einsum("etf,efd->etd", h, p[prefix + "w2"])
    if mp.etp > 1:
        with jax.named_scope("tp_site"):
            y = compressed_psum(y, ("model",), tp_cfg, mp.etp_groups)

    # ---- combine (BF16, unquantized — paper-faithful) ----
    y = y.reshape(mp.e_loc, mp.ep, cap, d).transpose(1, 0, 2, 3)
    y = y.reshape(mp.ep, mp.e_loc * cap, d)
    back = lax.all_to_all(y, "model", 0, 0, tiled=True,
                          axis_index_groups=groups)
    back = back.reshape(m.n_experts, cap, d)
    out_r = jnp.take(back.reshape(-1, d),
                     jnp.clip(re * cap + pos, 0, m.n_experts * cap - 1),
                     axis=0)
    out_r = out_r * (rw * keep)[:, None].astype(x.dtype)
    out = jnp.sum(out_r.reshape(t, m.top_k, d), axis=1)
    if ep_slice:
        # combine-direction gather of the per-slice outputs (BF16,
        # paper-faithful: only dispatch is quantized)
        full = lax.all_gather(out, "model", axis=0, tiled=True,
                              axis_index_groups=plan.moe.ep_groups
                              if mp.ep < plan.tp or mp.etp > 1 else None)
        out = full[:t_orig]
        # slice-local aux is an unbiased estimate; average over the group
        aux = lax.pmean(aux, "model")
    return out.reshape(b, s, d), aux
