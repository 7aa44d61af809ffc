"""GQA attention: blockwise (flash-style) training/prefill + cached decode.

TP layout: q heads sharded over the model axis (padded to a multiple of
the axis; padded heads are masked so they are exact no-ops). kv heads are
sharded when ``n_kv % tp == 0`` else replicated per rank (standard
Megatron GQA fallback). The out-projection partial sums cross the model
axis through ``compressed_psum`` — the paper's TP AllReduce site.

The blockwise attention is a pure-JAX online-softmax scan over KV chunks
(the TPU-native substrate for 32k prefill: no S x S score tensor ever
materializes; HLO stays O(1) in sequence length).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.policy import CommPolicy
from repro.models.config import ModelConfig
from repro.models.layers import rms_norm, rope, tp_psum
from repro.parallel.plan import ShardingPlan
from repro.parallel.shardings import ParamSpec

KV_CHUNK = 1024
_NEG = -1e30

# Roofline builds set this so the kv-chunk scan is fully unrolled and
# XLA's cost_analysis (which counts while-loop bodies ONCE) sees every
# chunk. Never set for real runs — HLO size grows by S/KV_CHUNK.
UNROLL_ATTN_SCAN = False


def attn_specs(cfg: ModelConfig, plan: ShardingPlan,
               cross: bool = False, prefix: str = "") -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.hd
    kv_dim = cfg.n_kv_heads * hd
    kv_tp = 1 if plan.kv_mode == "shard" else None
    s = {
        prefix + "wq": ParamSpec((d, plan.hq_pad * hd), tp_dim=1),
        prefix + "wk": ParamSpec((d, kv_dim), tp_dim=kv_tp),
        prefix + "wv": ParamSpec((d, kv_dim), tp_dim=kv_tp),
        prefix + "wo": ParamSpec((plan.hq_pad * hd, d), tp_dim=0,
                                 init="zeros"),
    }
    if cfg.use_bias:
        s[prefix + "bq"] = ParamSpec((plan.hq_pad * hd,), tp_dim=0,
                                     init="zeros")
        kv_btp = 0 if kv_tp is not None else None
        s[prefix + "bk"] = ParamSpec((kv_dim,), tp_dim=kv_btp, init="zeros")
        s[prefix + "bv"] = ParamSpec((kv_dim,), tp_dim=kv_btp, init="zeros")
        s[prefix + "bo"] = ParamSpec((d,), init="zeros")
    if cfg.qk_norm:
        s[prefix + "qnorm"] = ParamSpec((hd,), init="ones")
        s[prefix + "knorm"] = ParamSpec((hd,), init="ones")
    return s


def _head_maps(cfg: ModelConfig, plan: ShardingPlan):
    """Per-rank (q-head validity mask, local kv index per q head)."""
    rank = lax.axis_index("model")
    gq = rank * plan.hq_loc + jnp.arange(plan.hq_loc)          # global q ids
    valid = gq < cfg.n_heads
    q_per_kv = cfg.n_heads // cfg.n_kv_heads
    gkv = jnp.clip(gq // q_per_kv, 0, cfg.n_kv_heads - 1)
    if plan.kv_mode == "shard":
        kv_local = jnp.clip(gkv - rank * plan.kv_loc, 0, plan.kv_loc - 1)
    else:
        kv_local = gkv
    return valid, kv_local


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        qpos: jnp.ndarray, kpos: jnp.ndarray,
                        causal: bool, window: Optional[int],
                        chunk: int = KV_CHUNK) -> jnp.ndarray:
    """Online-softmax attention. q (B,S,H,hd); k/v (B,Skv,H,hd).

    kpos entries < 0 are masked (padding). Never materializes S x Skv.
    """
    b, s, h, hd = q.shape
    skv = k.shape[1]
    scale = 1.0 / jnp.sqrt(float(hd))
    nc = -(-skv // chunk)
    pad = nc * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=-1)
    kc = k.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = kpos.reshape(nc, chunk)
    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs                                   # (B,c,H,hd),(c,)
        sc = jnp.einsum("bshd,bchd->bshc", qf,
                        kb.astype(jnp.float32)) * scale   # (B,S,H,c)
        mask = (pb >= 0)[None, None, None, :]
        if causal:
            mask = mask & (pb[None, :] <= qpos[:, None])[None, :, None, :]
        if window is not None:
            mask = mask & (pb[None, :] > qpos[:, None]
                           - window)[None, :, None, :]
        sc = jnp.where(mask, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = (acc * corr[..., None]
               + jnp.einsum("bshc,bchd->bshd", p, vb.astype(jnp.float32)))
        return (m_new, l, acc), None

    init = (jnp.full((b, s, h), _NEG, jnp.float32),
            jnp.zeros((b, s, h), jnp.float32),
            jnp.zeros((b, s, h, hd), jnp.float32))
    (m, l, acc), _ = lax.scan(body, init, (kc, vc, pc),
                              unroll=nc if UNROLL_ATTN_SCAN else 1)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def init_kv_cache(cfg: ModelConfig, plan: ShardingPlan, batch: int,
                  cache_len: int, dtype) -> Dict[str, jnp.ndarray]:
    """Decode cache. kv_mode == "shard": head-sharded (each rank holds
    kv_loc heads, all positions). kv_mode == "replicate": SEQUENCE-
    sharded ring — each rank holds cache_len/tp positions of all kv
    heads (otherwise the cache would replicate over the model axis and
    blow per-chip HBM at 32k x large-batch decode); attention merges the
    per-rank online-softmax partials with a tiny stats all-gather."""
    if plan.kv_mode == "shard":
        c_loc = cache_len
    else:
        assert cache_len % plan.tp == 0, (cache_len, plan.tp)
        c_loc = cache_len // plan.tp
    return {
        "k": jnp.zeros((batch, c_loc, plan.kv_loc, cfg.hd), dtype),
        "v": jnp.zeros((batch, c_loc, plan.kv_loc, cfg.hd), dtype),
        "slot_pos": jnp.full((c_loc,), -1, jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }


def _project_qkv(p, x, kv_src, cfg, plan, prefix=""):
    b = x.shape[0]
    hd = cfg.hd
    q = jnp.einsum("...d,dh->...h", x, p[prefix + "wq"])
    k = jnp.einsum("...d,dh->...h", kv_src, p[prefix + "wk"])
    v = jnp.einsum("...d,dh->...h", kv_src, p[prefix + "wv"])
    if cfg.use_bias:
        q, k, v = (q + p[prefix + "bq"], k + p[prefix + "bk"],
                   v + p[prefix + "bv"])
    q = q.reshape(b, -1, plan.hq_loc, hd)
    k = k.reshape(b, -1, plan.kv_loc, hd)
    v = v.reshape(b, -1, plan.kv_loc, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p[prefix + "qnorm"])
        k = rms_norm(k, p[prefix + "knorm"])
    return q, k, v


def _finish(p, ctx, valid, policy: CommPolicy, cfg, prefix="",
            layer=None):
    """Mask padded heads, out-project, quantized TP AllReduce."""
    b, s = ctx.shape[0], ctx.shape[1]
    ctx = ctx * valid[None, None, :, None]
    y = jnp.einsum("...h,hd->...d", ctx.reshape(b, s, -1),
                   p[prefix + "wo"])
    y = tp_psum(y, policy, layer=layer)
    if cfg.use_bias:
        y = y + p[prefix + "bo"]
    return y


def self_attention(p: Dict, x: jnp.ndarray, positions: jnp.ndarray,
                   cfg: ModelConfig, plan: ShardingPlan,
                   policy: CommPolicy, *, causal: bool = True,
                   window: Optional[int] = None,
                   cache: Optional[Dict] = None, prefix: str = "",
                   layer: Optional[int] = None
                   ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Full-sequence (cache=None) or single-token cached decode.

    x: (B, S, d); positions (S,) for full-seq, scalar pos for decode.
    """
    with jax.named_scope("attention"):
        valid, kvmap = _head_maps(cfg, plan)

        if cache is None:
            q, k, v = _project_qkv(p, x, x, cfg, plan, prefix)
            if cfg.rope_theta is not None:
                q = rope(q, positions, cfg.rope_theta)
                k = rope(k, positions, cfg.rope_theta)
            ke = jnp.take(k, kvmap, axis=2)       # expand to per-q-head
            ve = jnp.take(v, kvmap, axis=2)
            ctx = blockwise_attention(q, ke, ve, positions, positions,
                                      causal, window)
            return _finish(p, ctx, valid, policy, cfg, prefix, layer), None

        # ---- cached decode: x is (B, 1, d), positions is scalar ----
        pos = cache["pos"]
        q, k, v = _project_qkv(p, x, x, cfg, plan, prefix)
        if cfg.rope_theta is not None:
            pvec = pos[None].astype(jnp.int32)
            q = rope(q, pvec, cfg.rope_theta)
            k = rope(k, pvec, cfg.rope_theta)
        c_loc = cache["k"].shape[1]

        if plan.kv_mode == "shard":
            # head-sharded cache: every rank holds all positions
            slot = pos % c_loc
            ck = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            cv = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
            spos = cache["slot_pos"].at[slot].set(pos)
        else:
            # sequence-sharded ring: rank slot//c_loc owns this position
            slot = pos % (c_loc * plan.tp)
            owner = slot // c_loc
            lslot = slot % c_loc
            rank = lax.axis_index("model")
            mine = rank == owner
            ck = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, lslot, 0, 0))
            cv = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, lslot, 0, 0))
            ck = jnp.where(mine, ck, cache["k"])
            cv = jnp.where(mine, cv, cache["v"])
            spos = jnp.where(mine, cache["slot_pos"].at[lslot].set(pos),
                             cache["slot_pos"])
        new_cache = {"k": ck, "v": cv, "slot_pos": spos, "pos": pos + 1}

        ke = jnp.take(ck, kvmap, axis=2)          # (B, C_loc, hq_loc, hd)
        ve = jnp.take(cv, kvmap, axis=2)
        scale = 1.0 / jnp.sqrt(float(cfg.hd))
        sc = jnp.einsum("bshd,bchd->bshc", q.astype(jnp.float32),
                        ke.astype(jnp.float32)) * scale   # (B,1,H,C_loc)
        mask = (spos >= 0) & (spos <= pos)
        if causal and window is not None:
            mask = mask & (spos > pos - window)
        sc = jnp.where(mask[None, None, None, :], sc, _NEG)

        if plan.kv_mode == "shard":
            w = jax.nn.softmax(sc, axis=-1)
            ctx = jnp.einsum("bshc,bchd->bshd", w, ve.astype(jnp.float32))
        else:
            # per-rank online-softmax partials, merged with a tiny stats
            # all-gather over the model axis (B*H*(hd+2) floats per rank)
            m_loc = jnp.max(sc, axis=-1)                       # (B,1,H)
            pw = jnp.exp(sc - m_loc[..., None])
            l_loc = jnp.sum(pw, axis=-1)
            acc = jnp.einsum("bshc,bchd->bshd", pw, ve.astype(jnp.float32))
            m_all = lax.all_gather(m_loc, "model", axis=0)     # (tp,B,1,H)
            l_all = lax.all_gather(l_loc, "model", axis=0)
            a_all = lax.all_gather(acc, "model", axis=0)
            m_g = jnp.max(m_all, axis=0)
            corr = jnp.exp(m_all - m_g[None])
            l_g = jnp.sum(l_all * corr, axis=0)
            ctx = (jnp.sum(a_all * corr[..., None], axis=0)
                   / jnp.maximum(l_g, 1e-20)[..., None])
        ctx = ctx.astype(x.dtype)
        return _finish(p, ctx, valid, policy, cfg, prefix, layer), new_cache


def cross_attention(p: Dict, x: jnp.ndarray, enc: jnp.ndarray,
                    cfg: ModelConfig, plan: ShardingPlan,
                    policy: CommPolicy, prefix: str = "x",
                    layer: Optional[int] = None) -> jnp.ndarray:
    """Cross-attention onto encoder/image embeddings (B, Senc, d).
    No positional rotation on q/k (whisper/mllama style abs-pos is in the
    embeddings); never causal; no cache needed (enc is static)."""
    with jax.named_scope("attention"):
        valid, kvmap = _head_maps(cfg, plan)
        q, k, v = _project_qkv(p, x, enc, cfg, plan, prefix)
        senc = enc.shape[1]
        kpos = jnp.arange(senc)
        qpos = jnp.zeros((x.shape[1],), jnp.int32)
        ke = jnp.take(k, kvmap, axis=2)
        ve = jnp.take(v, kvmap, axis=2)
        ctx = blockwise_attention(q, ke, ve, qpos, kpos, causal=False,
                                  window=None)
        return _finish(p, ctx, valid, policy, cfg, prefix, layer)
