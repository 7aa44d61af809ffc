"""Normalization, activations, RoPE, embeddings, vocab-parallel loss.

All apply-functions run per-rank inside shard_map; TP reductions go
through ``compressed_psum`` so the paper's quantized AllReduce is the
default transport for every activation that crosses the model axis.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.collectives import compressed_psum
from repro.core.comm_config import CommConfig, NO_COMPRESSION
from repro.core.policy import CommPolicy

TP_AXES = ("model",)


def tp_psum(x: jnp.ndarray, policy: CommPolicy, groups=None,
            layer: Optional[int] = None) -> jnp.ndarray:
    """The paper's TP AllReduce site (fwd; bwd per the tp_bwd site).

    ``layer`` is the global block index (None for out-of-block traffic
    like the embedding psum) — the policy engine resolves the
    ``(site, layer)`` pair, so depth-scheduled policies bind different
    widths to different layers here.
    """
    cfg = policy.resolve("tp", layer) or NO_COMPRESSION
    bwd = policy.resolve("tp_bwd", layer)
    with jax.named_scope("tp_site"):
        return compressed_psum(x, TP_AXES, cfg, groups, bwd)


# --------------------------------------------------------------------------
# norms / activations
# --------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, gain: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * gain.astype(jnp.float32)
            ).astype(x.dtype)


def layer_norm(x: jnp.ndarray, gain: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * gain.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x, p: Dict, kind: str):
    if kind == "rms":
        return rms_norm(x, p["gain"])
    return layer_norm(x, p["gain"], p["bias"])


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (.., S, half)
    cos = jnp.cos(ang)[..., None, :]                          # (.., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    while cos.ndim < x.ndim:
        cos, sin = cos[None], sin[None]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# vocab-parallel embedding + cross-entropy
# --------------------------------------------------------------------------

def _local_ids(tokens: jnp.ndarray, v_loc: int):
    """Global ids -> (row in this rank's vocab slice, clipped; whether
    the id is in the slice)."""
    rank = lax.axis_index("model")
    ids = tokens - rank * v_loc
    ok = (ids >= 0) & (ids < v_loc)
    return jnp.clip(ids, 0, v_loc - 1), ok


def embed_lookup(tokens: jnp.ndarray, emb_loc: jnp.ndarray,
                 policy: CommPolicy, dtype) -> jnp.ndarray:
    """tokens (B,S) int32; emb_loc (v_loc, d) = this rank's vocab rows.
    Masked local lookup + TP psum (the paper's quantized AR site)."""
    rows, ok = _local_ids(tokens, emb_loc.shape[0])
    vec = jnp.take(emb_loc, rows, axis=0)
    vec = jnp.where(ok[..., None], vec, 0).astype(dtype)
    return tp_psum(vec, policy).astype(dtype)


def embed_rows(tokens: jnp.ndarray, flat: jnp.ndarray, v_loc: int, d: int,
               policy: CommPolicy, dtype) -> jnp.ndarray:
    """:func:`embed_lookup` read from this rank's flat store row ``flat``
    (its ``(v_loc, d)`` table flattened, any dtype): only the tokens'
    ``d``-wide rows are gathered, then cast. Casting commutes with the
    gather, so the result is embed_lookup's bit for bit."""
    if v_loc * d >= 2 ** 31:
        raise ValueError(f"a {v_loc} x {d} table is past int32 offsets")
    rows, ok = _local_ids(tokens, v_loc)
    vec = jax.vmap(lambda r: lax.dynamic_slice(flat, (r * d,), (d,)))(
        rows.reshape(-1)).reshape(*rows.shape, d).astype(dtype)
    vec = jnp.where(ok[..., None], vec, 0).astype(dtype)
    return tp_psum(vec, policy).astype(dtype)


def vocab_parallel_logits(x: jnp.ndarray, unemb_loc: jnp.ndarray,
                          softcap: Optional[float] = None) -> jnp.ndarray:
    """x (..., d) @ unemb_loc (v_loc, d)^T -> per-rank logits (..., v_loc).
    No gather — the full vocab never materializes on one rank."""
    logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                        unemb_loc.astype(jnp.float32))
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def vocab_parallel_ce(logits_loc: jnp.ndarray, labels: jnp.ndarray,
                      vocab: int, v_loc: int) -> jnp.ndarray:
    """Cross-entropy over model-axis-sharded logits.

    logits_loc: (T, v_loc) f32, labels: (T,) int32 global ids.
    Exact psum/pmax reductions (scalars per token — not a quantization
    site; the paper quantizes activation tensors, not loss reductions).
    """
    rank = lax.axis_index("model")
    base = rank * v_loc
    col = jnp.arange(v_loc)[None, :] + base
    valid = col < vocab                                   # mask pad vocab
    masked = jnp.where(valid, logits_loc, -jnp.inf)
    # stabilizer max: mathematically gradient-free, and pmax has no
    # differentiation rule -> stop_gradient + differentiable all_gather.
    loc_mx = lax.stop_gradient(jnp.max(masked, axis=-1))
    mx = jnp.max(lax.all_gather(loc_mx, "model", axis=0), axis=0)  # (T,)
    se = lax.psum(jnp.sum(jnp.exp(masked - mx[:, None]), axis=-1), "model")
    lse = mx + jnp.log(se)
    ids = labels - base
    ok = (ids >= 0) & (ids < v_loc)
    own = jnp.take_along_axis(
        logits_loc, jnp.clip(ids, 0, v_loc - 1)[:, None], axis=1)[:, 0]
    label_logit = lax.psum(jnp.where(ok, own, 0.0), "model")
    return lse - label_logit                              # (T,) nll


# --------------------------------------------------------------------------
# dense MLP (TP: hidden sharded; down-proj partial sums -> quantized AR)
# --------------------------------------------------------------------------

def mlp_apply(p: Dict, x: jnp.ndarray, act: str, policy: CommPolicy,
              use_bias: bool = False,
              layer: Optional[int] = None) -> jnp.ndarray:
    with jax.named_scope("mlp"):
        if act in ("swiglu", "geglu"):
            h = jnp.einsum("...d,df->...f", x, p["w1"])
            g = jnp.einsum("...d,df->...f", x, p["w3"])
            if use_bias:
                h, g = h + p["b1"], g + p["b3"]
            h = (jax.nn.silu(h) if act == "swiglu" else gelu(h)) * g
        else:
            h = jnp.einsum("...d,df->...f", x, p["w1"])
            if use_bias:
                h = h + p["b1"]
            h = gelu(h)
        y = jnp.einsum("...f,fd->...d", h, p["w2"])
        y = tp_psum(y, policy, layer=layer)
        if use_bias:
            y = y + p["b2"]
        return y.astype(x.dtype)
