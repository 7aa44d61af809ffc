"""Plain float32 reference of a dense decoder with grouped-query attention.

Covers Qwen3 (query/key RMSNorm on) and dense decoders of the same block
as the configuration file's ``as_run`` block states it: RMSNorm before
attention and MLP, GQA attention
with rotary positions over the whole head (the two halves rotated against
each other), a SwiGLU MLP, a final RMSNorm and untied output logits.

Straightforward ``jax.numpy`` on logical weights, with no cache, no
batching across requests, no sharding and no quantized exchange: the
all-reduces the program quantizes are exact sums here. Matrix products run
at ``Precision.HIGHEST`` so that a TPU computes them in float32.

``precision="fp8"`` is the control: every matrix product of the linear
layers and of the logits takes operands rounded to float8 e4m3 (a scale per
activation row and per weight column, as an fp8 serving path would use),
accumulated in float32. Norms, rotary positions and attention stay
float32. It imports nothing of the program under test.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 1024          # query rows per attention block
E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Arch:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    norm_eps: float
    rope_theta: float
    qk_norm: bool

    @classmethod
    def of(cls, as_run: Dict) -> "Arch":
        if (as_run["rotary"], as_run["rotary_dim"], as_run["qkv_bias"],
                as_run["mlp"]) != ("halves", as_run["head_dim"], False,
                                   "swiglu"):
            raise ValueError(f"dense_gqa does not compute {as_run}")
        return cls(**{f.name: as_run[f.name]
                      for f in dataclasses.fields(cls)})


def round_e4m3(y: jnp.ndarray) -> jnp.ndarray:
    """Round float32 values to the nearest float8 e4m3 value (ties to
    even, saturating at 448), in float32 arithmetic: 3 mantissa bits
    above 2**-6, a fixed step of 2**-9 below it."""
    y = jnp.clip(y, -E4M3_MAX, E4M3_MAX)
    _, e = jnp.frexp(y)                        # |y| in [2**(e-1), 2**e)
    step_exp = jnp.maximum(e - 4, -9)
    q = jnp.round(jnp.ldexp(y, -step_exp))
    return jnp.ldexp(q, step_exp)


def _fp8_rows(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return round_e4m3(x / scale) * scale


def matmul(x: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """x (..., k) @ w (k, n) in float32."""
    if precision == "fp8":
        x = _fp8_rows(x, -1)
        w = _fp8_rows(w, 0)
    else:
        assert precision == "float32", precision
    return jnp.matmul(x, w, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x: jnp.ndarray, gain: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain


def rotary(x: jnp.ndarray, positions: jnp.ndarray,
           theta: float) -> jnp.ndarray:
    """x (T, H, hd): the first and second halves of each head form the
    rotated pairs, at frequency theta**(-i / (hd/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, arch: Arch) -> jnp.ndarray:
    """q (T, Hq, hd), k/v (T, Hkv, hd) -> (T, Hq, hd); each query sees
    its own and earlier positions. Queries go in blocks of Q_BLOCK rows
    so the score matrix never holds all T x T at once."""
    t = q.shape[0]
    rep = arch.n_heads // arch.n_kv_heads
    k = jnp.repeat(k, rep, axis=1)            # q head h reads kv h // rep
    v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.float32(arch.head_dim))
    kpos = jnp.arange(t)
    out = []
    for s in range(0, t, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        qpos = jnp.arange(s, s + qb.shape[0])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def layer(x: jnp.ndarray, w: Dict[str, jnp.ndarray], arch: Arch,
          precision: str = "float32") -> jnp.ndarray:
    """One block over one sequence x (T, d), positions 0..T-1."""
    t, hd = x.shape[0], arch.head_dim
    pos = jnp.arange(t)
    h = rms_norm(x, w["n1_gain"], arch.norm_eps)
    q = matmul(h, w["wq"], precision).reshape(t, arch.n_heads, hd)
    k = matmul(h, w["wk"], precision).reshape(t, arch.n_kv_heads, hd)
    v = matmul(h, w["wv"], precision).reshape(t, arch.n_kv_heads, hd)
    if arch.qk_norm:
        q = rms_norm(q, w["qnorm"], arch.norm_eps)
        k = rms_norm(k, w["knorm"], arch.norm_eps)
    q = rotary(q, pos, arch.rope_theta)
    k = rotary(k, pos, arch.rope_theta)
    ctx = causal_attention(q, k, v, arch).reshape(t, arch.n_heads * hd)
    x = x + matmul(ctx, w["wo"], precision)
    h = rms_norm(x, w["n2_gain"], arch.norm_eps)
    g = jax.nn.silu(matmul(h, w["w1"], precision)) * matmul(h, w["w3"],
                                                             precision)
    return x + matmul(g, w["w2"], precision)


@functools.partial(jax.jit, static_argnames=("arch",))
def final_norm(x: jnp.ndarray, gain: jnp.ndarray, arch: Arch) -> jnp.ndarray:
    return rms_norm(x, gain, arch.norm_eps)


@functools.partial(jax.jit, static_argnames=("precision",))
def logits(h: jnp.ndarray, unemb_rows: jnp.ndarray,
           precision: str = "float32") -> jnp.ndarray:
    """Normed hidden rows (T, d) against a block of output-embedding rows
    (V, d) -> (T, V)."""
    return matmul(h, unemb_rows.T, precision)
