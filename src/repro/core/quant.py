"""Asymmetric fine-grained round-to-nearest quantization at any bit width.

This is the paper's base quantizer (Tables 1-2): per-group (last axis
reshaped to ``(..., n_groups, group)``) asymmetric RTN with BF16 scales
and zeros. ``bits`` may be anything in 2..8 — the packing of irregular
widths is handled separately by :mod:`repro.core.bitsplit`.

The ``*_groups`` forms take the grouped tensor and the axis that runs
within a group: the wire tile bodies (:mod:`repro.core.tilecodec`) put
the group on a leading axis of a transposed tile, where the group
reduction is a sublane reduction on TPU. The min and max are two plain
reductions (a variadic ``lax.reduce`` has no Pallas TPU lowering); both
propagate NaN like ``jnp.min``/``jnp.max``.

The rounding helpers (:func:`cast`, :func:`round_codes`,
:func:`meta_scale`) are jitted: an eager caller, such as an un-jitted
``shard_map``, then dispatches each as one program, not op by op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_EPS = 1e-12
_HI16 = np.uint32(0xFFFF0000)      # the bf16 bits of an f32


def group_min_max(xg: jnp.ndarray, axis: int = -1):
    """(min, max) over the in-group ``axis``."""
    return jnp.min(xg, axis=axis), jnp.max(xg, axis=axis)


@functools.partial(jax.jit, static_argnums=1)
def cast(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """f32 ``x`` to ``dtype``, rounding to nearest even. To bfloat16 the
    rounding is integer arithmetic on the f32 bits: the TPU Pallas
    compiler's own f32 -> bf16 conversion does not round like XLA's, and
    every backend must produce the same wire bytes."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return x.astype(dtype)
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + ((bits >> 16) & 1) + 0x7FFF) & _HI16
    r = lax.bitcast_convert_type(bits, jnp.float32)     # exactly bf16
    return jnp.where(jnp.isnan(x), x, r).astype(jnp.bfloat16)


def _half_ulp(m: jnp.ndarray) -> jnp.ndarray:
    """Half an f32 ulp of ``|m|`` (normal, nonzero), as a power of two."""
    e = (lax.bitcast_convert_type(jnp.abs(m), jnp.uint32) >> 23) & 0xFF
    return lax.bitcast_convert_type((e - 24) << 23, jnp.float32)


@functools.partial(jax.jit, static_argnums=2)
def round_codes(d: jnp.ndarray, s: jnp.ndarray, qmax: float) -> jnp.ndarray:
    """``clip(round(d / s), 0, qmax)`` as int32, for a bf16-exact ``s``.

    ``round`` of the correctly rounded f32 quotient, reproduced from any
    backend's division: the candidate tie point ``t = (k + .5) * s`` is
    exact in f32 (at most 10 + 8 significant bits), so ``d - t`` decides
    exactly which side of it the quotient lies, and whether the f32
    quotient would have landed on the tie (round half to even).
    """
    q = d / s
    k = jnp.floor(jnp.clip(q, -1.0, qmax + 1.0))
    diff = d - (k + 0.5) * s                            # exact near a tie
    tie = jnp.abs(diff) <= _half_ulp(k + 0.5) * s
    odd = k - 2.0 * jnp.floor(k * 0.5) == 1.0
    up = (tie & odd) | (~tie & (diff > 0))      # no i1 select on TPU
    code = jnp.where(jnp.isnan(q), q, k + up.astype(jnp.float32))
    return jnp.clip(code, 0.0, qmax).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def meta_scale(rng: jnp.ndarray, qmax: float, meta_dtype) -> jnp.ndarray:
    """``max(rng / qmax, EPS)`` in ``meta_dtype`` (f32 quotient, then
    round to nearest even), the same on every backend: for bf16 the
    midpoint ``m`` between the two bf16 candidates times ``qmax`` is
    exact in f32, so ``rng - m * qmax`` places the quotient exactly."""
    q = rng / qmax
    if jnp.dtype(meta_dtype) != jnp.bfloat16:
        return jnp.maximum(q, _EPS).astype(meta_dtype)
    lo = lax.bitcast_convert_type(q, jnp.uint32) & _HI16
    m = lax.bitcast_convert_type(lo + 0x8000, jnp.float32)
    diff = rng - m * qmax                               # exact near a tie
    tie = jnp.abs(diff) <= _half_ulp(m) * qmax
    up = (tie & ((lo >> 16) & 1 == 1)) | (~tie & (diff > 0))
    r = lax.bitcast_convert_type(lo + up.astype(jnp.uint32) * 0x10000,
                                 jnp.float32)
    r = jnp.where(jnp.isfinite(q), r, q)
    return cast(jnp.where(q < _EPS, jnp.float32(_EPS), r), jnp.bfloat16)


def group_reshape(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """(..., n) -> (..., n//group, group). n must divide."""
    n = x.shape[-1]
    assert n % group == 0, f"n={n} not divisible by group={group}"
    return x.reshape(*x.shape[:-1], n // group, group)


def group_unreshape(xg: jnp.ndarray) -> jnp.ndarray:
    return xg.reshape(*xg.shape[:-2], xg.shape[-2] * xg.shape[-1])


def quantize_groups(xg: jnp.ndarray, bits: int, meta_dtype=jnp.bfloat16,
                    axis: int = -1):
    """RTN on a grouped f32 tensor whose ``axis`` runs within a group.

    Returns (codes int32 in [0, 2^bits-1], same shape as ``xg``;
    scale, zero in ``meta_dtype`` with ``axis`` reduced away).
    """
    qmax = float(2 ** bits - 1)
    mn, mx = group_min_max(xg, axis)
    # Store meta at wire precision, then quantize *with the stored values*
    # so encode/decode are self-consistent.
    scale_w = meta_scale(mx - mn, qmax, meta_dtype)
    zero_w = cast(mn, meta_dtype)
    s = jnp.expand_dims(scale_w.astype(jnp.float32), axis)
    z = jnp.expand_dims(zero_w.astype(jnp.float32), axis)
    return round_codes(xg - z, s, qmax), scale_w, zero_w


def dequantize_groups(codes: jnp.ndarray, scale: jnp.ndarray,
                      zero: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inverse of :func:`quantize_groups`; grouped f32 result."""
    s = jnp.expand_dims(scale.astype(jnp.float32), axis)
    z = jnp.expand_dims(zero.astype(jnp.float32), axis)
    return codes.astype(jnp.float32) * s + z


def quantize(x: jnp.ndarray, bits: int, group: int,
             meta_dtype=jnp.bfloat16):
    """Asymmetric RTN. Returns (codes uint8, scale, zero), grouped shapes.

    codes: (..., n_groups, group) uint8 in [0, 2^bits-1]
    scale/zero: (..., n_groups) meta_dtype
    """
    codes, scale_w, zero_w = quantize_groups(
        group_reshape(x.astype(jnp.float32), group), bits, meta_dtype)
    return codes.astype(jnp.uint8), scale_w, zero_w


def dequantize(codes: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
               out_dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize`; returns the flat (..., n) tensor."""
    xg = dequantize_groups(codes, scale, zero)
    return cast(group_unreshape(xg), out_dtype)


def qdq(x: jnp.ndarray, bits: int, group: int,
        meta_dtype=jnp.bfloat16) -> jnp.ndarray:
    """quantize-dequantize (simulation helper for accuracy benches)."""
    codes, s, z = quantize(x, bits, group, meta_dtype)
    return dequantize(codes, s, z, out_dtype=x.dtype)


def qdq_ste(x: jnp.ndarray, bits: int, group: int) -> jnp.ndarray:
    """QDQ with a straight-through gradient (for training-time use)."""
    return x + jax.lax.stop_gradient(qdq(x, bits, group) - x)
