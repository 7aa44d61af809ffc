"""Spike Reserving (paper Fig. 5): keep per-group min/max exact.

For each quantization group (paper default 32), the minimum and maximum —
the "spikes" — are removed from the group, stored exactly (value + int8
in-group index), and the remaining values are quantized against the
shrunk range. On dequantization the spikes are scattered back to their
original positions. This narrows the dynamic range dramatically
(paper Fig. 4) and makes INT2/INT3 usable.

Implementation: the old argmin/argmax + ``take_along_axis`` +
``nanmin``/``nanmax`` pipeline cost five variadic/gather reductions per
group — by far the hottest part of the low-bit encode path (XLA lowers
variadic arg-reductions and gathers to scalar loops on several
backends). It is now plain vectorized min/max reductions plus
first-match index selection:

* the spike *values* are a NaN-propagating min and max —
  no gather: the min/max of a group IS an element of it, bit-exactly;
* the spike *indices* are min reductions of masked positions: the
  first position matching the min, and the first two matching the max,
  so a group whose min and max collide on the same slot (constant
  groups, duplicated extremes, multi-NaN) still reserves two distinct
  slots with first-occurrence tie-breaking — exactly the old
  argmin/argmax-over-masked behaviour;
* the shrunk range is a min and a max with the spike slots (and NaNs,
  matching ``nanmin``/``nanmax``) masked out; a group whose remaining
  values are all NaN yields NaN scale/zero, ditto.

Every reduction is a plain ``min``/``max`` over the in-group axis (no
variadic ``lax.reduce``, which has no Pallas TPU lowering), so the
``*_groups`` forms also run inside compiled TPU kernels on the
transposed tiles of :mod:`repro.core.tilecodec`.

NaN semantics (diverged grads): a NaN group propagates NaN min/max, the
first NaN claims the min slot and the second NaN (if any) the max slot,
as before. The one deliberate change: a group with exactly ONE NaN used
to reserve its finite max as the second spike; it now forfeits the max
slot (both recorded spikes are the NaN) — the group is already poisoned,
and keeping the fast election is worth more than reserving a
finite extreme next to a NaN.

All of this is pure jnp (compare/select ops), used verbatim by
every backend — the jnp reference, the Pallas kernels and the RDMA
collectives — so spike bytes cannot drift between them.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from repro.core.quant import (cast, group_reshape, group_unreshape,
                              meta_scale, round_codes)

_EPS = 1e-12


class SpikeQuant(NamedTuple):
    codes: jnp.ndarray       # (..., n_groups, group) uint8
    scale: jnp.ndarray       # (..., n_groups) meta dtype
    zero: jnp.ndarray        # (..., n_groups) meta dtype
    spike_vals: jnp.ndarray  # (..., n_groups, 2) meta dtype  [min, max]
    spike_idx: jnp.ndarray   # (..., n_groups, 2) int8 in-group positions


def _spike_positions(eq_min, eq_max, pos, group: int, axis: int):
    """First ``eq_min`` position, and first and second ``eq_max``
    positions, over the in-group ``axis`` (``group`` where none)."""
    big = jnp.int32(group)
    imin = jnp.min(jnp.where(eq_min, pos, big), axis=axis)
    imax1 = jnp.min(jnp.where(eq_max, pos, big), axis=axis)
    second = eq_max & (pos != jnp.expand_dims(imax1, axis))
    imax2 = jnp.min(jnp.where(second, pos, big), axis=axis)
    return imin, imax1, imax2


def spike_quantize_groups(xg: jnp.ndarray, bits: int,
                          meta_dtype=jnp.bfloat16, axis: int = -1):
    """Spike-reserving RTN on a grouped f32 tensor whose ``axis`` runs
    within a group.

    Returns ``(codes int32, scale, zero, (vmin, vmax), (imin, imax))``:
    codes shaped like ``xg``, the rest with ``axis`` reduced away;
    spike values are f32 (exact elements of the group), indices int32.
    """
    axis %= xg.ndim
    group = xg.shape[axis]
    assert group <= 128, "in-group spike indices are int8 on the wire"
    qmax = float(2 ** bits - 1)
    pos = lax.broadcasted_iota(jnp.int32, xg.shape, axis)
    nan = jnp.isnan(xg)
    ex = functools.partial(jnp.expand_dims, axis=axis)

    # spike values: NaN-propagating min and max (the extreme of a group
    # is an element of it, so the value bits are exact)
    vmin, vmax = jnp.min(xg, axis=axis), jnp.max(xg, axis=axis)
    has_nan = ex(jnp.isnan(vmin))

    # spike indices: first min match, first + second max match (second
    # resolves min/max landing on the same slot: constant groups,
    # duplicated extremes, >= 2 NaNs)
    # (boolean and/or, not a select of masks: Mosaic has no i1 select)
    eq_min = (has_nan & nan) | (~has_nan & (xg == ex(vmin)))
    eq_max = (has_nan & nan) | (~has_nan & (xg == ex(vmax)))
    imin, imax1, imax2 = _spike_positions(eq_min, eq_max, pos, group, axis)
    imax = jnp.where(imax1 == imin, imax2, imax1)
    # single-NaN groups forfeit the max slot (imax2 is the out-of-range
    # sentinel); keep the wire index valid by pointing it at the min
    # slot — both spikes are the NaN, and the decode scatter writes the
    # same NaN there twice
    imax = jnp.where(imax == group, imin, imax)
    min_mask = pos == ex(imin)
    max_mask = pos == ex(imax)

    # Shrunk range over the remaining group-2 values (NaNs ignored, as
    # nanmin/nanmax did; all-NaN remainder -> NaN scale/zero, ditto).
    # Each side only needs its own spike slot masked: leaving the max in
    # cannot move a min (and vice versa), so the masks stay one compare.
    mn = jnp.min(jnp.where(min_mask | nan, jnp.inf, xg), axis=axis)
    mx = jnp.max(jnp.where(max_mask | nan, -jnp.inf, xg), axis=axis)
    # both extremes untouched by data <=> every remaining value was NaN
    all_dropped = (mn == jnp.inf) & (mx == -jnp.inf)
    mn = jnp.where(all_dropped, jnp.float32(jnp.nan), mn)
    mx = jnp.where(all_dropped, jnp.float32(jnp.nan), mx)

    scale_w = meta_scale(mx - mn, qmax, meta_dtype)
    zero_w = cast(mn, meta_dtype)
    s = scale_w.astype(jnp.float32)
    z = zero_w.astype(jnp.float32)
    # Spike slots are set to the new minimum before quantization (paper:
    # "set them to zeros" of the shrunk range); their codes are dummies
    # overwritten on dequant. Quantizing xg everywhere and patching the
    # spike slots with the (per-group) code of `mn` afterwards is the
    # same arithmetic per element.
    codes = round_codes(xg - ex(z), ex(s), qmax)
    code_mn = round_codes(mn - z, s, qmax)
    codes = jnp.where(min_mask | max_mask, ex(code_mn), codes)
    return codes, scale_w, zero_w, (vmin, vmax), (imin, imax)


def spike_dequantize_groups(codes, scale, zero, spike_vals, spike_idx,
                            axis: int = -1) -> jnp.ndarray:
    """Inverse of :func:`spike_quantize_groups`: grouped f32 result.

    ``spike_vals`` / ``spike_idx`` are the (min, max) pairs, each with
    ``axis`` reduced away; the exact spikes are written back by one-hot
    selects (the group is small).
    """
    ex = functools.partial(jnp.expand_dims, axis=axis)
    xg = (codes.astype(jnp.float32) * ex(scale.astype(jnp.float32))
          + ex(zero.astype(jnp.float32)))
    pos = lax.broadcasted_iota(jnp.int32, xg.shape, axis % xg.ndim)
    for val, idx in zip(spike_vals, spike_idx):
        hit = pos == ex(idx.astype(jnp.int32))
        xg = jnp.where(hit, ex(val.astype(jnp.float32)), xg)
    return xg


def spike_quantize(x: jnp.ndarray, bits: int, group: int,
                   meta_dtype=jnp.bfloat16) -> SpikeQuant:
    codes, scale_w, zero_w, vals, idx = spike_quantize_groups(
        group_reshape(x.astype(jnp.float32), group), bits, meta_dtype)
    spike_vals = cast(jnp.stack(vals, axis=-1), meta_dtype)
    spike_idx = jnp.stack(idx, axis=-1).astype(jnp.int8)
    return SpikeQuant(codes.astype(jnp.uint8), scale_w, zero_w,
                      spike_vals, spike_idx)


def spike_dequantize(q: SpikeQuant, out_dtype=jnp.float32) -> jnp.ndarray:
    codes, scale, zero, spike_vals, spike_idx = q
    xg = spike_dequantize_groups(
        codes, scale, zero, (spike_vals[..., 0], spike_vals[..., 1]),
        (spike_idx[..., 0], spike_idx[..., 1]))
    return cast(group_unreshape(xg), out_dtype)


def spike_qdq(x: jnp.ndarray, bits: int, group: int,
              meta_dtype=jnp.bfloat16) -> jnp.ndarray:
    return spike_dequantize(spike_quantize(x, bits, group, meta_dtype),
                            out_dtype=x.dtype)
