"""Bit-plane pack/unpack: the codec's innermost loop.

One shared implementation of the paper's bit-splitting plane layout for
every call site — the pure-jnp reference codec (:mod:`repro.core.bitsplit`,
:mod:`repro.core.codec`), the fused Pallas wire kernels
(:mod:`repro.kernels.wire`, ``quant_pack``, ``dequant_unpack``,
``spike_reserve``) and the fused RDMA collectives — so the backends
cannot drift byte-wise.

Values run along ``axis``. Packing splits that axis into
``(n / per, per)`` with ``per = 8 // unit`` and ORs the ``per`` shifted
slices together; unpacking stacks the ``per`` shifted/masked fields on a
new axis and merges it back. The wire kernels call these with
``axis=0`` on a transposed tile, where the split is a sublane reshape:
the form the TPU compiler (Mosaic) lowers, unlike a strided lane slice.

Byte layout: LSB-first within each byte, values packed in index order —
golden wire vectors pin it (tests/test_wire_golden.py). The arithmetic
runs in the input dtype (every packed value fits a byte), so uint8 and
int32 codes give the same bytes.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core.comm_config import BIT_UNITS


def plane_nbytes(n: int, unit: int) -> int:
    """Wire bytes for one ``unit``-bit plane of ``n`` values (ceil)."""
    return (n * unit + 7) // 8


def pack_plane(field: jnp.ndarray, unit: int, axis: int = -1) -> jnp.ndarray:
    """Sub-byte values (< 2^unit) along ``axis`` -> ceil(n*unit/8) bytes.

    LSB-first within each byte: byte ``b`` holds values
    ``b*per .. b*per+per-1`` at bit offsets ``0, unit, 2*unit, ...``.
    Tails (n not a multiple of ``8 // unit``) are zero-padded, matching
    :func:`unpack_plane`'s trailing slice.
    """
    if unit == 8:
        return field
    assert unit in (1, 2, 4), unit
    per = 8 // unit
    axis %= field.ndim
    n = field.shape[axis]
    rem = (-n) % per
    if rem:
        pad = [(0, 0)] * field.ndim
        pad[axis] = (0, rem)
        field = jnp.pad(field, pad)
    shape = field.shape
    v = field.reshape(shape[:axis] + (shape[axis] // per, per)
                      + shape[axis + 1:])
    out = lax.index_in_dim(v, 0, axis + 1, keepdims=False)
    for k in range(1, per):
        out = out | (lax.index_in_dim(v, k, axis + 1, keepdims=False)
                     << (unit * k))
    return out


def unpack_plane(packed: jnp.ndarray, unit: int, n: int,
                 axis: int = -1) -> jnp.ndarray:
    """ceil(n*unit/8) bytes along ``axis`` -> n plane values (same dtype).

    Exact inverse of :func:`pack_plane` (zero-padded tail sliced off).
    """
    if unit == 8:
        return packed
    assert unit in (1, 2, 4), unit
    per = 8 // unit
    axis %= packed.ndim
    mask = (1 << unit) - 1
    v = jnp.stack([(packed >> (unit * k)) & mask for k in range(per)],
                  axis=axis + 1)
    shape = packed.shape
    v = v.reshape(shape[:axis] + (shape[axis] * per,) + shape[axis + 1:])
    if v.shape[axis] != n:
        v = lax.slice_in_dim(v, 0, n, axis=axis)
    return v


def pack_codes(codes: jnp.ndarray, bits: int, axis: int = -1) -> list:
    """Split codes (values along ``axis``) into the bit-split planes.

    Returns ``[(unit, packed_plane), ...]`` in wire order (regular part
    first, then the extra bit planes — paper Fig. 3). The caller places
    each plane at its :func:`repro.core.comm_config.CommConfig.wire_layout`
    offset.
    """
    planes = []
    shift = 0
    for unit in BIT_UNITS[bits]:
        field = (codes >> shift) & ((1 << unit) - 1)
        planes.append((unit, pack_plane(field, unit, axis)))
        shift += unit
    return planes


def unpack_codes(read_plane, bits: int, n: int, axis: int = -1):
    """Rebuild the n codes along ``axis`` from the bit-split planes.

    ``read_plane(plane_index, unit, nbytes)`` returns the packed bytes of
    plane ``plane_index`` (so callers can slice a wire buffer or a ref at
    layout offsets without materialising the payload twice).
    """
    out = None
    shift = 0
    for i, unit in enumerate(BIT_UNITS[bits]):
        plane = read_plane(i, unit, plane_nbytes(n, unit))
        vals = unpack_plane(plane, unit, n, axis)
        contrib = vals if shift == 0 else vals << shift
        out = contrib if out is None else out | contrib
        shift += unit
    return out
