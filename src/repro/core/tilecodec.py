"""Shared wire-format tile bodies: the one codec implementation.

``encode_tile`` / ``decode_tile`` are the complete per-tile codec bodies
as pure ``(R, n) <-> (R, wire_bytes(n))`` array functions. They are THE
wire codec: the jnp reference backend (:mod:`repro.core.codec`), the
fused Pallas wire kernels (:mod:`repro.kernels.wire`), the fused RDMA
collectives (:mod:`repro.kernels.rdma_allreduce`,
:mod:`repro.kernels.rdma_all2all`) and their CPU emulation
(:mod:`repro.kernels.emulate`) all run these exact functions, so the
backends cannot drift byte-wise (tests/test_wire_golden.py,
tests/test_backend_equality.py).

Shape of the computation: the tile is transposed first, so the values
of a row run along the leading axis and the rows along the last one.
Groups are then a leading-axis reshape ``(n/group, group, R)``, group
min/max are reductions over that middle axis, and bit-plane packing and
the byte interleave of 2-byte metadata are leading-axis reshapes too.
On TPU these are sublane operations, which the Pallas compiler (Mosaic)
lowers; the same steps along the lane axis (a ``group``-wide lane split,
a strided lane slice, a bitcast that changes the element width) it
refuses. All bytes are carried as int32 until the one final
``uint8`` cast of the assembled ``(R, total)`` wire tile. The
word-level pack/unpack is :mod:`repro.core.wordpack`, the Eq.-1
scale/zero codec the transcendental-free exponent arithmetic of
:mod:`repro.core.scale_codec`.

Everything here is pure jnp — valid under jit/vmap/shard_map and inside
Pallas kernel bodies (interpret or compiled).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import rotation as rot
from repro.core import scale_codec, wordpack
from repro.core.comm_config import WireLayout, _wire_layout
from repro.core.quant import cast, dequantize_groups, quantize_groups
from repro.core.spike import spike_dequantize_groups, spike_quantize_groups


def tile_layout(n: int, *, bits: int, group: int, spike: bool,
                scale_int: bool) -> WireLayout:
    """The wire layout for one (R, n) tile (cached static offsets)."""
    return _wire_layout(n, bits, group, spike, scale_int)


def tile_kwargs(cfg, n: int) -> dict:
    """The static kwargs of the tile bodies for one comm site.

    The single builder every caller uses (ref codec, wire kernels, RDMA
    kernels, emulation) — add a codec knob here and each backend picks
    it up, instead of five hand-maintained dict literals drifting apart.
    """
    return dict(bits=cfg.bits, group=cfg.group, n=n, spike=cfg.spike,
                rotation=cfg.rotation, scale_int=cfg.scale_int,
                theta=cfg.theta, meta_dtype=jnp.dtype(cfg.meta_dtype))


def _meta_bytes(m: jnp.ndarray) -> jnp.ndarray:
    """(k, R) 2-byte meta values -> (2k, R) int32 bytes, little-endian
    pairs along the leading axis."""
    if m.dtype == jnp.bfloat16:     # bf16 bits are the top half of f32's
        bits = jax.lax.bitcast_convert_type(m.astype(jnp.float32),
                                            jnp.int32) >> 16
    else:
        bits = jax.lax.bitcast_convert_type(m, jnp.uint16).astype(jnp.int32)
    pair = jnp.stack([bits & 0xFF, (bits >> 8) & 0xFF], axis=1)
    return pair.reshape(2 * m.shape[0], *m.shape[1:])


def _bytes_meta(b: jnp.ndarray, dtype) -> jnp.ndarray:
    """(2k, R) int32 bytes -> (k, R) f32 holding the 2-byte meta values
    exactly (inverse of :func:`_meta_bytes`)."""
    pair = b.reshape(b.shape[0] // 2, 2, *b.shape[1:])
    bits = pair[:, 0] | (pair[:, 1] << 8)
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.bitcast_convert_type(bits << 16, jnp.float32)
    return jax.lax.bitcast_convert_type(
        bits.astype(jnp.uint16), jnp.dtype(dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# encode: float tile -> wire sections at layout offsets
# ---------------------------------------------------------------------------

def encode_sections(x: jnp.ndarray, *, bits: int, group: int, n: int,
                    spike: bool, scale_int: bool, theta: int, meta_dtype,
                    rotation: bool = False):
    """(R, n) float tile -> [(Section, (nbytes, R) int32 bytes), ...] in
    wire order — each section transposed, bytes along the leading axis.

    The single place the wire format is produced. With ``rotation`` each
    group is Hadamard-rotated (f32) before quantization — the wire then
    carries rotated coordinates under the identical section layout
    (spike sections are absent by construction: rotation replaces
    reserving).
    """
    assert x.shape[-1] == n, (x.shape, n)
    rows = x.shape[0]
    g = n // group
    layout = tile_layout(n, bits=bits, group=group, spike=spike,
                         scale_int=scale_int)

    if rotation:
        assert not spike
        x = rot.rotate(x, group)
    xg = x.astype(jnp.float32).T.reshape(g, group, rows)
    if spike:
        codes, scale_w, zero_w, vals, idx = spike_quantize_groups(
            xg, bits, meta_dtype, axis=1)
    else:
        codes, scale_w, zero_w = quantize_groups(xg, bits, meta_dtype,
                                                 axis=1)
    codes = codes.reshape(n, rows)

    out = []
    for (unit, span), (u2, plane) in zip(
            layout.planes, wordpack.pack_codes(codes, bits, axis=0)):
        assert unit == u2 and plane.shape[0] == span.nbytes
        out.append((span, plane))                         # bit splitting

    if scale_int:                                         # paper Eq. 1
        out.append((layout.scale, scale_codec.encode_scale(
            scale_w, theta).astype(jnp.int32) & 0xFF))
        out.append((layout.zero, scale_codec.encode_signed(
            zero_w, theta).astype(jnp.int32)))
    else:
        out.append((layout.scale, _meta_bytes(scale_w)))
        out.append((layout.zero, _meta_bytes(zero_w)))

    if spike:                                             # paper Fig. 5c
        # per group [min, max]: values exact at meta width, indices
        # int8 with scale_int (all < 128), meta-width otherwise
        sv = jnp.stack(vals, axis=1).reshape(2 * g, rows)
        out.append((layout.spike_vals, _meta_bytes(cast(sv, meta_dtype))))
        si = jnp.stack(idx, axis=1).reshape(2 * g, rows)
        out.append((layout.spike_idx, si if scale_int
                    else _meta_bytes(si.astype(meta_dtype))))
    return out


def encode_tile(x: jnp.ndarray, *, bits: int, group: int, n: int,
                spike: bool, scale_int: bool, theta: int,
                meta_dtype, rotation: bool = False) -> jnp.ndarray:
    """(R, n) float tile -> (R, wire_bytes(n)) uint8 wire tile (pure)."""
    secs = encode_sections(
        x, bits=bits, group=group, n=n, spike=spike, scale_int=scale_int,
        theta=theta, meta_dtype=meta_dtype, rotation=rotation)
    # the layout's sections are contiguous and in wire order
    assert [span.offset for span, _ in secs] == \
        [0] + [span.end for span, _ in secs[:-1]]
    buf = jnp.concatenate([sec for _, sec in secs], axis=0)
    return buf.T.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# decode: wire tile -> float tile
# ---------------------------------------------------------------------------

def decode_tile(wire: jnp.ndarray, *, bits: int, group: int, n: int,
                spike: bool, scale_int: bool, theta: int, meta_dtype,
                out_dtype, rotation: bool = False) -> jnp.ndarray:
    """(R, wire_bytes(n)) uint8 wire tile -> (R, n) out_dtype tile."""
    rows = wire.shape[0]
    g = n // group
    layout = tile_layout(n, bits=bits, group=group, spike=spike,
                         scale_int=scale_int)
    assert wire.shape[-1] == layout.total, (wire.shape, layout.total)
    w = wire.astype(jnp.int32).T                          # (total, R)

    def sec(span):
        return w[span.offset:span.end]

    def read_plane(i, unit, nbytes):
        span = layout.planes[i][1]
        assert span.nbytes == nbytes
        return sec(span)

    codes = wordpack.unpack_codes(read_plane, bits, n, axis=0)
    codes = codes.reshape(g, group, rows)

    if scale_int:
        sb = sec(layout.scale)
        scale = scale_codec.decode_scale(
            jnp.where(sb >= 128, sb - 256, sb), theta)    # int8 code
        zero = scale_codec.decode_signed(sec(layout.zero), theta)
    else:
        scale = _bytes_meta(sec(layout.scale), meta_dtype)
        zero = _bytes_meta(sec(layout.zero), meta_dtype)

    if spike:
        sv = _bytes_meta(sec(layout.spike_vals), meta_dtype)
        si = sec(layout.spike_idx)
        if not scale_int:
            si = _bytes_meta(si, meta_dtype).astype(jnp.int32)
        sv = sv.reshape(g, 2, rows)
        si = si.reshape(g, 2, rows)
        xg = spike_dequantize_groups(codes, scale, zero,
                                     (sv[:, 0], sv[:, 1]),
                                     (si[:, 0], si[:, 1]), axis=1)
    else:
        xg = dequantize_groups(codes, scale, zero, axis=1)
    out = xg.reshape(n, rows).T
    if rotation:
        out = rot.unrotate(out, group)
    return cast(out, out_dtype)
