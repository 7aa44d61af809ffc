"""Lockstep CPU emulation of the fused RDMA collectives.

The real things (:mod:`repro.kernels.rdma_allreduce`,
:mod:`repro.kernels.rdma_all2all`) run Pallas kernels on TPU: quantize +
bit-split pack + RDMA push (``make_async_remote_copy``) + dequant
(+ local reduce for the AllReduce), all in VMEM. Remote DMA does not
execute in plain interpret mode off-TPU, so this module runs the *same*
kernel bodies —
:func:`repro.kernels.wire.encode_tile` /
:func:`repro.kernels.wire.decode_tile`, the exact functions the RDMA
kernels call — as interpret-mode ``pallas_call``s on every shard, and
replaces only the RDMA hop with the XLA collective the hardware push is
equivalent to (``all_to_all`` for the scatter phase and the A2A
dispatch, ``all_gather`` for the gather phase) inside shard_map.

Because the tile bodies are shared, the bytes this emulation puts on the
(emulated) link are identical to both ``codec.encode`` and the compiled
RDMA kernels' send buffers — enforced by tests/test_wire_golden.py,
tests/test_fused_allreduce.py and tests/test_fused_all2all.py.

Off-TPU (``interpret=True``) the phase functions run the tile bodies
*directly* as jitted jnp instead of through interpret-mode
``pallas_call``: interpret mode adds per-call state-discharge machinery
with zero fidelity gain here (the discharged computation is the very
same jnp graph), and it made the emulated fused schemes measurably
slower than the unfused two-step they are byte-identical to
(benchmarks/results/collectives.json, the old 13.3 ms vs 7.0 ms
int4 inversion). ``interpret=False`` keeps the real ``pallas_call``
path for TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.comm_config import CommConfig
from repro.kernels.wire import _cfg_kw, decode_tile, encode_tile


def _hashable_kw(cfg: CommConfig, chunk: int) -> tuple:
    return tuple(sorted(_cfg_kw(cfg, chunk).items()))


@functools.lru_cache(maxsize=None)
def _encode_fn(kw_items: tuple):
    """Jitted direct tile-body encode (cached per static config)."""
    return jax.jit(functools.partial(encode_tile, **dict(kw_items)))


@functools.lru_cache(maxsize=None)
def _decode_fn(kw_items: tuple, out_dtype, reduce_rows: bool):
    kw = dict(kw_items)

    def run(wire):
        out = decode_tile(wire, out_dtype=out_dtype, **kw)
        if reduce_rows:
            out = jnp.sum(out, axis=0, keepdims=True)
        return out

    return jax.jit(run)


# ---------------------------------------------------------------------------
# per-phase kernels (grid=(1,), whole-shard tiles — shard shapes are small
# and per-device, so no row tiling is needed here)
# ---------------------------------------------------------------------------

def _encode_kernel(x_ref, wire_ref, *, kw):
    wire_ref[...] = encode_tile(x_ref[...], **kw)


def _decode_reduce_kernel(wire_ref, partial_ref, *, kw, out_dtype):
    parts = decode_tile(wire_ref[...], out_dtype=out_dtype, **kw)
    partial_ref[...] = jnp.sum(parts, axis=0, keepdims=True)


def _decode_kernel(wire_ref, out_ref, *, kw, out_dtype):
    out_ref[...] = decode_tile(wire_ref[...], out_dtype=out_dtype, **kw)


def encode_rows(x: jnp.ndarray, cfg: CommConfig,
                interpret: bool = True) -> jnp.ndarray:
    """(R, chunk) float -> (R, wire_bytes(chunk)) uint8, one kernel pass.

    The phase-1 "quantize + pack" body (and, with R == 1, the phase-2
    re-quantize body) of the fused AllReduce.
    """
    rows, chunk = x.shape
    wb = cfg.wire_bytes(chunk)
    if interpret:                        # off-TPU: run the body directly
        if isinstance(x, jax.core.Tracer):
            # already under jit/shard_map: inline so XLA can fuse the
            # codec into the surrounding collective schedule
            return encode_tile(x, **_cfg_kw(cfg, chunk))
        # eager (tests): jit the body so FMA contraction matches the
        # jitted reference codec bit-for-bit
        return _encode_fn(_hashable_kw(cfg, chunk))(x)
    return pl.pallas_call(
        functools.partial(_encode_kernel, kw=_cfg_kw(cfg, chunk)),
        out_shape=jax.ShapeDtypeStruct((rows, wb), jnp.uint8),
        interpret=interpret,
    )(x)


def decode_reduce_rows(wire: jnp.ndarray, cfg: CommConfig, chunk: int,
                       interpret: bool = True) -> jnp.ndarray:
    """(R, wb) uint8 -> (1, chunk) f32: fused dequant + local reduce."""
    rows = wire.shape[0]
    assert wire.shape == (rows, cfg.wire_bytes(chunk))
    if interpret:
        if isinstance(wire, jax.core.Tracer):
            parts = decode_tile(wire, out_dtype=jnp.float32,
                                **_cfg_kw(cfg, chunk))
            return jnp.sum(parts, axis=0, keepdims=True)
        return _decode_fn(_hashable_kw(cfg, chunk), jnp.float32,
                          True)(wire)
    return pl.pallas_call(
        functools.partial(_decode_reduce_kernel, kw=_cfg_kw(cfg, chunk),
                          out_dtype=jnp.float32),
        out_shape=jax.ShapeDtypeStruct((1, chunk), jnp.float32),
        interpret=interpret,
    )(wire)


def decode_rows(wire: jnp.ndarray, cfg: CommConfig, chunk: int,
                interpret: bool = True,
                out_dtype=jnp.float32) -> jnp.ndarray:
    """(R, wb) uint8 -> (R, chunk): the receive-side dequant.

    The phase-2 gather dequant of the fused AllReduce (f32 default) and,
    with ``out_dtype`` set, the A2A receive dequant (payload dtype).
    """
    rows = wire.shape[0]
    assert wire.shape == (rows, cfg.wire_bytes(chunk))
    if interpret:
        if isinstance(wire, jax.core.Tracer):
            return decode_tile(wire, out_dtype=jnp.dtype(out_dtype),
                               **_cfg_kw(cfg, chunk))
        return _decode_fn(_hashable_kw(cfg, chunk), jnp.dtype(out_dtype),
                          False)(wire)
    return pl.pallas_call(
        functools.partial(_decode_kernel, kw=_cfg_kw(cfg, chunk),
                          out_dtype=jnp.dtype(out_dtype)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), jnp.dtype(out_dtype)),
        interpret=interpret,
    )(wire)


# ---------------------------------------------------------------------------
# the emulated two-step AllReduce (runs inside shard_map)
# ---------------------------------------------------------------------------

def fused_all_reduce_emulated(x: jnp.ndarray, axis: str, cfg: CommConfig,
                              groups=None,
                              interpret: bool = True) -> jnp.ndarray:
    """Flash two-step AR, fused-kernel choreography, RDMA emulated.

    Phase 1 (scatter-reduce): one kernel encodes the tp per-peer chunks
    into wire rows; the RDMA all-to-all push is emulated with
    ``lax.all_to_all`` on the wire bytes; a second kernel dequantizes the
    received rows and reduces them in the same pass.

    Phase 2 (gather): the partial sum is re-encoded (same encode kernel,
    R=1), the push-to-all is emulated with ``lax.all_gather``, and one
    kernel dequantizes all tp wire rows back to the full vector.
    """
    if groups is not None:
        tp = len(groups[0])
    else:
        tp = jax.lax.axis_size(axis)
    n = x.shape[-1]
    assert n % tp == 0 and (n // tp) % cfg.group == 0, (n, tp, cfg.group)
    chunk = n // tp

    xc = x.reshape(tp, chunk).astype(jnp.float32)
    wire = encode_rows(xc, cfg, interpret)                  # (tp, wb)
    recv = lax.all_to_all(wire, axis, 0, 0, tiled=True,
                          axis_index_groups=groups)         # rows from peers
    partial = decode_reduce_rows(recv, cfg, chunk, interpret)   # (1, chunk)
    wire2 = encode_rows(partial, cfg, interpret)            # (1, wb)
    allw = lax.all_gather(wire2, axis, axis=0, tiled=True,
                          axis_index_groups=groups)         # (tp, wb)
    full = decode_rows(allw, cfg, chunk, interpret)         # (tp, chunk)
    return full.reshape(n).astype(x.dtype)


# ---------------------------------------------------------------------------
# the emulated fused All2All (runs inside shard_map)
# ---------------------------------------------------------------------------

def fused_all_to_all_emulated(x: jnp.ndarray, axis: str, cfg: CommConfig,
                              groups=None,
                              interpret: bool = True) -> jnp.ndarray:
    """Fused quantized A2A choreography, RDMA emulated.

    One kernel encodes all ``tp`` per-peer blocks of ``x`` (shape
    ``(tp, ..., d)``, ``d`` a group multiple — the collectives layer
    pads) into wire rows; the per-peer RDMA push of
    :mod:`repro.kernels.rdma_all2all` is emulated with
    ``lax.all_to_all`` on the wire bytes; a second kernel dequantizes
    the received blocks straight to the payload dtype. Bit-identical to
    the XLA ``quantized_all_to_all`` wire (same encode bytes, same hop,
    same dequant body — tests/_multidev_script.py ``fused_a2a``).
    """
    if groups is not None:
        tp = len(groups[0])
    else:
        tp = jax.lax.axis_size(axis)
    assert x.shape[0] == tp, (x.shape, tp)
    d = x.shape[-1]
    assert d % cfg.group == 0, (d, cfg.group)
    wb = cfg.wire_bytes(d)
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    m = rows // tp

    wire = encode_rows(x.reshape(rows, d), cfg, interpret)  # (tp*m, wb)
    recv = lax.all_to_all(wire.reshape(tp, m, wb), axis, 0, 0, tiled=True,
                          axis_index_groups=groups)         # blocks from peers
    out = decode_rows(recv.reshape(rows, wb), cfg, d, interpret,
                      out_dtype=x.dtype)
    return out.reshape(x.shape)
