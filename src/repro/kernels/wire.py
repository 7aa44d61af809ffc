"""Fused *complete wire format* encode/decode Pallas kernels.

The earlier kernels (:mod:`repro.kernels.quant_pack`,
:mod:`repro.kernels.spike_reserve`) stop at raw payload/scale/zero
tensors. These kernels go all the way: one grid step reads a
``(block_rows, n)`` float tile from VMEM and writes the full
``(block_rows, wire_bytes(n))`` uint8 wire tile —

    [bit-split packed codes | scales | zeros | spike vals | spike idx]

— with every section at its
:meth:`repro.core.comm_config.CommConfig.wire_layout` offset, including
the integer-log scale/zero encoding (paper Eq. 1, transcendental-free
exponent arithmetic) and the spike-reserving metadata (paper Fig. 5c).
The tensor is read from HBM exactly once and only wire bytes leave the
kernel.

The kernel bodies are :mod:`repro.core.tilecodec` — the same functions
the pure-jnp reference backend runs — so the byte layout is identical to
:mod:`repro.core.codec` by construction (enforced anyway by
tests/test_backend_equality.py and the golden vectors);
tests/test_tpu_compile.py compiles them for a described v5e.

``block_rows`` is picked by the dispatchers in :mod:`repro.kernels.ops`
from the tile size (whole-array single grid step off-TPU; VMEM-budgeted
multiple of 8 sublanes on TPU, with rows longer than one step takes cut
into sub-rows there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.comm_config import CommConfig
# Shared tile bodies (re-exported: the RDMA kernels and the emulation
# import them from here so all fused call sites read as one module).
from repro.core.tilecodec import (decode_tile, encode_tile,  # noqa: F401
                                  tile_kwargs)

_cfg_kw = tile_kwargs


# ---------------------------------------------------------------------------
# encode: float tile -> wire tile (sections written at layout offsets)
# ---------------------------------------------------------------------------

def _encode_kernel(x_ref, wire_ref, *, kw):
    wire_ref[...] = encode_tile(x_ref[...], **kw)


@functools.partial(jax.jit,
                   static_argnames=("bits", "group", "spike", "rotation",
                                    "scale_int", "theta", "meta_dtype",
                                    "block_rows", "interpret"))
def encode_wire(x: jnp.ndarray, *, bits: int, group: int, spike: bool,
                scale_int: bool, theta: int = 10,
                meta_dtype: str = "bfloat16", rotation: bool = False,
                block_rows: int | None = None,
                interpret: bool = True):
    """(R, n) float -> (R, wire_bytes(n)) uint8 complete wire buffer.

    R must be a multiple of ``block_rows`` (default: one grid step over
    the whole array; the wrappers in ops.py pad and pick the block).
    """
    rows, n = x.shape
    block = block_rows or rows
    assert rows % block == 0 and n % group == 0
    cfg = CommConfig(bits=bits, group=group, spike=spike,
                     rotation=rotation, scale_int=scale_int, theta=theta,
                     meta_dtype=meta_dtype)
    wb = cfg.wire_bytes(n)
    kw = _cfg_kw(cfg, n)
    grid = (rows // block,)
    return pl.pallas_call(
        functools.partial(_encode_kernel, kw=kw),
        name="wire_encode",
        grid=grid,
        in_specs=[pl.BlockSpec((block, n), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((block, wb), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, wb), jnp.uint8)],
        interpret=interpret,
    )(x)[0]


# ---------------------------------------------------------------------------
# decode: wire tile -> float tile
# ---------------------------------------------------------------------------

def _decode_kernel(wire_ref, out_ref, *, kw, out_dtype):
    out_ref[...] = decode_tile(wire_ref[...], out_dtype=out_dtype, **kw)


@functools.partial(jax.jit,
                   static_argnames=("bits", "group", "n", "spike",
                                    "rotation", "scale_int", "theta",
                                    "meta_dtype", "out_dtype", "block_rows",
                                    "interpret"))
def decode_wire(buf: jnp.ndarray, *, bits: int, group: int, n: int,
                spike: bool, scale_int: bool, theta: int = 10,
                meta_dtype: str = "bfloat16", rotation: bool = False,
                out_dtype=jnp.float32,
                block_rows: int | None = None, interpret: bool = True):
    """(R, wire_bytes(n)) uint8 -> (R, n) out_dtype. Inverse of encode."""
    rows = buf.shape[0]
    block = block_rows or rows
    assert rows % block == 0
    cfg = CommConfig(bits=bits, group=group, spike=spike,
                     rotation=rotation, scale_int=scale_int, theta=theta,
                     meta_dtype=meta_dtype)
    wb = cfg.wire_bytes(n)
    assert buf.shape == (rows, wb), (buf.shape, (rows, wb))
    kw = _cfg_kw(cfg, n)
    grid = (rows // block,)
    return pl.pallas_call(
        functools.partial(_decode_kernel, kw=kw,
                          out_dtype=jnp.dtype(out_dtype)),
        grid=grid,
        in_specs=[pl.BlockSpec((block, wb), lambda r: (r, 0))],
        out_specs=[pl.BlockSpec((block, n), lambda r: (r, 0))],
        name="wire_decode",
        out_shape=[jax.ShapeDtypeStruct((rows, n), jnp.dtype(out_dtype))],
        interpret=interpret,
    )(buf)[0]
