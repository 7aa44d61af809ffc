"""Jit'd dispatch wrappers for the Pallas kernels.

``use_pallas=None`` (default) picks the Pallas path on TPU and the pure-jnp
reference path elsewhere; ``interpret`` mode is selected automatically on
CPU so the kernels stay testable in this container.

The dispatchers pick the kernel row block from the tile size instead of
a fixed 8-row grid: off-TPU (interpret mode) the whole array is one grid
step — interpret-mode ``pallas_call`` pays a large per-grid-step overhead,
so an 8-row block turned every encode into ``R/8`` sequential interpreted
tiles; on TPU the block is VMEM-budgeted (~2 MB of float tile per step)
and rounded to the 8-sublane quantum; a row longer than ``_MAX_COLS``
values is cut into sub-rows of whole groups, and the sub-rows' wire
sections are spliced back into the row's. Rows are padded to the chosen
block transparently, which for the single-step case means no padding.
The underlying kernel entry points are ``jax.jit``-cached per
(shape, config, block) so repeated dispatches reuse one closure.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dequant_unpack import dequant_unpack
from repro.kernels.quant_pack import ROW_BLOCK, quant_pack
from repro.kernels.spike_reserve import spike_pack
from repro.kernels.wire import decode_wire, encode_wire

# VMEM budget for one compiled-TPU float tile (bytes). ~2 MB leaves room
# for the wire output + double buffering inside the ~16 MB/core VMEM.
_TILE_BUDGET = 2 << 20

# Widest row one compiled grid step takes. A longer row (the flattened
# all-reduce message is one row of the whole activation) is encoded as
# sub-rows of whole groups, and its wire sections are spliced back
# together outside the kernel: the bytes are those of the whole row.
_MAX_COLS = 8192


def _backend() -> str:
    return jax.default_backend()


def mesh_axis_names() -> tuple:
    """All mesh axis names, in mesh order, from inside shard_map.

    The fused RDMA collectives address peers by full MESH coordinates,
    so they need every axis of the mesh, not just the communicated one.
    """
    names = tuple(jax.sharding.get_abstract_mesh().axis_names)
    assert names, "the fused collectives run inside shard_map over a mesh"
    return names


def _pick_block(rows: int, n: int, on_tpu: bool) -> int:
    """Kernel row block for an (rows, n) float tile.

    TPU blocks are VMEM-budgeted and split the rows EVENLY across grid
    steps (rounded up to the 8-sublane quantum), so padding never
    exceeds ROW_BLOCK-1 rows — naively rounding the budget down would
    pad e.g. 65 rows to 128 (a near-2x compute blowup) instead of 72.
    """
    if not on_tpu:
        return rows                     # interpret mode: one grid step
    cap = max(ROW_BLOCK, _TILE_BUDGET // (4 * n))
    steps = -(-rows // cap)             # grid steps at the VMEM cap
    per = -(-rows // steps)             # even rows per step
    return -(-per // ROW_BLOCK) * ROW_BLOCK


def _col_split(n: int, group: int, on_tpu: bool) -> int:
    """Sub-rows a compiled kernel cuts an n-value row into (1 = none):
    the fewest whose width is at most ``_MAX_COLS`` and a whole number
    of groups and of bytes in every bit plane."""
    if not on_tpu or n <= _MAX_COLS:
        return 1
    unit = math.lcm(group, 8)
    for k in range(-(-n // _MAX_COLS), n // unit + 1):
        if n % k == 0 and (n // k) % unit == 0:
            return k
    return 1


def _spans(cfg, n: int) -> list:
    """The wire sections of an n-value row, in wire order."""
    lay = cfg.wire_layout(n)
    return ([span for _, span in lay.planes] + [lay.scale, lay.zero]
            + [s for s in (lay.spike_vals, lay.spike_idx) if s is not None])


def _join_rows(sub: jnp.ndarray, cfg, n: int, k: int) -> jnp.ndarray:
    """(R*k, wire(n/k)) sub-row wires -> (R, wire(n)) row wires: each
    section of a row is its sub-rows' sections, in order."""
    rows = sub.shape[0] // k
    sub = sub.reshape(rows, k, -1)
    return jnp.concatenate([sub[:, :, s.offset:s.end].reshape(rows, -1)
                            for s in _spans(cfg, n // k)], axis=1)


def _split_rows(buf: jnp.ndarray, cfg, n: int, k: int) -> jnp.ndarray:
    """Inverse of :func:`_join_rows`."""
    rows = buf.shape[0]
    return jnp.concatenate([buf[:, s.offset:s.end].reshape(rows, k, -1)
                            for s in _spans(cfg, n)], axis=2
                           ).reshape(rows * k, -1)


def _pad_rows(x: jnp.ndarray, block: int):
    rows = x.shape[0]
    rem = (-rows) % block
    if rem:
        x = jnp.pad(x, ((0, rem), (0, 0)))
    return x, rows


def fused_quant_pack(x: jnp.ndarray, bits: int, group: int,
                     use_pallas: bool | None = None):
    """(R, n) -> (payload, scale, zero). Pallas on TPU, ref elsewhere."""
    if use_pallas is None:
        use_pallas = _backend() == "tpu"
    if not use_pallas:
        return ref.quant_pack_ref(x, bits, group)
    on_tpu = _backend() == "tpu"
    block = _pick_block(x.shape[0], x.shape[1], on_tpu)
    xp, rows = _pad_rows(x, block)
    p, s, z = quant_pack(xp, bits=bits, group=group, block_rows=block,
                         interpret=not on_tpu)
    return p[:rows], s[:rows], z[:rows]


def fused_dequant_unpack(payload, scale, zero, bits: int, group: int,
                         n: int, out_dtype=jnp.float32,
                         use_pallas: bool | None = None):
    if use_pallas is None:
        use_pallas = _backend() == "tpu"
    if not use_pallas:
        return ref.dequant_unpack_ref(payload, scale, zero, bits, group, n,
                                      out_dtype)
    on_tpu = _backend() == "tpu"
    block = _pick_block(payload.shape[0], n, on_tpu)
    pp, rows = _pad_rows(payload, block)
    sp, _ = _pad_rows(scale, block)
    zp, _ = _pad_rows(zero, block)
    out = dequant_unpack(pp, sp, zp, bits=bits, group=group, n=n,
                         out_dtype=out_dtype, block_rows=block,
                         interpret=not on_tpu)
    return out[:rows]


def fused_spike_pack(x: jnp.ndarray, bits: int, group: int,
                     use_pallas: bool | None = None):
    """(R, n) -> (payload, scale, zero, spike_vals, spike_idx)."""
    if use_pallas is None:
        use_pallas = _backend() == "tpu"
    if not use_pallas:
        return ref.spike_pack_ref(x, bits, group)
    on_tpu = _backend() == "tpu"
    block = _pick_block(x.shape[0], x.shape[1], on_tpu)
    xp, rows = _pad_rows(x, block)
    outs = spike_pack(xp, bits=bits, group=group, block_rows=block,
                      interpret=not on_tpu)
    return tuple(o[:rows] for o in outs)


# --------------------------------------------------------------------------
# complete wire format (the codec's pallas backend)
# --------------------------------------------------------------------------

def fused_encode_wire(x: jnp.ndarray, cfg, use_pallas: bool | None = None):
    """(R, n) float -> (R, cfg.wire_bytes(n)) uint8 full wire buffer.

    The fused analogue of ``repro.core.codec.encode`` for 2-D inputs:
    payload, scale/zero (optionally Eq.-1 log-encoded) and the spike
    sections are assembled in one kernel pass.
    """
    if use_pallas is None:
        use_pallas = _backend() == "tpu"
    if not use_pallas:
        from repro.core import codec
        return codec.encode_ref(x, cfg)
    on_tpu = _backend() == "tpu"
    rows, n = x.shape
    k = _col_split(n, cfg.group, on_tpu)
    if k > 1:
        sub = fused_encode_wire(x.reshape(rows * k, n // k), cfg, True)
        return _join_rows(sub, cfg, n, k)
    block = _pick_block(x.shape[0], x.shape[1], on_tpu)
    xp, rows = _pad_rows(x, block)
    buf = encode_wire(xp, bits=cfg.bits, group=cfg.group, spike=cfg.spike,
                      rotation=cfg.rotation, scale_int=cfg.scale_int,
                      theta=cfg.theta, meta_dtype=cfg.meta_dtype,
                      block_rows=block, interpret=not on_tpu)
    return buf[:rows]


def fused_decode_wire(buf: jnp.ndarray, cfg, n: int,
                      out_dtype=jnp.float32,
                      use_pallas: bool | None = None):
    """(R, cfg.wire_bytes(n)) uint8 -> (R, n) out_dtype."""
    if use_pallas is None:
        use_pallas = _backend() == "tpu"
    if not use_pallas:
        from repro.core import codec
        return codec.decode_ref(buf, cfg, n, out_dtype)
    on_tpu = _backend() == "tpu"
    k = _col_split(n, cfg.group, on_tpu)
    if k > 1:
        rows = buf.shape[0]
        out = fused_decode_wire(_split_rows(buf, cfg, n, k), cfg, n // k,
                                out_dtype, True)
        return out.reshape(rows, n)
    block = _pick_block(buf.shape[0], n, on_tpu)
    bp, rows = _pad_rows(buf, block)
    out = decode_wire(bp, bits=cfg.bits, group=cfg.group, n=n,
                      spike=cfg.spike, rotation=cfg.rotation,
                      scale_int=cfg.scale_int, theta=cfg.theta,
                      meta_dtype=cfg.meta_dtype, out_dtype=out_dtype,
                      block_rows=block, interpret=not on_tpu)
    return out[:rows]


# --------------------------------------------------------------------------
# fused two-step AllReduce (CommConfig.scheme == "fused")
# --------------------------------------------------------------------------

def fused_all_reduce(x: jnp.ndarray, axis: str, cfg,
                     groups=None,
                     mesh_axes: Sequence[str] | None = None) -> jnp.ndarray:
    """Fused-kernel two-step AR on a flat (n,) vector (inside shard_map).

    TPU: the real RDMA kernels (``repro.kernels.rdma_allreduce``) —
    quantize + pack + ``make_async_remote_copy`` push + dequant + reduce,
    one Pallas kernel per phase. Elsewhere (and for ``tp == 1`` or
    ``axis_index_groups``, which the RDMA addressing doesn't cover): the
    lockstep emulation (``repro.kernels.emulate``) running the same tile
    bodies in interpret mode with the push emulated by XLA collectives.

    ``mesh_axes`` (all mesh axis names, mesh order) is needed for MESH
    device addressing on multi-axis meshes; when not given it is read
    from the ambient shard_map axis env.
    """
    from repro.kernels import emulate
    on_tpu = _backend() == "tpu"
    if on_tpu and groups is None and jax.lax.axis_size(axis) > 1:
        from repro.kernels import rdma_allreduce
        return rdma_allreduce.fused_all_reduce_rdma(
            x, axis, cfg, mesh_axes=mesh_axes or mesh_axis_names())
    return emulate.fused_all_reduce_emulated(x, axis, cfg, groups=groups,
                                             interpret=not on_tpu)


# --------------------------------------------------------------------------
# fused quantized All2All (CommConfig.scheme == "fused", MoE dispatch)
# --------------------------------------------------------------------------

def fused_all_to_all(x: jnp.ndarray, axis: str, cfg,
                     groups=None,
                     mesh_axes: Sequence[str] | None = None) -> jnp.ndarray:
    """Fused-kernel A2A on a (tp, ..., d) block tensor (inside shard_map).

    TPU: the real RDMA kernel (``repro.kernels.rdma_all2all``) —
    quantize + pack + one ``make_async_remote_copy`` chunk per
    destination rank + dequant, a single Pallas kernel. Elsewhere (and
    for ``tp == 1`` or ``axis_index_groups``, which the RDMA addressing
    doesn't cover): the lockstep emulation (``repro.kernels.emulate``)
    running the same tile bodies with the push emulated by
    ``lax.all_to_all``. ``d`` must be a group multiple (the collectives
    layer pads and unpads around this call).
    """
    from repro.kernels import emulate
    on_tpu = _backend() == "tpu"
    if on_tpu and groups is None and jax.lax.axis_size(axis) > 1:
        from repro.kernels import rdma_all2all
        return rdma_all2all.fused_all_to_all_rdma(
            x, axis, cfg, mesh_axes=mesh_axes or mesh_axis_names())
    return emulate.fused_all_to_all_emulated(x, axis, cfg, groups=groups,
                                             interpret=not on_tpu)
