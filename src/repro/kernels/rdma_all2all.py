"""Fused quantized All2All as a Pallas RDMA kernel (TPU).

The paper's second headline number — up to 2x All2All speedup — comes
from the MoE expert-parallel dispatch riding the same fused schedule as
the AllReduce: the dispatch buffer is read once, quantized, bit-split
packed, and only the wire bytes cross the link, with dequant happening
in the same kernel on the receiving side. This module is that schedule
on TPU, one ``pallas_call`` for the whole collective (A2A is a single
hop, so unlike the two-phase AllReduce there is only one kernel):

    Each device encodes its ``tp`` per-peer blocks into wire rows
    (:func:`repro.kernels.wire.encode_tile`, the same body as the codec
    kernels and the fused AllReduce), RDMA-pushes block ``p`` to peer
    ``p`` with ``pltpu.make_async_remote_copy`` (one chunk per
    destination rank, landing at slot ``my_id`` over there), then
    dequantizes the ``tp`` received blocks — quantize + pack + push +
    dequant in one kernel.

A per-peer block is the ``m`` payload rows destined for that peer (for
MoE dispatch: ``e_loc * capacity`` token rows of width ``d_model``),
staged as one ``(m, wire_bytes(d))`` slab (lanes padded to 128) so each
peer gets exactly one remote copy regardless of how many tokens it
carries.

Addressing, barriers and per-peer semaphore slotting are shared with
:mod:`repro.kernels.rdma_allreduce` (``_peer_coords`` / ``_ring_barrier``
/ ``_push_rows``), so both RDMA kernels have one choreography to
validate on hardware. Off TPU this does not execute (remote DMA has no
CPU lowering); :func:`repro.kernels.emulate.fused_all_to_all_emulated`
runs the same tile bodies with the push emulated by ``lax.all_to_all``,
and :func:`repro.kernels.ops.fused_all_to_all` picks between them.
tests/test_tpu_compile.py compiles it for a described v5e 2x2.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.comm_config import CommConfig
from repro.kernels.protocol import A2A_COLLECTIVE_ID, all2all_protocol
from repro.kernels.rdma_allreduce import (_cfg_kw, _push_rows,
                                          _ring_barrier, _scratch, _stage,
                                          slab_width)
from repro.kernels.wire import decode_tile, encode_tile

__all__ = ["A2A_COLLECTIVE_ID", "fused_all_to_all_rdma"]


def _a2a_kernel(x_ref, out_ref, send_buf, recv_buf, send_sem, recv_sem,
                *, axis: str, mesh_axes: Sequence[str], tp: int, wb: int,
                kw: dict, out_dtype, proto):
    my = lax.axis_index(axis)
    # one (m, wb) wire slab per destination on the buffers' leading axis
    for p in range(tp):
        _stage(send_buf, p, encode_tile(x_ref[p], **kw))
    _ring_barrier(my, tp, axis, mesh_axes, proto.barrier)
    # push slab p of my wire to peer p; it lands in recv_buf[my] there,
    # so recv_buf[j] here is peer j's slab my — lax.all_to_all order
    _push_rows(send_buf, recv_buf, send_sem, recv_sem, my, tp,
               axis, mesh_axes, proto)
    # own slab never crossed the link: decode send_buf[my] for slab my
    for p in range(tp):
        wire = jnp.where(my == p, send_buf[p, :, :wb], recv_buf[p, :, :wb])
        out_ref[p] = decode_tile(wire, out_dtype=out_dtype, **kw)


def fused_all_to_all_rdma(x: jnp.ndarray, axis: str, cfg: CommConfig,
                          mesh_axes: Sequence[str] | None = None
                          ) -> jnp.ndarray:
    """Fused quantized A2A on a (tp, ..., d) block tensor over one axis.

    Must be called inside shard_map on TPU with ``tp > 1``; ``x[p]`` is
    the payload for peer ``p`` and the output's block ``j`` is what peer
    ``j`` sent here (``lax.all_to_all`` split/concat axis 0 semantics).
    ``d`` must already be a group multiple (the collectives layer pads).
    Pass ``mesh_axes`` (all mesh axis names, in mesh order) when the
    mesh has axes other than ``axis``. Wire bytes are identical to
    ``codec.encode`` (shared tile bodies; see tests/test_wire_golden.py).
    """
    tp = jax.lax.axis_size(axis)
    assert tp > 1, "RDMA path needs peers; use the emulation for tp == 1"
    assert x.shape[0] == tp, (x.shape, tp)
    d = x.shape[-1]
    assert d % cfg.group == 0, (d, cfg.group)
    m = math.prod(x.shape[1:-1]) if x.ndim > 2 else 1
    wb = cfg.wire_layout(d).total         # per-peer RDMA chunk addressing
    mesh_axes = tuple(mesh_axes) if mesh_axes else (axis,)
    assert axis in mesh_axes, (axis, mesh_axes)
    kw = _cfg_kw(cfg, d)

    # scratch shapes and the collective id come from the declared
    # protocol (repro.kernels.protocol) — the object commcheck verifies
    proto = all2all_protocol(tp)
    out = pl.pallas_call(
        functools.partial(_a2a_kernel, axis=axis, mesh_axes=mesh_axes,
                          tp=tp, wb=wb, kw=kw, out_dtype=x.dtype,
                          proto=proto),
        out_shape=jax.ShapeDtypeStruct((tp, m, d), x.dtype),
        name="rdma_all2all",
        scratch_shapes=_scratch(proto, m, slab_width(wb)),
        compiler_params=pltpu.CompilerParams(
            collective_id=proto.collective_id),
    )(x.reshape(tp, m, d))

    return out.reshape(x.shape)
