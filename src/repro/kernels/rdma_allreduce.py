"""Fused two-step AllReduce as Pallas RDMA kernels (TPU).

The paper's headline AllReduce win comes from *fusing* the codec with the
collective: the tensor is read once, quantized, bit-split packed, and the
wire bytes are pushed straight over the interconnect, with dequant +
local reduce happening in the same kernel on the receiving side. This
module is that schedule on TPU, one ``pallas_call`` per phase:

phase 1 — scatter-reduce
    Each device encodes its ``tp`` per-peer chunks into wire rows
    (:func:`repro.kernels.wire.encode_tile`, the same body as the codec
    kernels), RDMA-pushes row ``p`` to peer ``p`` with
    ``pltpu.make_async_remote_copy``, then dequantizes the ``tp``
    received rows and reduces them — quantize + pack + push + dequant +
    reduce in one kernel, only wire bytes cross the link.

phase 2 — gather
    The partial sum is re-encoded (same encode body, one row), pushed to
    every peer's gather buffer at slot ``my_id``, and all ``tp`` wire
    rows are dequantized back to the full vector.

Addressing uses ``DeviceIdType.MESH`` coordinates so the kernel works on
multi-axis meshes: ``mesh_axes`` names every mesh axis in order and the
peer coordinate only varies along the communicated ``axis``.

The choreography itself — barrier signalling, per-peer semaphore slots,
buffer lifetimes, the barrier ``collective_id`` — is declared as data in
:mod:`repro.kernels.protocol` and *executed* here: ``_ring_barrier`` and
``_push_rows`` walk the declared plan, and the ``pallas_call`` scratch
shapes come from the protocol fields. The same declaration is what
:mod:`repro.analysis.choreography` statically verifies (deadlock
freedom, slot matching, write-before-wait races) per mesh shape.

Off TPU this does not execute (remote DMA has no CPU lowering);
:mod:`repro.kernels.emulate` runs the same tile bodies with the push
emulated by XLA collectives, and :func:`repro.kernels.ops.
fused_all_reduce` picks between them. tests/test_tpu_compile.py compiles
both phases for a described v5e 2x2; ``chip_smoke.py --chips 4`` runs
them.
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
from repro.kernels.protocol import (KernelProtocol, RingBarrier,
                                    allreduce_gather_protocol,
                                    allreduce_scatter_protocol,
                                    resolve_row)
from repro.kernels.wire import _cfg_kw, decode_tile, encode_tile


def _peer_coords(dst, axis: str, mesh_axes: Sequence[str]):
    """MESH device id of the peer at index ``dst`` along ``axis``."""
    return tuple(dst if a == axis else lax.axis_index(a)
                 for a in mesh_axes)


def _ring_barrier(my, tp: int, axis: str, mesh_axes: Sequence[str],
                  plan: RingBarrier):
    """Execute the declared barrier plan: signal each peer at
    ``(my + off) % tp`` once, wait for the symmetric signals — all comm
    scratch buffers are live before any RDMA lands in them."""
    barrier = pltpu.get_barrier_semaphore()
    for off in plan.signal_offsets:
        pltpu.semaphore_signal(
            barrier, inc=1,
            device_id=_peer_coords(lax.rem(my + off, tp), axis, mesh_axes),
            device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, plan.wait_count)


def _push_rows(src_buf, dst_buf, send_sem, recv_sem, my, tp: int,
               axis: str, mesh_axes: Sequence[str],
               proto: KernelProtocol):
    """Execute the declared push plan: start every ``PushStep``'s RDMA
    and wait for the symmetric receives.

    Step ``dst_off=i`` sends to peer ``my + i`` and (by SPMD symmetry)
    the matching receive into semaphore slot ``recv_slot`` comes from
    peer ``my - i``; waiting on each descriptor covers both directions.
    """
    rdmas = []
    for step in proto.pushes:
        dst = lax.rem(my + step.dst_off, tp)
        src_row = resolve_row(step.src_row, my, dst)
        dst_row = resolve_row(step.dst_row, my, dst)
        rdma = pltpu.make_async_remote_copy(
            src_ref=src_buf.at[pl.ds(src_row, 1)],
            dst_ref=dst_buf.at[pl.ds(dst_row, 1)],
            send_sem=send_sem.at[step.send_slot],
            recv_sem=recv_sem.at[step.recv_slot],
            device_id=_peer_coords(dst, axis, mesh_axes),
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        rdmas.append(rdma)
    for rdma in rdmas:
        rdma.wait()


# ---------------------------------------------------------------------------
# staging slabs
# ---------------------------------------------------------------------------

#: A VMEM DMA moves whole (rows, 128-lane) uint8 tiles, so each peer's
#: wire is staged as one slab on the buffer's leading axis, padded with
#: zero bytes to a lane multiple; uint8 packs 4 rows per 32-bit word, so
#: a single row cannot be addressed on its own.
_LANES = 128
_SLAB_ROWS = 8


def slab_width(wb: int) -> int:
    """Staged bytes per wire row: ``wb`` rounded up to whole lanes."""
    return -(-wb // _LANES) * _LANES


def slab_rows(chunk: int, group: int) -> int:
    """Rows a peer's ``chunk`` values are encoded as (each row a whole
    number of groups, so the quantized values are the same as one row's;
    only the byte arrangement on the link differs)."""
    return math.gcd(_SLAB_ROWS, chunk // group)


def _stage(buf, p: int, wire) -> None:
    """Write wire tile ``wire`` into slab ``p`` of ``buf``, zero tail."""
    rows, wb = wire.shape
    buf[p, :, :wb] = wire
    if buf.shape[-1] > wb:
        buf[p, :, wb:] = jnp.zeros((rows, buf.shape[-1] - wb), jnp.uint8)


# ---------------------------------------------------------------------------
# phase kernels
# ---------------------------------------------------------------------------

def _scatter_reduce_kernel(x_ref, partial_ref, send_buf, recv_buf,
                           send_sem, recv_sem, *, axis: str,
                           mesh_axes: Sequence[str], tp: int, wb: int,
                           kw: dict, proto: KernelProtocol):
    my = lax.axis_index(axis)
    for p in range(tp):                    # encode the tp per-peer slabs
        _stage(send_buf, p, encode_tile(x_ref[p], **kw))
    _ring_barrier(my, tp, axis, mesh_axes, proto.barrier)
    # push slab p of my wire to peer p; it lands in recv_buf[my] over there
    _push_rows(send_buf, recv_buf, send_sem, recv_sem, my, tp,
               axis, mesh_axes, proto)
    # own chunk never crossed the link: decode send_buf[my] for slab my
    acc = None
    for p in range(tp):
        wire = jnp.where(my == p, send_buf[p, :, :wb], recv_buf[p, :, :wb])
        part = decode_tile(wire, out_dtype=jnp.float32, **kw)
        acc = part if acc is None else acc + part
    partial_ref[...] = acc


def _gather_kernel(partial_ref, out_ref, send_buf, gather_buf,
                   send_sem, recv_sem, *, axis: str,
                   mesh_axes: Sequence[str], tp: int, wb: int, kw: dict,
                   proto: KernelProtocol):
    my = lax.axis_index(axis)
    _stage(send_buf, 0, encode_tile(partial_ref[...], **kw))
    _ring_barrier(my, tp, axis, mesh_axes, proto.barrier)
    # push my (single) partial-sum slab into every peer's slot my
    _push_rows(send_buf, gather_buf, send_sem, recv_sem, my, tp,
               axis, mesh_axes, proto)
    for p in range(tp):
        wire = jnp.where(my == p, send_buf[0, :, :wb],
                         gather_buf[p, :, :wb])
        out_ref[p] = decode_tile(wire, out_dtype=jnp.float32, **kw)


# ---------------------------------------------------------------------------
# public entry point (call inside shard_map, TPU only)
# ---------------------------------------------------------------------------

def _scratch(proto: KernelProtocol, rows: int, width: int) -> list:
    """Send/receive slabs and DMA semaphores, shaped by the protocol."""
    return [
        pltpu.VMEM((proto.buffer("send").rows, rows, width), jnp.uint8),
        pltpu.VMEM((proto.buffer("recv").rows, rows, width), jnp.uint8),
        pltpu.SemaphoreType.DMA((proto.sem_slots,)),
        pltpu.SemaphoreType.DMA((proto.sem_slots,)),
    ]


def fused_all_reduce_rdma(x: jnp.ndarray, axis: str, cfg: CommConfig,
                          mesh_axes: Sequence[str] | None = None
                          ) -> jnp.ndarray:
    """Fused two-step AR on a flat (n,) vector over one mesh axis.

    Must be called inside shard_map on TPU with ``tp > 1``; pass
    ``mesh_axes`` (all mesh axis names, in mesh order) when the mesh has
    axes other than ``axis``. Each wire row is ``codec.encode`` of
    ``chunk / slab_rows`` values (shared tile bodies; see
    tests/test_wire_golden.py).
    """
    tp = jax.lax.axis_size(axis)
    assert tp > 1, "RDMA path needs peers; use the emulation for tp == 1"
    n = x.shape[-1]
    assert n % tp == 0 and (n // tp) % cfg.group == 0, (n, tp, cfg.group)
    chunk = n // tp
    rows = slab_rows(chunk, cfg.group)
    cols = chunk // rows
    wb = cfg.wire_layout(cols).total      # send/recv buffer addressing
    mesh_axes = tuple(mesh_axes) if mesh_axes else (axis,)
    assert axis in mesh_axes, (axis, mesh_axes)

    comm = dict(axis=axis, mesh_axes=mesh_axes, tp=tp, wb=wb,
                kw=_cfg_kw(cfg, cols))
    # scratch shapes and collective ids come from the declared protocol
    # — the same object repro.analysis.choreography statically verifies
    sp = allreduce_scatter_protocol(tp)
    partial = pl.pallas_call(
        functools.partial(_scatter_reduce_kernel, proto=sp, **comm),
        name="rdma_allreduce_scatter",
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        scratch_shapes=_scratch(sp, rows, slab_width(wb)),
        compiler_params=pltpu.CompilerParams(
            collective_id=sp.collective_id),
    )(x.reshape(tp, rows, cols).astype(jnp.float32))

    gp = allreduce_gather_protocol(tp)
    full = pl.pallas_call(
        functools.partial(_gather_kernel, proto=gp, **comm),
        name="rdma_allreduce_gather",
        out_shape=jax.ShapeDtypeStruct((tp, rows, cols), jnp.float32),
        scratch_shapes=_scratch(gp, rows, slab_width(wb)),
        compiler_params=pltpu.CompilerParams(
            collective_id=gp.collective_id),
    )(partial)

    return full.reshape(n).astype(x.dtype)
