"""Quantized collectives — the paper's communication schemes on TPU axes.

All functions are written for use INSIDE :func:`jax.shard_map` and take
mesh axis names. The wire that crosses the link is the packed uint8 buffer
from :mod:`repro.core.codec` — produced by whichever codec backend
``cfg.backend`` selects (pure jnp ``"ref"``, fused Pallas ``"pallas"``, or
``"auto"``), so every collective here transparently rides the fused
kernels when they are enabled; everything else (chunking, local reduction,
scatter/gather choreography) is the Flash Communication two-step and its
hierarchical / pipelined variants mapped onto ``jax.lax`` collectives:

===============================  =======================================
paper (GPU / NCCL)               this module (TPU / jax.lax)
===============================  =======================================
NCCL Ring AllReduce (baseline)   ``lax.psum``
Flash two-step AllReduce         ``quantized_all_reduce`` (a2a + local
                                 reduce + ag, QDQ at both phases)
hierarchical two-step (NUMA)     ``hierarchical_all_reduce`` over
                                 (inner=ICI, outer=pod/DCI) axes
hier. + pipeline parallelism     ``pipelined_hierarchical_all_reduce``
                                 (microchunked, overlappable)
All2All dispatch quant (EP)      ``quantized_all_to_all``
ZeRO++-style qAG/qRS (beyond)    ``quantized_all_gather`` /
                                 ``quantized_reduce_scatter``
===============================  =======================================

Gradient notes: every collective here carries its *true* transpose so
``jax.grad`` inside shard_map (with per-rank loss seeding) is exact:
``compressed_psum`` transposes to a psum of cotangents (the Megatron
f-operator all-reduce), ``fsdp_all_gather`` / ``quantized_all_gather``
to a reduce-scatter, ``quantized_reduce_scatter`` to an all-gather, and
``quantized_all_to_all`` to a full-precision all_to_all in the reverse
direction (dispatch is quantized, combine stays BF16, following
DeepSeek-V3 / the paper). Quantization itself is straight-through.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import codec
from repro.core.comm_config import CommConfig


# --------------------------------------------------------------------------
# padding helpers
# --------------------------------------------------------------------------

def _pad_to(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    n = x.shape[-1]
    rem = (-n) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, rem)]
    return jnp.pad(x, pad)


def padded_len(n: int, mult: int) -> int:
    return n + (-n) % mult


# --------------------------------------------------------------------------
# flat-vector building blocks (x: (n,) per device, n % (tp*group) == 0)
# --------------------------------------------------------------------------

def _gsize(axis, groups):
    return len(groups[0]) if groups is not None else jax.lax.axis_size(axis)


def quantized_all_reduce(x: jnp.ndarray, axis: str,
                         cfg: CommConfig, groups=None) -> jnp.ndarray:
    """Flash two-step AR on (..., n) vectors over one mesh axis.

    Phase 1: chunk + quantize + all_to_all + dequant + local reduce.
    Phase 2: re-quantize partial sum + all_gather + dequant.
    Matches the paper's fused kernel semantics (QDQ around each hop).

    Leading dims are batched through one schedule (one collective per
    phase) — the pipelined hierarchical scheme feeds its microchunks
    through here as a single (chunks, n/chunks) batch.

    With ``cfg.scheme == "fused"`` the same two-step schedule runs as
    actual fused kernels: quantize + pack + RDMA push + dequant + reduce
    in one Pallas kernel per phase (``repro.kernels.rdma_allreduce`` on
    TPU, the lockstep emulation in ``repro.kernels.emulate`` elsewhere).
    """
    if cfg.scheme == "fused":
        from repro.kernels import ops   # deferred: keeps core import-light
        if x.ndim > 1:
            # the fused kernels take one flat per-device vector; a batch
            # (e.g. a fused outer hop under the batched hierarchical
            # schedules) is concatenated — sums are elementwise so the
            # result is the same AR, the wire just re-chunks the whole
            # batch instead of each row (group alignment is preserved:
            # every row length is a tp*group multiple)
            out = ops.fused_all_reduce(x.reshape(-1), axis, cfg,
                                       groups=groups)
            return out.reshape(x.shape).astype(x.dtype)
        return ops.fused_all_reduce(x, axis, cfg, groups=groups)
    tp = _gsize(axis, groups)
    n = x.shape[-1]
    lead = x.shape[:-1]
    b = len(lead)                                        # tp-axis position
    assert n % tp == 0 and (n // tp) % cfg.group == 0, (n, tp, cfg.group)
    xc = x.reshape(*lead, tp, n // tp)
    wire = codec.encode(xc, cfg)                         # (..., tp, w)
    recv = lax.all_to_all(wire, axis, b, b, tiled=True,
                          axis_index_groups=groups)      # rows from peers
    parts = codec.decode(recv, cfg, n // tp)             # (..., tp, n/tp)
    partial = jnp.sum(parts, axis=b)                     # my chunk, summed
    wire2 = codec.encode(partial, cfg)                   # (..., w)
    allw = lax.all_gather(wire2, axis, axis=b,
                          axis_index_groups=groups)      # (..., tp, w)
    full = codec.decode(allw, cfg, n // tp)              # (..., tp, n/tp)
    return full.reshape(*lead, n).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quantized_reduce_scatter(x: jnp.ndarray, axis: str,
                             cfg: CommConfig) -> jnp.ndarray:
    """Quantized RS: (..., n) -> (..., n/tp) summed chunk (phase 1 of
    two-step); leading dims batch through one collective.

    Transpose (bwd) is the exact all_gather of cotangents — the true
    transpose of a tiled reduce-scatter — so jax.grad through it under
    per-rank seeding is exact (tests/test_collective_properties.py).

    Chunks are padded to the group size (and the pad sliced back off
    after the summed decode — every rank pads the same tail positions of
    its own chunk), so any ``n % tp == 0`` length compresses instead of
    only ``group``-aligned ones. No-op for aligned sizes.
    """
    tp = jax.lax.axis_size(axis)
    n = x.shape[-1]
    lead = x.shape[:-1]
    b = len(lead)
    assert n % tp == 0, (n, tp)
    m = n // tp
    xc = _pad_to(x.reshape(*lead, tp, m), cfg.group)
    wire = codec.encode(xc, cfg)
    recv = lax.all_to_all(wire, axis, b, b, tiled=True)
    parts = codec.decode(recv, cfg, xc.shape[-1])
    return jnp.sum(parts, axis=b)[..., :m].astype(x.dtype)


def _qrs_fwd(x, axis, cfg):
    return quantized_reduce_scatter(x, axis, cfg), None


def _qrs_bwd(axis, cfg, res, g):
    del res
    return (lax.all_gather(g, axis, axis=g.ndim - 1, tiled=True),)


quantized_reduce_scatter.defvjp(_qrs_fwd, _qrs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def quantized_all_gather(x: jnp.ndarray, axis: str,
                         cfg: CommConfig) -> jnp.ndarray:
    """Quantized AG: (..., k) -> (..., tp*k). ZeRO++-style weight gather;
    leading dims batch through one collective.

    Transpose (bwd) is the exact psum_scatter of cotangents — the true
    transpose of a tiled all_gather — matching ``fsdp_all_gather``'s
    reduce-scatter transpose; gradients stay exact under quantized
    forward (tests/test_collective_properties.py).
    """
    n = x.shape[-1]
    lead = x.shape[:-1]
    b = len(lead)
    assert n % cfg.group == 0
    wire = codec.encode(x, cfg)
    allw = lax.all_gather(wire, axis, axis=b)            # (..., tp, w)
    full = codec.decode(allw, cfg, n)                    # (..., tp, k)
    return full.reshape(*lead, -1).astype(x.dtype)


def _qag_fwd(x, axis, cfg):
    return quantized_all_gather(x, axis, cfg), None


def _qag_bwd(axis, cfg, res, g):
    del res
    return (lax.psum_scatter(g, axis, scatter_dimension=g.ndim - 1,
                             tiled=True),)


quantized_all_gather.defvjp(_qag_fwd, _qag_bwd)


def quantized_all_to_all(x: jnp.ndarray, axis: str, cfg: CommConfig,
                         split_axis: int = 0,
                         concat_axis: int = 0, groups=None) -> jnp.ndarray:
    """Quantized A2A for MoE dispatch. x: (tp, ..., d) rows to each peer.

    Only the dispatch payload is quantized (combine stays BF16), following
    the paper / DeepSeek-V3. A last axis that is not a multiple of the
    quantization group is zero-padded before encode and sliced back after
    decode (same treatment as ``compressed_psum``), so MoE model dims
    that don't divide the group no longer crash.

    Schemes: ``cfg.scheme == "nccl"`` bypasses the codec entirely (the
    exact BF16 baseline, mirroring ``compressed_psum``); with
    ``"fused"`` (and the standard split/concat axis 0 used by MoE
    dispatch) the quantize + per-peer push + dequant run as one fused
    kernel (``repro.kernels.rdma_all2all`` on TPU, the lockstep
    emulation elsewhere) — bit-identical to this XLA path by
    construction (shared tile bodies). Everything else runs codec
    around a plain ``lax.all_to_all``.
    """
    if not cfg.enabled or cfg.scheme == "nccl":
        return lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True,
                              axis_index_groups=groups)
    d = x.shape[-1]
    dp = padded_len(d, cfg.group)
    if cfg.scheme == "fused" and split_axis == 0 and concat_axis == 0:
        from repro.kernels import ops   # deferred: keeps core import-light
        out = ops.fused_all_to_all(_pad_to(x, cfg.group), axis, cfg,
                                   groups=groups)
        return out[..., :d]
    wire = codec.encode(_pad_to(x, cfg.group), cfg)
    recv = lax.all_to_all(wire, axis, split_axis, concat_axis, tiled=True,
                          axis_index_groups=groups)
    out = codec.decode(recv, cfg, dp, out_dtype=x.dtype)
    return out[..., :d]


# --------------------------------------------------------------------------
# hierarchical schemes (paper: NUMA -> here: inner=ICI fast, outer=pod slow)
# --------------------------------------------------------------------------

def hierarchical_all_reduce(x: jnp.ndarray, inner_axis: str, outer_axis: str,
                            cfg: CommConfig,
                            outer_cfg: CommConfig | None = None
                            ) -> jnp.ndarray:
    """Three-stage hierarchical AR (paper Figs. 6-7, Table 5).

    1. partial ReduceScatter inside the fast domain (inner axis),
    2. AllReduce of the scattered partial sums across the slow bridge
       (outer axis) — only n/inner values cross, the 4M -> M saving,
    3. partial AllGather inside the fast domain.

    ``outer_cfg`` lets the slow hop use a more aggressive width than the
    fast hop (beyond-paper knob; defaults to ``cfg``). Leading dims are
    batched through one schedule (how ``hier_pp`` rides this function).
    """
    outer_cfg = outer_cfg or cfg
    inner = jax.lax.axis_size(inner_axis)
    n = x.shape[-1]
    b = x.ndim - 1
    assert n % inner == 0 and (n // inner) % cfg.group == 0
    chunk = quantized_reduce_scatter(x, inner_axis, cfg)     # (..., n/inner)
    outer = jax.lax.axis_size(outer_axis)
    if outer > 1:
        if (n // inner) % (outer * outer_cfg.group) == 0:
            chunk = quantized_all_reduce(chunk, outer_axis, outer_cfg)
        else:  # small remainder chunks: quantized AG + local sum
            wire = codec.encode(chunk, outer_cfg)
            allw = lax.all_gather(wire, outer_axis, axis=b)
            chunk = jnp.sum(
                codec.decode(allw, outer_cfg, chunk.shape[-1]), axis=b
            ).astype(x.dtype)
    full = quantized_all_gather(chunk, inner_axis, cfg)      # (..., n)
    return full.astype(x.dtype)


def pipelined_hierarchical_all_reduce(x: jnp.ndarray, inner_axis: str,
                                      outer_axis: str, cfg: CommConfig,
                                      outer_cfg: CommConfig | None = None
                                      ) -> jnp.ndarray:
    """Microchunked hierarchical AR (paper Fig. 8).

    The vector is cut into ``cfg.pipeline_chunks`` microchunks and the
    whole batch runs through ONE three-stage schedule as a
    ``(chunks, n/chunks)`` tensor: one all_to_all / all_gather per stage
    carries every microchunk, instead of the old Python loop that traced
    ``chunks`` copies of the schedule (per-call dispatch overhead and a
    ``chunks``-times bigger HLO for zero numerical difference — each
    microchunk's quantization groups and reduce order are unchanged, so
    the result is bit-identical to the serial loop). On real hardware the
    XLA/ICI scheduler can still overlap the batched stages' cross-pod hop
    with the intra-pod stages of the next wave (paper: up to 20%).
    """
    chunks = max(1, cfg.pipeline_chunks)
    inner = jax.lax.axis_size(inner_axis)
    n = x.shape[-1]
    mult = inner * cfg.group * chunks
    assert n % mult == 0, (n, mult)
    xs = x.reshape(chunks, n // chunks)
    out = hierarchical_all_reduce(xs, inner_axis, outer_axis, cfg,
                                  outer_cfg)
    return out.reshape(n)


# --------------------------------------------------------------------------
# shaped wrappers with padding + custom VJP (the public model-facing API)
# --------------------------------------------------------------------------

def _flat_all_reduce(xf: jnp.ndarray, axes: Sequence[str],
                     cfg: CommConfig,
                     outer_cfg: CommConfig | None = None) -> jnp.ndarray:
    """Dispatch on scheme for a padded flat vector over (inner[, outer]).

    ``outer_cfg`` gives the slow bridge hop (the LAST axis — the pod /
    DCN tier) its own wire format: different bits, and optionally the
    self-describing frame (``outer_cfg.framed``), while the inner ICI
    hop stays on ``cfg`` — mixed-policy pods on one fabric.
    """
    if len(axes) == 1:
        # Single axis: no (inner, outer) split exists, so "hierarchical"
        # degenerates to the two-step itself; "hier_pp" keeps its
        # pipelining by feeding the microchunks through ONE batched
        # two-step schedule (collectives batch over leading dims) — this
        # is how hier_pp grad policies keep their pipelined schedule on
        # the already-reduce-scattered single pod axis (train_step). The
        # lone axis IS the bridge, so ``outer_cfg`` (when given) is the
        # wire format that runs.
        hop = outer_cfg or cfg
        if cfg.scheme == "hier_pp":
            chunks = max(1, cfg.pipeline_chunks)
            out = quantized_all_reduce(xf.reshape(chunks, -1), axes[0],
                                       hop)
            return out.reshape(xf.shape)
        return quantized_all_reduce(xf, axes[0], hop)
    if cfg.scheme in ("two_step", "fused"):
        out = xf
        for i, ax in enumerate(axes):  # sequential two-step per axis
            hop = outer_cfg if (outer_cfg is not None
                                and i == len(axes) - 1) else cfg
            out = quantized_all_reduce(out, ax, hop)
        return out
    inner, outer = axes
    if cfg.scheme == "hierarchical":
        return hierarchical_all_reduce(xf, inner, outer, cfg, outer_cfg)
    if cfg.scheme == "hier_pp":
        return pipelined_hierarchical_all_reduce(xf, inner, outer, cfg,
                                                 outer_cfg)
    raise ValueError(f"unknown scheme {cfg.scheme}")


def _group_mult(cfg: CommConfig, outer_cfg: CommConfig | None) -> int:
    """Group granularity both tiers' wire formats align on."""
    if outer_cfg is None or not outer_cfg.enabled:
        return cfg.group
    return math.lcm(cfg.group, outer_cfg.group)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def compressed_psum(x: jnp.ndarray, axes: tuple, cfg: CommConfig,
                    groups=None, bwd_cfg: CommConfig | None = None,
                    outer_cfg: CommConfig | None = None):
    """psum(x) over mesh axes with the paper's compressed wire format.

    Accepts any shape; flattens, zero-pads to the chunking granularity,
    runs the configured scheme, and restores the shape. ``axes`` is a
    tuple: 1 axis -> two-step; 2 axes -> (inner, outer) hierarchical
    schemes are available via ``cfg.scheme``.

    ``outer_cfg`` overrides the wire format of the bridge tier (the last
    axis): the pod/DCN hop can run at different bits than the ICI hop
    and, with ``outer_cfg.framed``, carry the self-describing frame
    header of :mod:`repro.core.frame` — the mixed-policy-pods knob.
    Padding aligns to both tiers' group sizes (lcm).

    Backward pass: the true transpose — psum of cotangents over the same
    axes (exact, unquantized). Under per-rank loss seeding inside
    shard_map this is the Megatron f-operator all-reduce; it makes
    jax.grad of the global function exact. (The paper's inference path
    has no backward; training-side cotangent compression is a separate
    knob we deliberately keep exact.)
    """
    if not cfg.enabled or cfg.scheme == "nccl":
        out = x
        for ax in axes:
            out = lax.psum(out, ax, axis_index_groups=groups)
        return out
    if groups is not None:
        assert len(axes) == 1, "groups only supported for single-axis psum"
        sizes = [len(groups[0])]
        mult = sizes[0] * cfg.group
        xf = _pad_to(x.reshape(-1), mult)
        out = quantized_all_reduce(xf.astype(jnp.float32), axes[0], cfg,
                                   groups=groups)
        n = 1
        for s in x.shape:
            n *= s
        return out[:n].reshape(x.shape).astype(x.dtype)
    sizes = [jax.lax.axis_size(a) for a in axes]
    chunks = cfg.pipeline_chunks if cfg.scheme == "hier_pp" else 1
    mult = sizes[0] * _group_mult(cfg, outer_cfg) * chunks
    for s in sizes[1:]:
        mult *= s
    xf = _pad_to(x.reshape(-1), mult)
    out = _flat_all_reduce(xf.astype(jnp.float32), tuple(axes), cfg,
                           outer_cfg)
    n = 1
    for s in x.shape:
        n *= s
    return out[:n].reshape(x.shape).astype(x.dtype)


def _psum_fwd(x, axes, cfg, groups, bwd_cfg, outer_cfg):
    return compressed_psum(x, axes, cfg, groups, bwd_cfg, outer_cfg), None


def _psum_bwd(axes, cfg, groups, bwd_cfg, outer_cfg, res, g):
    del res
    if bwd_cfg is not None and bwd_cfg.enabled:
        return (compressed_psum(g, axes, bwd_cfg, groups),)
    out = g
    for ax in axes:
        out = lax.psum(out, ax, axis_index_groups=groups)
    return (out,)


compressed_psum.defvjp(_psum_fwd, _psum_bwd)


# --------------------------------------------------------------------------
# error-feedback (EF21 / 1-bit-LAMB style) compressed collectives
# --------------------------------------------------------------------------

def _local_qdq_error(xe_flat: jnp.ndarray, cfg: CommConfig,
                     mult: int) -> jnp.ndarray:
    """This rank's phase-1 quantization error of a flat vector.

    Every AR/RS schedule chunks the padded flat vector into contiguous
    rows and encodes each row with ``cfg.group``-sized groups, so the
    group boundaries of a flat QDQ over the same padding are identical
    to the ones the collective's first quantization actually used — the
    captured residual is exactly the phase-1 error. (The two-step's
    phase-2 re-quantization of the *summed* partials is a shared error
    across ranks and is deliberately not fed back.)
    """
    xp = _pad_to(xe_flat, mult)
    err = xp - codec.qdq_wire(xp, cfg)
    return err[:xe_flat.shape[0]]


def _ef_two_step(xe_flat: jnp.ndarray, axis: str, cfg: CommConfig):
    """Single-axis two-step AR on a padded flat vector with FULL error
    capture: ``(xe) -> (out, residual)``.

    The two-step quantizes twice — each rank's input chunks (phase 1)
    and the summed partials before the all_gather (phase 2). Phase-1
    error is local by construction; phase-2 error is known exactly at
    the rank that owns the chunk (it holds both ``partial`` and its
    dequantized broadcast), so folding it into that rank's residual at
    its own chunk position makes the per-step residuals *sum across
    ranks to the AR's entire error*:

        sum_r residual_r = sum_r err1_r (all chunks) + sum_c err2_c

    i.e. next step's psum of ``x + residual`` re-injects every bit the
    wire dropped — the strongest EF the schedule admits. Leading batch
    dims pipeline through one schedule (the hier_pp microchunk path).
    """
    tp = jax.lax.axis_size(axis)
    lead = xe_flat.shape[:-1]
    b = len(lead)
    m = xe_flat.shape[-1]
    xc = xe_flat.reshape(*lead, tp, m // tp)
    wire = codec.encode(xc, cfg)
    err1 = xc - codec.decode(wire, cfg, m // tp)         # phase-1, mine
    recv = lax.all_to_all(wire, axis, b, b, tiled=True)
    parts = codec.decode(recv, cfg, m // tp)
    partial = jnp.sum(parts, axis=b)                     # my chunk's sum
    wire2 = codec.encode(partial, cfg)
    err2 = partial - codec.decode(wire2, cfg, m // tp)   # phase-2, mine
    allw = lax.all_gather(wire2, axis, axis=b)
    out = codec.decode(allw, cfg, m // tp).reshape(*lead, m)
    own = (jnp.arange(tp) == lax.axis_index(axis))[:, None]
    res = (err1 + own * err2[..., None, :]).reshape(*lead, m)
    return out, res


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def compressed_psum_ef(x: jnp.ndarray, residual: jnp.ndarray, axes: tuple,
                       cfg: CommConfig, groups=None):
    """Error-feedback ``compressed_psum``: ``(x, residual_in) ->
    (out, residual_out)``.

    Each step adds the previous step's local quantization error back in
    before compressing (``xe = x + residual``), runs the configured
    quantized AR on ``xe``, and returns the error the wire dropped for
    the caller to carry to the next step (SDP4Bit / EF21: the bias of
    low-bit gradient compression becomes a *bounded* residual instead
    of an accumulating drift, which is what lets the grad site run at
    2-4 bits and still converge).

    On a single axis with the XLA schedules the residual captures BOTH
    quantization stages of the two-step (see :func:`_ef_two_step`) —
    the per-rank residuals sum to the AR's entire error. Multi-axis /
    grouped / fused runs fall back to phase-1-only capture (the local
    QDQ error), which is the part a rank can know by itself there.

    ``residual`` has ``x``'s shape and should start at zeros. With the
    site disabled (or scheme ``"nccl"``) the psum is exact and the
    residual passes through unchanged (zeros stay zeros).
    """
    if not cfg.enabled or cfg.scheme == "nccl":
        out = x
        for ax in axes:
            out = lax.psum(out, ax, axis_index_groups=groups)
        return out, residual
    shape = x.shape
    n = 1
    for s in shape:
        n *= s
    xe = x.astype(jnp.float32) + residual.astype(jnp.float32)
    if len(axes) == 1 and groups is None and \
            cfg.scheme in ("two_step", "hierarchical", "hier_pp"):
        tp = jax.lax.axis_size(axes[0])
        chunks = cfg.pipeline_chunks if cfg.scheme == "hier_pp" else 1
        xf = _pad_to(xe.reshape(-1), tp * cfg.group * chunks)
        if chunks > 1:          # hier_pp: batched microchunk pipeline
            xf = xf.reshape(chunks, xf.shape[0] // chunks)
        out, res = _ef_two_step(xf, axes[0], cfg)
        return (out.reshape(-1)[:n].reshape(shape).astype(x.dtype),
                res.reshape(-1)[:n].reshape(shape).astype(residual.dtype))
    out = compressed_psum(xe, axes, cfg, groups)
    sizes = [len(groups[0])] if groups is not None \
        else [jax.lax.axis_size(a) for a in axes]
    chunks = cfg.pipeline_chunks if cfg.scheme == "hier_pp" else 1
    mult = cfg.group * chunks
    for s in sizes:
        mult *= s
    new_res = _local_qdq_error(xe.reshape(-1), cfg, mult).reshape(shape)
    return out.astype(x.dtype), new_res.astype(residual.dtype)


def _psum_ef_fwd(x, residual, axes, cfg, groups):
    return compressed_psum_ef(x, residual, axes, cfg, groups), None


def _psum_ef_bwd(axes, cfg, groups, res, g):
    del res
    g_out, _ = g      # the residual output is state, not a loss path
    out = g_out
    for ax in axes:
        out = lax.psum(out, ax, axis_index_groups=groups)
    # out = psum(x + residual) straight-through; the residual output is
    # x + r - QDQ(x + r), whose straight-through Jacobian is zero — the
    # exact transpose used everywhere else in this module.
    return out, out


compressed_psum_ef.defvjp(_psum_ef_fwd, _psum_ef_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def quantized_reduce_scatter_ef(x: jnp.ndarray, residual: jnp.ndarray,
                                axis: str, cfg: CommConfig):
    """Error-feedback quantized RS: ``(x (..., n), residual (..., n)) ->
    (chunk (..., n/tp), residual_out (..., n))``.

    Same contract as :func:`compressed_psum_ef` for the scatter-shaped
    ZeRO++ gradient site: the residual lives at the *input* (full n)
    shape, the output is this rank's summed chunk. Alignment contract
    matches :func:`quantized_reduce_scatter` (``n % tp == 0``; chunks
    are group-padded internally).
    """
    if not cfg.enabled or cfg.scheme == "nccl":
        out = lax.psum_scatter(x, axis, scatter_dimension=x.ndim - 1,
                               tiled=True)
        return out, residual
    tp = jax.lax.axis_size(axis)
    m = x.shape[-1] // tp
    xe = x.astype(jnp.float32) + residual.astype(jnp.float32)
    out = quantized_reduce_scatter(xe, axis, cfg)
    # The RS has a single quantization stage, so this rank's entire
    # error is its local QDQ error — taken on the same (tp, m)-chunked,
    # group-padded view the RS encoded, pad error sliced off with it.
    xc = _pad_to(xe.reshape(*xe.shape[:-1], tp, m), cfg.group)
    err = (xc - codec.qdq_wire(xc, cfg))[..., :m].reshape(xe.shape)
    return out.astype(x.dtype), err.astype(residual.dtype)


def _qrs_ef_fwd(x, residual, axis, cfg):
    return quantized_reduce_scatter_ef(x, residual, axis, cfg), None


def _qrs_ef_bwd(axis, cfg, res, g):
    del res
    g_out, _ = g
    ag = lax.all_gather(g_out, axis, axis=g_out.ndim - 1, tiled=True)
    return ag, ag


quantized_reduce_scatter_ef.defvjp(_qrs_ef_fwd, _qrs_ef_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def dispatch_all_to_all(x: jnp.ndarray, axis: str, cfg: CommConfig,
                        groups=None):
    """MoE dispatch A2A with quantized payload; bwd = BF16 A2A (combine
    direction), i.e. the dispatch quantization is straight-through."""
    return quantized_all_to_all(x, axis, cfg, groups=groups)


def _a2a_fwd(x, axis, cfg, groups):
    return dispatch_all_to_all(x, axis, cfg, groups), None


def _a2a_bwd(axis, cfg, groups, res, g):
    del res
    return (lax.all_to_all(g, axis, 0, 0, tiled=True,
                           axis_index_groups=groups),)


dispatch_all_to_all.defvjp(_a2a_fwd, _a2a_bwd)


def grad_all_reduce(grads, axes: Sequence[str], cfg: CommConfig,
                    mean: bool = True,
                    outer_cfg: CommConfig | None = None):
    """Gradient sync for a pytree over (data[, pod]) axes — the paper's
    hierarchical scheme applied to DP gradient AllReduce (outside
    autodiff). ``outer_cfg`` gives the last (pod/DCN bridge) axis its
    own wire format, see :func:`compressed_psum`.
    """
    denom = 1
    for a in axes:
        denom *= jax.lax.axis_size(a)

    def one(g):
        out = compressed_psum(g, tuple(axes), cfg, None, None, outer_cfg)
        return out / denom if mean else out

    return jax.tree_util.tree_map(one, grads)
