"""Property-based collective-level tests (via tests/_hyp.py fallback).

Collective-level (not just codec-level) conformance, the net SDP4Bit
says low-bit collectives need:

* ``compressed_psum`` stays within a quantization-step error bound of
  the exact ``lax.psum`` for EVERY scheme — including the
  ``"fused"`` Pallas path — across widths and metadata codecs;
* ``jax.grad`` of ``compressed_psum`` under shard_map with per-rank
  loss seeding is *exact* (the custom VJP is the unquantized psum of
  cotangents), for every scheme;
* ``quantized_all_gather`` / ``quantized_reduce_scatter`` get the same
  treatment: per-shard QDQ conformance, error bound vs the exact
  collective, and exact per-rank-seeded gradients (their custom VJPs
  are the true transposes: AG -> reduce-scatter, RS -> all-gather);
* ``quantized_all_to_all`` handles shape edge cases — last axes that
  are not group (or rank-count) multiples, a single row per peer — and
  its ``"fused"`` scheme is bit-identical to the XLA wire.

Multi-device cases run under ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` (the CI multidev job) and skip on fewer devices; the
single-device cases always run.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.core import (compressed_psum, default_comm_config,
                        dispatch_all_to_all)
from repro.core.codec import qdq_wire
from repro.core.collectives import padded_len, quantized_all_to_all
from repro.core.comm_config import NO_COMPRESSION, CommConfig
from repro.launch.mesh import make_test_mesh

multidev = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >=4 devices (XLA_FLAGS host platform)")

# Per-width absolute error budget for a psum of 4 N(0,2) shards: a few
# quantization steps of the summed range at the coarsest group size,
# across up to three QDQ stages (hierarchical). The Eq.-1 integer-log
# metadata adds a width-independent floor (the zero-point is rounded to
# a 2^(1/theta) grid, so its absolute error scales with |group min|,
# not with the code width).
TOL = {2: 10.0, 3: 6.0, 4: 3.0, 5: 2.0, 6: 1.0, 7: 0.6, 8: 0.3}
SCALE_INT_FLOOR = 6.0


def _mesh4():
    # (pod=2, model=2): gives the hierarchical schemes their two axes
    return make_test_mesh(data=1, model=2, pod=2)


def _psum_all_axes(x, cfg, mesh):
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(("pod", "data", "model")),
                       out_specs=P(("pod", "data", "model")),
                       check_vma=False)
    def f(xs):
        return compressed_psum(xs[0], ("model", "pod"), cfg)[None]
    return np.asarray(jax.jit(f)(x))


@multidev
@settings(max_examples=12, deadline=None)
@given(bits=st.sampled_from([2, 3, 4, 5, 6, 7, 8]),
       scheme=st.sampled_from(["two_step", "fused", "hierarchical",
                               "hier_pp"]),
       scale_int=st.booleans())
def test_compressed_psum_error_bounded_all_schemes(bits, scheme, scale_int):
    mesh = _mesh4()
    x = jax.random.normal(jax.random.PRNGKey(bits), (4, 3, 512),
                          jnp.float32) * 2
    exact = np.sum(np.asarray(x), axis=0)
    cfg = default_comm_config(bits, scheme=scheme, scale_int=scale_int)
    out = _psum_all_axes(x, cfg, mesh)
    # every rank agrees, and the result is near the exact psum
    agree = max(float(np.max(np.abs(out[i] - out[0]))) for i in range(4))
    assert agree == 0.0, (scheme, bits, agree)
    err = float(np.max(np.abs(out[0] - exact)))
    tol = TOL[bits] + (SCALE_INT_FLOOR if scale_int else 0.0)
    assert err < tol, (scheme, bits, scale_int, err)


@multidev
@settings(max_examples=8, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]),
       scheme=st.sampled_from(["two_step", "fused", "hierarchical"]))
def test_compressed_psum_grad_exact(bits, scheme):
    """Per-rank seeded jax.grad through compressed_psum == the exact
    (unquantized) gradient, bit for bit: the custom VJP is the true
    transpose regardless of forward quantization."""
    mesh = _mesh4()
    x = jax.random.normal(jax.random.PRNGKey(7 + bits), (4, 256),
                          jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (256,), jnp.float32)
    cfg = default_comm_config(bits, scheme=scheme)

    def grad_of(c):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("pod", "model")),
                           out_specs=P(("pod", "model")),
                           check_vma=False)
        def g(xs):
            def loss(xr):   # per-rank seeded scalar loss
                out = compressed_psum(xr * xr, ("model", "pod"), c)
                return jnp.sum(out * w)
            return jax.grad(loss)(xs[0])[None]
        return np.asarray(jax.jit(g)(x))

    np.testing.assert_array_equal(grad_of(cfg), grad_of(NO_COMPRESSION))


@multidev
def test_nccl_scheme_is_exact_psum():
    """scheme="nccl" on an *enabled* config must bypass the codec."""
    mesh = _mesh4()
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 128), jnp.float32)
    cfg = CommConfig(bits=2, group=32, scheme="nccl")
    out = _psum_all_axes(x[:, None], cfg, mesh)
    np.testing.assert_allclose(out[0, 0], np.sum(np.asarray(x), axis=0),
                               rtol=1e-6, atol=1e-5)


# ---------------------------------------------------------------------------
# quantized_all_to_all padding regression (former hard assert at
# src/repro/core/collectives.py: d % cfg.group == 0)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(d=st.integers(min_value=1, max_value=200),
       bits=st.sampled_from([4, 8]))
def test_a2a_pads_non_group_multiples(d, bits):
    """Any last-axis size works now; result == QDQ of the zero-padded
    tensor, sliced back. Runs on one device (tp=1 A2A is identity)."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(bits)   # group 32 or 128
    x = jax.random.normal(jax.random.PRNGKey(d), (1, 3, d),
                          jnp.float32) * 2

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def f(xs):
        return quantized_all_to_all(xs, "model", cfg)

    out = np.asarray(jax.jit(f)(x))
    assert out.shape == x.shape
    dp = padded_len(d, cfg.group)
    pad = jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))
    want = np.asarray(qdq_wire(pad, cfg))[..., :d]
    np.testing.assert_allclose(out, want, atol=1e-6)


@multidev
def test_a2a_pad_multidevice_semantics():
    """Non-multiple d through a real 4-way A2A: each received block is
    the QDQ of the padded sender block."""
    mesh = make_test_mesh(data=2, model=4)
    cfg = default_comm_config(4)              # group 32
    d = 100                                   # not a multiple of 32
    xa = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 2, d),
                           jnp.float32)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def g(xs):
        return dispatch_all_to_all(xs[0], "model", cfg)[None]

    out = np.asarray(jax.jit(g)(xa))
    dp = padded_len(d, cfg.group)
    for i in range(4):
        for j in range(4):
            blk = jnp.pad(xa[j, i], ((0, 0), (0, dp - d)))
            want = np.asarray(qdq_wire(blk, cfg))[..., :d]
            np.testing.assert_allclose(out[i, j], want, atol=1e-6)


@multidev
@settings(max_examples=10, deadline=None)
@given(d=st.sampled_from([1, 30, 100, 128]),   # none a multiple of tp=4;
       m=st.sampled_from([1, 3]),              # 30/100 not of the group
       bits=st.sampled_from([2, 4, 8]))
def test_a2a_edge_shapes_fused_lockstep(d, m, bits):
    """A2A shape edge cases — last axis not a multiple of the group or
    of the rank count, down to a single row per peer — give the same
    bits on the fused scheme as on the XLA wire, and both match the
    padded-QDQ semantics."""
    mesh = make_test_mesh(data=2, model=4)
    xa = jax.random.normal(jax.random.PRNGKey(17 * d + m), (4, 4, m, d),
                           jnp.float32) * 2
    outs = {}
    for scheme in ("two_step", "fused"):
        cfg = default_comm_config(bits, scheme=scheme)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("model"), out_specs=P("model"),
                           check_vma=False)
        def g(xs):
            return dispatch_all_to_all(xs[0], "model", cfg)[None]

        outs[scheme] = np.asarray(jax.jit(g)(xa))
    np.testing.assert_array_equal(outs["fused"], outs["two_step"])
    dp = padded_len(d, cfg.group)
    for i in range(4):
        for j in range(4):
            blk = jnp.pad(xa[j, i], ((0, 0), (0, dp - d)))
            want = np.asarray(qdq_wire(blk, cfg))[..., :d]
            np.testing.assert_allclose(outs["fused"][i, j], want,
                                       atol=1e-6)


# ---------------------------------------------------------------------------
# quantized_all_gather / quantized_reduce_scatter: the previously
# undertested collectives get the AllReduce treatment
# ---------------------------------------------------------------------------

K = 256     # per-rank shard width for the AG/RS properties


def _per_rank_x(seed, k=K):
    # distinct shard per (pod, model) rank so conformance is meaningful
    return jax.random.normal(jax.random.PRNGKey(seed), (4, k),
                             jnp.float32) * 2


@multidev
@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([2, 3, 4, 5, 6, 7, 8]),
       scale_int=st.booleans())
def test_quantized_all_gather_conformance(bits, scale_int):
    """qAG over the model axis == concat of per-shard QDQ (exact
    conformance), which also bounds the error vs the exact all_gather
    by the per-shard quantization error."""
    from repro.core.collectives import quantized_all_gather

    mesh = _mesh4()
    x = _per_rank_x(100 + bits)
    cfg = default_comm_config(bits, scale_int=scale_int)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(("pod", "model")),
                       out_specs=P(("pod", "model")), check_vma=False)
    def f(xs):
        return quantized_all_gather(xs[0], "model", cfg)[None]

    out = np.asarray(jax.jit(f)(x))          # (4, 2K): per-rank gathers
    # jit the reference too; scale_int's f32 scale math still contracts
    # FMAs differently across differently-shaped jits, so that path
    # gets a 1-ulp budget (same caveat as tests/test_fused_allreduce).
    qdq = np.asarray(jax.jit(lambda v: qdq_wire(v, cfg))(x))
    for p in range(2):
        want = np.concatenate([qdq[2 * p], qdq[2 * p + 1]])
        for mr in range(2):                  # both model ranks agree
            np.testing.assert_array_equal(out[2 * p], out[2 * p + mr])
            if scale_int:
                np.testing.assert_allclose(out[2 * p + mr], want,
                                           rtol=0, atol=3e-6)
            else:
                np.testing.assert_array_equal(out[2 * p + mr], want)
    # error bound vs the exact gather: pure per-element QDQ error
    exact = np.concatenate([np.asarray(x[0]), np.asarray(x[1])])
    err = float(np.max(np.abs(out[0] - exact)))
    tol = TOL[bits] + (SCALE_INT_FLOOR if scale_int else 0.0)
    assert err < tol, (bits, scale_int, err)


@multidev
@settings(max_examples=10, deadline=None)
@given(bits=st.sampled_from([2, 3, 4, 5, 6, 7, 8]),
       scale_int=st.booleans())
def test_quantized_reduce_scatter_error_bounded(bits, scale_int):
    """qRS over the model axis stays within a quantization-step error
    bound of the exact psum_scatter chunk."""
    from repro.core.collectives import quantized_reduce_scatter

    mesh = _mesh4()
    x = _per_rank_x(200 + bits)
    cfg = default_comm_config(bits, scale_int=scale_int)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(("pod", "model")),
                       out_specs=P(("pod", "model")), check_vma=False)
    def f(xs):
        return quantized_reduce_scatter(xs[0], "model", cfg)[None]

    out = np.asarray(jax.jit(f)(x))          # (4, K/2) chunks
    xn = np.asarray(x)
    for p in range(2):
        summed = xn[2 * p] + xn[2 * p + 1]   # model-axis pair sum
        for mr in range(2):
            chunk = summed[mr * (K // 2):(mr + 1) * (K // 2)]
            err = float(np.max(np.abs(out[2 * p + mr] - chunk)))
            tol = TOL[bits] + (SCALE_INT_FLOOR if scale_int else 0.0)
            assert err < tol, (bits, scale_int, p, mr, err)


@multidev
@settings(max_examples=6, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]))
def test_quantized_all_gather_grad_exact(bits):
    """Per-rank seeded jax.grad through quantized_all_gather == the
    exact all_gather gradient, bit for bit: the custom VJP is the true
    reduce-scatter transpose regardless of forward quantization."""
    from jax import lax
    from repro.core.collectives import quantized_all_gather

    mesh = _mesh4()
    x = _per_rank_x(300 + bits)
    w = jax.random.normal(jax.random.PRNGKey(31), (2 * K,), jnp.float32)
    cfg = default_comm_config(bits)

    def grad_of(gather):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("pod", "model")),
                           out_specs=P(("pod", "model")),
                           check_vma=False)
        def g(xs):
            def loss(xr):   # per-rank seeded scalar loss
                return jnp.sum(gather(xr * xr) * w)
            return jax.grad(loss)(xs[0])[None]
        return np.asarray(jax.jit(g)(x))

    quant = grad_of(lambda v: quantized_all_gather(v, "model", cfg))
    exact = grad_of(
        lambda v: lax.all_gather(v, "model", axis=0, tiled=True))
    np.testing.assert_array_equal(quant, exact)


@multidev
@settings(max_examples=6, deadline=None)
@given(bits=st.sampled_from([2, 4, 8]))
def test_quantized_reduce_scatter_grad_exact(bits):
    """Per-rank seeded jax.grad through quantized_reduce_scatter == the
    exact psum_scatter gradient, bit for bit: the custom VJP is the
    true all-gather transpose."""
    from jax import lax
    from repro.core.collectives import quantized_reduce_scatter

    mesh = _mesh4()
    x = _per_rank_x(400 + bits)
    w = jax.random.normal(jax.random.PRNGKey(37), (K // 2,), jnp.float32)
    cfg = default_comm_config(bits)

    def grad_of(scatter):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("pod", "model")),
                           out_specs=P(("pod", "model")),
                           check_vma=False)
        def g(xs):
            def loss(xr):   # per-rank seeded scalar loss
                return jnp.sum(scatter(xr * xr) * w)
            return jax.grad(loss)(xs[0])[None]
        return np.asarray(jax.jit(g)(x))

    quant = grad_of(lambda v: quantized_reduce_scatter(v, "model", cfg))
    exact = grad_of(lambda v: lax.psum_scatter(
        v, "model", scatter_dimension=0, tiled=True))
    np.testing.assert_array_equal(quant, exact)
