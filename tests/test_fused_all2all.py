"""Fused quantized All2All: lockstep emulation vs the XLA wire.

The ``"fused"`` A2A scheme must be a drop-in for the codec-around-
``lax.all_to_all`` path ``quantized_all_to_all`` runs otherwise:
identical bits on the wire and out of the dequant, with quantize +
per-peer push + dequant fused into one kernel. Single-device cases run
everywhere; the full 8-device lockstep (incl. MoE dispatch shapes) is
tests/_multidev_script.py ``fused_a2a`` and the shape-edge-case
property test in tests/test_collective_properties.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import codec, default_comm_config, dispatch_all_to_all
from repro.core.collectives import padded_len, quantized_all_to_all
from repro.core.comm_config import CommConfig
from repro.kernels import emulate
from repro.launch.mesh import make_test_mesh

D = 128


def _x(shape=(1, 3, D), seed=0, scale=2.0, dtype=jnp.float32):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape,
                              jnp.float32) * scale).astype(dtype)


@pytest.mark.parametrize("spike,scale_int", [(False, False), (True, True)])
def test_emulated_a2a_blocks_are_codec_qdq(spike, scale_int):
    """At tp=1 the fused A2A is encode + (identity hop) + decode: its
    output must be exactly the codec round trip of each block."""
    cfg = CommConfig(bits=4, group=32, spike=spike, scale_int=scale_int)
    mesh = make_test_mesh(data=1, model=1)
    x = _x(seed=3)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def f(xs):
        return emulate.fused_all_to_all_emulated(xs, "model", cfg)

    out = np.asarray(jax.jit(f)(x))
    # jit on both sides: eager-vs-jit FMA contraction differs at 1 ulp
    # for scale_int's f32 scales (see tests/test_backend_equality.py)
    want = np.asarray(jax.jit(
        lambda v: codec.decode(codec.encode(v, cfg), cfg, D,
                               out_dtype=v.dtype))(x))
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matches_xla_single_device(bits, dtype):
    """tp=1 degenerate case: same bits out of both schemes, in the
    payload dtype MoE dispatch actually uses (f32 and bf16)."""
    mesh = make_test_mesh(data=1, model=1)
    x = _x(seed=bits, dtype=dtype)

    def run(scheme):
        cfg = default_comm_config(bits, scheme=scheme)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("model"), out_specs=P("model"),
                           check_vma=False)
        def f(xs):
            return quantized_all_to_all(xs, "model", cfg)
        out = jax.jit(f)(x)
        assert out.dtype == dtype
        return np.asarray(out.astype(jnp.float32))

    np.testing.assert_array_equal(run("fused"), run("two_step"))


@pytest.mark.parametrize("d", [1, 100])
def test_fused_pad_path_single_device(d):
    """Non-group-multiple last axes ride the same pad/unpad treatment
    on the fused scheme."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(4, scheme="fused")     # group 32
    x = _x(shape=(1, 2, d), seed=d)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def f(xs):
        return quantized_all_to_all(xs, "model", cfg)

    out = np.asarray(jax.jit(f)(x))
    assert out.shape == x.shape
    dp = padded_len(d, cfg.group)
    pad = jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))
    want = np.asarray(jax.jit(
        lambda v: codec.decode(codec.encode(v, cfg.with_scheme("two_step")),
                               cfg, dp, out_dtype=v.dtype))(pad))[..., :d]
    np.testing.assert_array_equal(out, want)


def test_nccl_scheme_bypasses_codec():
    """scheme="nccl" on an *enabled* a2a config is the exact BF16
    baseline: bits go through untouched (mirrors compressed_psum)."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = CommConfig(bits=2, group=32, scheme="nccl")
    x = _x(seed=9)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def f(xs):
        return quantized_all_to_all(xs, "model", cfg)

    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(x))


def test_dispatch_vjp_stays_bf16_combine():
    """The custom VJP of dispatch_all_to_all under the fused scheme is
    still the full-precision reverse A2A (combine direction): gradient
    of sum(dispatch(x)) is exactly ones — untouched by the forward
    quantization."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(2, scheme="fused")     # harshest forward
    x = _x(shape=(1, 2, 64), seed=11)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def g(xs):
        def loss(xr):
            return jnp.sum(dispatch_all_to_all(xr, "model", cfg))
        return jax.grad(loss)(xs)

    np.testing.assert_array_equal(np.asarray(jax.jit(g)(x)),
                                  np.ones(x.shape, np.float32))


def test_rdma_module_structure():
    """The TPU RDMA A2A module is importable off-TPU, shares the
    AllReduce choreography helpers, and claims its own collective_id
    (execution is TPU-only; see ROADMAP open items)."""
    from repro.kernels import rdma_all2all, rdma_allreduce

    assert callable(rdma_all2all.fused_all_to_all_rdma)
    assert rdma_all2all._push_rows is rdma_allreduce._push_rows
    assert rdma_all2all._ring_barrier is rdma_allreduce._ring_barrier
    # AllReduce phases use 0 and 1; the A2A barrier must not alias them
    assert rdma_all2all.A2A_COLLECTIVE_ID not in (0, 1)


def test_dispatcher_uses_emulation_off_tpu():
    """ops.fused_all_to_all must not touch the RDMA path on CPU."""
    from repro.kernels import ops

    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(8, scheme="fused")
    x = _x(shape=(1, 2, D), seed=1)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    def f(xs):
        return ops.fused_all_to_all(xs, "model", cfg)

    out = jax.jit(f)(x)
    want = jax.jit(lambda v: codec.decode(
        codec.encode(v, cfg), cfg, D, out_dtype=v.dtype))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_policy_with_scheme_routes_a2a():
    """with_scheme("fused") flips the MoE dispatch site too, so the
    launch CLIs' --comm-scheme reaches models/moe.py dispatch."""
    from repro.core.policy import paper_policy, with_scheme

    pol = with_scheme(paper_policy(), "fused")
    assert pol.a2a.scheme == "fused"
    assert pol.tp.scheme == "fused"
    nccl = with_scheme(paper_policy(), "nccl")
    assert nccl.a2a.scheme == "nccl"
