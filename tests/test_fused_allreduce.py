"""Fused two-step AllReduce: lockstep emulation vs the XLA two-step.

The ``"fused"`` scheme must be a drop-in for ``"two_step"``: identical
numerics (same wire bytes, same reduce order) with the codec+hop fused
into per-phase kernels. Single-device cases run everywhere; the full
8-device lockstep checks live in tests/_multidev_script.py (``fused_ar``)
and tests/test_collective_properties.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import codec, compressed_psum, default_comm_config
from repro.core.comm_config import CommConfig
from repro.kernels import emulate, ops
from repro.launch.mesh import make_test_mesh

N = 512


def _x(shape=(2, N), seed=0, scale=2.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32) * scale


@pytest.mark.parametrize("spike,scale_int", [(False, False), (True, True)])
def test_phase_kernels_roundtrip(spike, scale_int):
    """encode_rows -> decode_rows is the codec roundtrip; decode_reduce
    fuses the row sum."""
    cfg = CommConfig(bits=4, group=32, spike=spike, scale_int=scale_int)
    x = _x(seed=3)
    wire = emulate.encode_rows(x, cfg)
    assert wire.shape == (2, cfg.wire_bytes(N))
    dec = emulate.decode_rows(wire, cfg, N)
    # jit on both sides: eager-vs-jit FMA contraction differs at 1 ulp
    # for scale_int's f32 scales (see tests/test_backend_equality.py)
    ref = jax.jit(lambda b: codec.decode(b, cfg, N))(codec.encode(x, cfg))
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(ref))
    red = emulate.decode_reduce_rows(wire, cfg, N)
    np.testing.assert_allclose(np.asarray(red[0]),
                               np.asarray(jnp.sum(ref, axis=0)),
                               rtol=1e-6, atol=1e-6)


def test_encode_rows_matches_codec_bytes():
    """The bytes the fused AR pushes over the link ARE codec.encode's."""
    for bits in (2, 5, 8):
        cfg = default_comm_config(bits)
        x = _x(seed=bits)
        np.testing.assert_array_equal(
            np.asarray(emulate.encode_rows(x, cfg)),
            np.asarray(codec.encode(x, cfg.with_backend("ref"))))


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_fused_matches_two_step_single_device(bits):
    """tp=1 degenerate case still applies both QDQ phases identically."""
    mesh = make_test_mesh(data=1, model=1)
    x = _x(shape=(1, 640), seed=bits)

    def run(scheme):
        cfg = default_comm_config(bits, scheme=scheme)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("model"), out_specs=P("model"),
                           check_vma=False)
        def f(xs):
            return compressed_psum(xs[0], ("model",), cfg)[None]
        return np.asarray(jax.jit(f)(x))

    np.testing.assert_array_equal(run("fused"), run("two_step"))


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (XLA_FLAGS host platform)")
@pytest.mark.parametrize("bits,spike,scale_int",
                         [(8, False, False), (4, False, True),
                          (2, True, True)])
def test_fused_matches_two_step_multidevice(bits, spike, scale_int):
    """Acceptance: scheme="fused" == quantized_all_reduce numerics on
    fake CPU devices through the emulation backend."""
    mesh = make_test_mesh(data=1, model=4)
    x = _x(shape=(4, 3, 640), seed=bits)

    def run(scheme):
        cfg = CommConfig(bits=bits, group=32, spike=spike,
                         scale_int=scale_int, scheme=scheme)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("data", "model")),
                           out_specs=P(("data", "model")),
                           check_vma=False)
        def f(xs):
            return compressed_psum(xs[0], ("model",), cfg)[None]
        return np.asarray(jax.jit(f)(x))

    np.testing.assert_array_equal(run("fused"), run("two_step"))


def test_mesh_axis_names_ambient():
    """ops.fused_all_reduce derives full MESH coordinates from the
    ambient shard_map axis env (no caller threading needed)."""
    mesh = make_test_mesh(data=1, model=1)
    seen = {}

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P(), check_vma=False)
    def f(xs):
        seen["names"] = ops.mesh_axis_names()
        return xs

    f(jnp.zeros((4,)))
    assert seen["names"] == ("data", "model")


def test_rdma_module_structure():
    """The TPU RDMA module is importable off-TPU and guards its
    preconditions (execution is TPU-only; see ROADMAP open items)."""
    from repro.kernels import rdma_allreduce

    assert callable(rdma_allreduce.fused_all_reduce_rdma)
    # MESH addressing covers multi-axis meshes via mesh_axes
    coords_fn = rdma_allreduce._peer_coords
    assert coords_fn(3, "model", ("model",)) == (3,)


def test_dispatcher_uses_emulation_off_tpu():
    """ops.fused_all_reduce must not touch the RDMA path on CPU."""
    from repro.kernels import ops

    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(8, scheme="fused")
    x = _x(shape=(640,), seed=1)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P(), check_vma=False)
    def f(xs):
        return ops.fused_all_reduce(xs, "model", cfg)

    out = f(x)
    want = codec.qdq_wire(
        codec.qdq_wire(x, cfg), cfg)       # two QDQ phases at tp=1
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6)
