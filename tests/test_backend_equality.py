"""Cross-backend wire equality: the fused Pallas codec backend must be a
drop-in replacement for the pure-jnp reference backend — byte-identical
wire buffers for every supported config, matching decodes, and identical
collective results under shard_map."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import codec
from repro.core.collectives import compressed_psum
from repro.core.comm_config import BIT_UNITS, CommConfig, \
    default_comm_config
from repro.core.policy import paper_policy, with_backend
from repro.launch.mesh import make_test_mesh

ALL_BITS = sorted(BIT_UNITS)[1:]          # 2..8 (1-bit is payload-only)
N = 512


def _combos():
    for bits, group, spike, scale_int in itertools.product(
            ALL_BITS, (32, 128), (False, True), (False, True)):
        yield pytest.param(
            bits, group, spike, scale_int,
            id=f"int{bits}-g{group}"
               f"{'-sr' if spike else ''}{'-si' if scale_int else ''}")


def _x(rows=3, n=N, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, n), jnp.float32)
    return x * 3


@pytest.mark.parametrize("bits,group,spike,scale_int", _combos())
def test_encode_byte_identical(bits, group, spike, scale_int):
    cfg = CommConfig(bits=bits, group=group, spike=spike,
                     scale_int=scale_int)
    x = _x(seed=bits)
    ref = codec.encode(x, cfg.with_backend("ref"))
    pal = codec.encode(x, cfg.with_backend("pallas"))
    assert ref.dtype == pal.dtype == jnp.uint8
    assert ref.shape == pal.shape == (3, cfg.wire_bytes(N))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("bits,group,spike,scale_int", _combos())
def test_decode_roundtrip_matches(bits, group, spike, scale_int):
    """Both backends decode the same wire buffer to the same floats.

    Compared under jit on both sides: eager-vs-jit XLA FMA contraction
    differs at the 1-ulp level (scale_int's full-precision f32 scales
    expose it), and all real call sites (the collectives) are jitted.
    """
    cfg = CommConfig(bits=bits, group=group, spike=spike,
                     scale_int=scale_int)
    x = _x(seed=100 + bits)
    buf = codec.encode(x, cfg.with_backend("ref"))
    dec_ref = jax.jit(
        lambda b: codec.decode(b, cfg.with_backend("ref"), N))(buf)
    dec_pal = jax.jit(
        lambda b: codec.decode(b, cfg.with_backend("pallas"), N))(buf)
    np.testing.assert_array_equal(np.asarray(dec_ref), np.asarray(dec_pal))


@pytest.mark.parametrize("scale_int", [False, True])
def test_encode_byte_identical_nonfinite(scale_int):
    """Byte-identity must survive non-finite inputs (diverged grads):
    the spike kernel's masked reductions mirror spike_quantize op-for-op,
    including NaN propagation through nanmin/nanmax."""
    cfg = CommConfig(bits=2, group=32, spike=True, scale_int=scale_int)
    x = np.array(_x(seed=42))   # writable copy
    x[0, 3:8] = np.nan          # >2 NaNs in one group: leftovers stay NaN
    x[1, 40] = np.inf
    x[2, 100] = -np.inf
    xj = jnp.asarray(x)
    ref = codec.encode(xj, cfg.with_backend("ref"))
    pal = codec.encode(xj, cfg.with_backend("pallas"))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_qdq_wire_roundtrip_error_small(backend):
    cfg = default_comm_config(8, backend=backend)
    x = _x(seed=7)
    y = codec.qdq_wire(x, cfg)
    # INT8 g128 on N(0,3): scale ~ range/255 ~ 0.08, so half-ulp + bf16
    # meta error stays well under 0.15
    assert float(jnp.max(jnp.abs(y - x))) < 0.15


def test_odd_leading_shapes():
    """Pallas row padding is transparent for 1-D and >2-D inputs."""
    cfg = default_comm_config(4)
    for shape in [(N,), (5, N), (2, 3, N)]:
        x = jax.random.normal(jax.random.PRNGKey(1), shape) * 2
        ref = codec.encode(x, cfg.with_backend("ref"))
        pal = codec.encode(x, cfg.with_backend("pallas"))
        assert pal.shape == codec.wire_shape(shape, cfg)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
        back = codec.decode(pal, cfg.with_backend("pallas"), N)
        assert back.shape == shape


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (XLA_FLAGS host platform)")
def test_compressed_psum_identical_across_backends():
    mesh = make_test_mesh(data=1, model=4)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 1024), jnp.float32)

    def run(backend):
        cfg = default_comm_config(8, backend=backend)

        def f(xs):
            return compressed_psum(xs, ("model",), cfg)
        sm = jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                              out_specs=P("model"), check_vma=False)
        return np.asarray(jax.jit(sm)(x))

    np.testing.assert_array_equal(run("ref"), run("pallas"))


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >=4 devices (XLA_FLAGS host platform)")
def test_policy_with_backend_end_to_end():
    """paper_policy flipped to the pallas backend gives identical psums
    (spike + scale_int sites included via an aggressive cfg)."""
    mesh = make_test_mesh(data=1, model=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 512), jnp.float32)
    base = CommConfig(bits=2, group=32, spike=True, scale_int=True)

    def run(cfg):
        def f(xs):
            return compressed_psum(xs, ("model",), cfg)
        sm = jax.shard_map(f, mesh=mesh, in_specs=P("model"),
                              out_specs=P("model"), check_vma=False)
        return np.asarray(jax.jit(sm)(x))

    np.testing.assert_array_equal(run(base.with_backend("ref")),
                                  run(base.with_backend("pallas")))
    # policy-level switch resolves to the same site configs
    pol = with_backend(paper_policy(), "pallas")
    assert pol.tp.backend == "pallas" and pol.a2a.backend == "pallas"
    assert pol.grad.backend == "pallas"
