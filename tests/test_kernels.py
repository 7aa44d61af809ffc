"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import (fused_dequant_unpack, fused_quant_pack,
                           fused_spike_pack)
from repro.kernels import ref
from repro.kernels.dequant_unpack import dequant_unpack
from repro.kernels.quant_pack import quant_pack
from repro.kernels.spike_reserve import spike_pack

SWEEP = [(8, 128), (6, 128), (5, 128), (4, 32), (3, 32), (2, 32), (7, 128)]


def _rand(rows, n, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, n), jnp.float32)
    return (x * 3).astype(dtype)


@pytest.mark.parametrize("bits,group", SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,n", [(8, 4096), (16, 1024), (8, 256)])
def test_quant_pack_matches_ref(bits, group, dtype, rows, n):
    if n % group:
        pytest.skip("n not multiple of group")
    x = _rand(rows, n, dtype, seed=bits)
    p, s, z = quant_pack(x, bits=bits, group=group, interpret=True)
    pr, sr, zr = ref.quant_pack_ref(x, bits, group)
    np.testing.assert_array_equal(np.asarray(p), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(z), np.asarray(zr))

    y = dequant_unpack(p, s, z, bits=bits, group=group, n=n,
                       interpret=True)
    yr = ref.dequant_unpack_ref(pr, sr, zr, bits, group, n)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=0)


@pytest.mark.parametrize("bits,group", [(2, 32), (3, 32), (4, 32)])
def test_spike_kernel_matches_ref(bits, group):
    x = _rand(8, 4096, jnp.float32, seed=bits + 100)
    outs = spike_pack(x, bits=bits, group=group, interpret=True)
    refs = ref.spike_pack_ref(x, bits, group)
    names = ["payload", "scale", "zero", "spike_vals", "spike_idx"]
    for a, b, name in zip(outs, refs, names):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{name} mismatch")


@settings(max_examples=15, deadline=None)
@given(bits=st.sampled_from([2, 3, 4, 5, 6, 8]),
       rows=st.sampled_from([8, 24]),
       seed=st.integers(0, 2 ** 20))
def test_kernel_property_sweep(bits, rows, seed):
    group = 128 if bits >= 5 else 32
    x = _rand(rows, 512, jnp.float32, seed=seed)
    p, s, z = quant_pack(x, bits=bits, group=group, interpret=True)
    pr, sr, zr = ref.quant_pack_ref(x, bits, group)
    assert np.array_equal(np.asarray(p), np.asarray(pr))


@pytest.mark.parametrize("bits,group", SWEEP)
@pytest.mark.parametrize("spike,scale_int",
                         [(False, False), (True, False),
                          (False, True), (True, True)])
def test_wire_kernel_matches_ref_codec(bits, group, spike, scale_int):
    """The full-wire-format kernel == ref codec, byte for byte."""
    from repro.core import codec
    from repro.core.comm_config import CommConfig
    from repro.kernels.wire import decode_wire, encode_wire
    cfg = CommConfig(bits=bits, group=group, spike=spike,
                     scale_int=scale_int)
    x = _rand(8, 1024, jnp.float32, seed=bits + 10 * spike)
    buf = encode_wire(x, bits=bits, group=group, spike=spike,
                      scale_int=scale_int, theta=cfg.theta, interpret=True)
    ref_buf = codec.encode_ref(x, cfg)
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(ref_buf))
    y = decode_wire(buf, bits=bits, group=group, n=1024, spike=spike,
                    scale_int=scale_int, theta=cfg.theta, interpret=True)
    y_ref = jax.jit(lambda b: codec.decode_ref(b, cfg, 1024))(ref_buf)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_fused_wire_wrappers_pad_rows():
    """ops.fused_{en,de}code_wire pad odd row counts transparently."""
    from repro.core import codec
    from repro.core.comm_config import default_comm_config
    from repro.kernels.ops import fused_decode_wire, fused_encode_wire
    cfg = default_comm_config(3)
    x = _rand(5, 256, jnp.float32)
    buf = fused_encode_wire(x, cfg, use_pallas=True)
    assert buf.shape == (5, cfg.wire_bytes(256))
    np.testing.assert_array_equal(np.asarray(buf),
                                  np.asarray(codec.encode_ref(x, cfg)))
    y = fused_decode_wire(buf, cfg, 256, use_pallas=True)
    assert y.shape == (5, 256)


def test_ops_wrappers_pad_rows():
    """ops.py pads odd row counts to ROW_BLOCK transparently."""
    x = _rand(5, 256, jnp.float32)
    p, s, z = fused_quant_pack(x, 4, 32, use_pallas=True)
    pr, sr, zr = ref.quant_pack_ref(x, 4, 32)
    assert p.shape[0] == 5
    np.testing.assert_array_equal(np.asarray(p), np.asarray(pr))
    y = fused_dequant_unpack(p, s, z, 4, 32, 256, use_pallas=True)
    yr = ref.dequant_unpack_ref(pr, sr, zr, 4, 32, 256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=0)
    outs = fused_spike_pack(x, 2, 32, use_pallas=True)
    refs = ref.spike_pack_ref(x, 2, 32)
    for a, b in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("bits,group,spike,scale_int",
                         [(8, 128, False, False), (3, 32, False, False),
                          (2, 32, True, False), (5, 128, False, True)])
def test_long_rows_split_into_sub_rows(bits, group, spike, scale_int,
                                       monkeypatch):
    """On TPU a row longer than one grid step takes is encoded as
    sub-rows and spliced back: the wire is the whole row's, byte for
    byte, and decodes to the whole row's values. The dispatch is steered
    to its TPU branch; the kernels still run in interpret mode here."""
    from repro.core import codec
    from repro.core.comm_config import CommConfig
    from repro.kernels import ops, wire
    monkeypatch.setattr(ops, "_MAX_COLS", 1024)
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    for name in ("encode_wire", "decode_wire"):   # no TPU: interpret
        real = getattr(wire, name)
        monkeypatch.setattr(
            ops, name, lambda *a, _f=real, **k: _f(*a, **{**k,
                                                       "interpret": True}))
    cfg = CommConfig(bits=bits, group=group, spike=spike,
                     scale_int=scale_int)
    x = _rand(3, 4096, jnp.float32, seed=bits)
    assert ops._col_split(4096, group, on_tpu=True) == 4
    buf = ops.fused_encode_wire(x, cfg, use_pallas=True)
    want = codec.encode_ref(x, cfg)
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(want))
    y = ops.fused_decode_wire(buf, cfg, 4096, use_pallas=True)
    ref_dec = jax.jit(lambda w: codec.decode_ref(w, cfg, 4096))  # as fused
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref_dec(want)))
