"""Frame conformance: the self-describing pod-bridge wire is trustworthy.

Every malformed-buffer class must raise its *typed*
:class:`repro.core.frame.FrameError` subclass on the host path, and the
traced path must NaN-poison exactly the corrupted rows — a framed buffer
never decodes into silently wrong numbers (the corruption class the raw
position-addressed wire cannot detect). The framed golden vectors in
tests/golden/wire_vectors.npz byte-pin the header + CRC32C exactly like
the raw wire is pinned.

Also the PR-8 silent-corruption regressions: spike-index overflow at
group > 128 (construction-time rejection + LAYOUT-SPIKEIDX) and the
serving batch truncation (``_local_batch`` raising instead of flooring).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec, frame
from repro.core.comm_config import FRAME_HEADER_BYTES, CommConfig

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts"))
from gen_golden_wire import golden_cfg  # noqa: E402

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "wire_vectors.npz")
_DATA = np.load(GOLDEN)
FRAME_KEYS = sorted(k for k in _DATA.files if k.startswith("frame_"))

CFG = CommConfig(bits=4, group=32, framed=True, backend="ref")
N = 64


def _x(rows=2, n=N, seed=0):
    return np.asarray(np.random.RandomState(seed)
                      .standard_normal((rows, n)), np.float32)


def _wire(cfg=CFG, rows=2, n=N, seed=0):
    return np.asarray(codec.encode(jnp.asarray(_x(rows, n, seed)),
                                   cfg)).copy()


# ---------------------------------------------------------------------------
# CRC32C
# ---------------------------------------------------------------------------

def test_crc32c_check_vector():
    assert frame.crc32c(b"123456789") == 0xE3069283


def test_crc32c_rows_matches_host():
    buf = np.random.RandomState(1).randint(0, 256, (3, 57), np.uint8)
    traced = np.asarray(jax.jit(frame.crc32c_rows)(jnp.asarray(buf)))
    host = [frame.crc32c(buf[r]) for r in range(buf.shape[0])]
    np.testing.assert_array_equal(traced, np.asarray(host, np.uint32))


# ---------------------------------------------------------------------------
# clean frames: framed == header + the exact raw wire
# ---------------------------------------------------------------------------

def test_frame_payload_is_the_raw_wire():
    x = _x()
    framed = np.asarray(codec.encode(jnp.asarray(x), CFG))
    raw = np.asarray(codec.encode(jnp.asarray(x), CFG.with_framed(False)))
    np.testing.assert_array_equal(framed[..., FRAME_HEADER_BYTES:], raw)
    assert framed.shape[-1] == CFG.wire_bytes(N) \
        == raw.shape[-1] + FRAME_HEADER_BYTES


def test_framed_roundtrip_bit_exact_with_raw():
    x = _x()
    framed = codec.decode(jnp.asarray(_wire()), CFG, N)
    raw_cfg = CFG.with_framed(False)
    raw = codec.decode(codec.encode(jnp.asarray(x), raw_cfg), raw_cfg, N)
    np.testing.assert_array_equal(np.asarray(framed), np.asarray(raw))


def test_self_describing_decode_matches_pinned_config():
    wire = _wire()
    no_cfg = np.asarray(frame.frame_decode(wire))
    with_cfg = np.asarray(frame.frame_decode(wire, CFG))
    np.testing.assert_array_equal(no_cfg, with_cfg)
    _, hdr = frame.frame_unwrap(wire)
    assert (hdr.bits, hdr.group, hdr.payload_len) == \
        (CFG.bits, CFG.group, CFG.wire_layout(N).total)


# ---------------------------------------------------------------------------
# malformed-buffer classes -> typed errors
# ---------------------------------------------------------------------------

def test_truncated_below_header():
    with pytest.raises(frame.FrameTruncatedError):
        frame.frame_unwrap(_wire()[:, :FRAME_HEADER_BYTES - 1])


def test_truncated_payload():
    with pytest.raises(frame.FrameTruncatedError):
        frame.frame_unwrap(_wire()[:, :-5])


def test_trailing_garbage_is_a_length_error():
    wire = _wire()
    padded = np.concatenate(
        [wire, np.zeros((wire.shape[0], 3), np.uint8)], axis=-1)
    with pytest.raises(frame.FrameLengthError):
        frame.frame_unwrap(padded)


def test_wrong_version():
    wire = _wire()
    wire[:, 2] = 99
    with pytest.raises(frame.FrameVersionError):
        frame.frame_unwrap(wire)


def test_bad_magic():
    wire = _wire()
    wire[:, 0] = 0x00
    with pytest.raises(frame.FrameHeaderError):
        frame.frame_unwrap(wire)


def test_config_disagreement():
    with pytest.raises(frame.FrameHeaderError):
        frame.frame_unwrap(_wire(), CFG.with_bits(8))


def test_row_header_disagreement():
    wire = _wire()
    wire[1, :frame._PREFIX_BYTES] = frame.header_prefix(
        CFG.with_bits(2), wire.shape[-1] - FRAME_HEADER_BYTES)
    with pytest.raises(frame.FrameHeaderError):
        frame.frame_unwrap(wire)


def test_non_uint8_rejected():
    with pytest.raises(frame.FrameHeaderError):
        frame.frame_unwrap(_wire().astype(np.int32))


def test_caller_length_disagreement():
    with pytest.raises(frame.FrameLengthError):
        frame.frame_decode(_wire(), CFG, n=2 * N)


@pytest.mark.parametrize("cfg", [
    CFG,
    CommConfig(bits=2, group=32, spike=True, scale_int=True,
               framed=True, backend="ref"),
    CommConfig(bits=8, group=128, rotation=True, framed=True,
               backend="ref"),
], ids=["int4", "int2_sr_si", "int8_rot"])
def test_every_single_bit_flip_is_detected(cfg):
    """Full CRC coverage, proven bluntly: flip one bit in every byte of
    the frame (header and payload) — each flip must raise a typed
    FrameError, never return a payload."""
    wire = _wire(cfg, rows=1, n=2 * cfg.group)
    for i in range(wire.shape[-1]):
        mut = wire.copy()
        mut[0, i] ^= 0x01
        with pytest.raises(frame.FrameError):
            frame.frame_unwrap(mut, cfg)


# ---------------------------------------------------------------------------
# traced path: per-row NaN poison inside jit, bit-exact on clean rows
# ---------------------------------------------------------------------------

def test_traced_clean_passthrough_bit_exact():
    wire = _wire(rows=3)
    traced = np.asarray(jax.jit(
        lambda b: codec.decode(b, CFG, N))(jnp.asarray(wire)))
    host = np.asarray(codec.decode(wire, CFG, N))
    np.testing.assert_array_equal(traced, host)
    assert np.all(np.isfinite(traced))


def test_traced_poisons_exactly_the_corrupt_rows():
    wire = _wire(rows=3)
    host = np.asarray(codec.decode(wire, CFG, N))
    bad = wire.copy()
    bad[1, FRAME_HEADER_BYTES + 7] ^= 0x10      # payload corruption
    bad[2, 4] ^= 0x01                           # header corruption
    out = np.asarray(jax.jit(
        lambda b: codec.decode(b, CFG, N))(jnp.asarray(bad)))
    np.testing.assert_array_equal(out[0], host[0])
    assert np.all(np.isnan(out[1])) and np.all(np.isnan(out[2]))


def test_traced_truncation_is_a_static_error():
    wire = _wire()
    with pytest.raises(frame.FrameTruncatedError):
        jax.jit(lambda b: codec.decode(b, CFG, N))(
            jnp.asarray(wire[:, :-4]))


# ---------------------------------------------------------------------------
# framed golden vectors: header + CRC byte-pinned like the raw wire
# ---------------------------------------------------------------------------

def _golden_combo(key):
    stem = key[len("frame_"):]
    bits = int(stem.split("_")[0][len("int"):])
    return bits, stem.endswith("_sr"), stem.endswith("_rot")


def test_framed_golden_keys_exist():
    assert FRAME_KEYS == sorted(
        f"frame_int{b}{t}" for b in (2, 4, 8)
        for t in ("", "_sr", "_rot"))


@pytest.mark.parametrize("key", FRAME_KEYS)
def test_framed_encode_matches_golden(key):
    bits, spike, rot = _golden_combo(key)
    cfg = golden_cfg(bits, spike, rot).with_framed()
    buf = codec.encode(jnp.asarray(_DATA["x"]), cfg)
    np.testing.assert_array_equal(np.asarray(buf), _DATA[key])
    assert _DATA[key].shape[-1] == cfg.wire_bytes(_DATA["x"].shape[-1])


@pytest.mark.parametrize("key", FRAME_KEYS)
def test_framed_golden_self_describes(key):
    """Archived framed buffers decode with no out-of-band config."""
    y = np.asarray(frame.frame_decode(_DATA[key]))
    assert y.shape == _DATA["x"].shape and np.all(np.isfinite(y))


# ---------------------------------------------------------------------------
# PR-8 regressions: spike-index overflow, serving batch truncation
# ---------------------------------------------------------------------------

def test_spike_group_overflow_rejected_at_construction():
    with pytest.raises(AssertionError, match="group <= 128"):
        CommConfig(bits=2, group=512, spike=True, scale_int=True)
    with pytest.raises(AssertionError, match="group <= 128"):
        CommConfig(bits=2, group=256, spike=True)
    CommConfig(bits=2, group=128, spike=True, scale_int=True)  # boundary


def test_spike_capacity_rule():
    from repro.analysis.layout import check_spike_capacity
    diags = check_spike_capacity(512, True)
    assert [d.rule for d in diags] == ["LAYOUT-SPIKEIDX"]
    assert check_spike_capacity(128, True) == []
    assert check_spike_capacity(512, False) == []   # 2-byte meta dtype


class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_local_batch_raises_on_truncation():
    """global_batch=6 on (pod=2, data=2): batch_spec falls back to
    P(("data",)) but the cache tree shards over pod x data = 4 slices —
    the old floor division served 1 row per slice and dropped 2."""
    from repro.train.serve_step import _local_batch
    mesh = _FakeMesh(pod=2, data=2, model=2)
    with pytest.raises(ValueError, match="silently drop"):
        _local_batch(6, mesh)


def test_local_batch_divisible_and_replicated_paths():
    from repro.train.serve_step import _local_batch
    mesh = _FakeMesh(pod=2, data=2, model=2)
    assert _local_batch(8, mesh) == 2
    # odd batch: batch_spec replicates, so every rank holds all rows
    assert _local_batch(3, mesh) == 3


def test_train_batch_spec_never_truncates():
    """The train-path guard: whatever axes batch_spec shards over, their
    product divides the batch (replication is the fallback, never a
    silent floor)."""
    from repro.train.train_step import batch_spec
    for pod, data, gb in [(2, 2, 8), (2, 2, 6), (2, 2, 3), (1, 4, 6),
                          (2, 3, 7), (3, 2, 4)]:
        mesh = _FakeMesh(pod=pod, data=data, model=2)
        spec = batch_spec(gb, mesh)
        axes = spec[0] if len(spec) else ()
        if isinstance(axes, str):         # P(("data",))[0] is 'data'
            axes = (axes,)
        size = 1
        for a in (axes or ()):
            size *= mesh.shape[a]
        assert gb % size == 0, (pod, data, gb, spec)
