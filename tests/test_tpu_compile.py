"""The Pallas kernels of the main path compile for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU
compiler (Mosaic) refuses: a lane-dim reshape, a strided lane slice, a
bitcast that changes the element width, a DMA of a single uint8 row.
These tests compile the real kernels at real widths for a ``v5e:2x2``
that is described, not attached: the wire encode/decode at the four
codec configs of the benchmark plan, a flattened all-reduce message
longer than one grid step takes, and the fused RDMA AllReduce (both
phases) and All2All under ``shard_map`` on all four chips. Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time.
"""
import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import codec
from repro.core.comm_config import CommConfig
from repro.kernels import ops, rdma_all2all, rdma_allreduce
from repro.kernels.wire import decode_wire, encode_wire

# the benchmark plan's codec configs: paper TP int8, paper A2A int4, a
# bit-split width, and int2 with spike reserving
CONFIGS = {"int8_g128": CommConfig(bits=8, group=128),
           "int4_g32": CommConfig(bits=4, group=32),
           "int3_g32": CommConfig(bits=3, group=32),
           "int2_g32_spike": CommConfig(bits=2, group=32, spike=True)}
ROWS, N = 2048, 5120


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back without one;
    # the cache setting is restored for the tests that follow
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


def _kernels(compiled) -> collections.Counter:
    return collections.Counter(re.findall(
        r"%(\w+?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', compiled.as_text()))


def _kw(cfg):
    return dict(bits=cfg.bits, group=cfg.group, spike=cfg.spike,
                rotation=cfg.rotation, scale_int=cfg.scale_int,
                theta=cfg.theta, meta_dtype=cfg.meta_dtype)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_wire_kernels_compile(name, one_chip):
    """Encode and decode at n=5120 with the block the TPU dispatch
    picks (rows padded to it, as the dispatcher does)."""
    cfg = CONFIGS[name]
    block = ops._pick_block(ROWS, N, on_tpu=True)
    rows = -(-ROWS // block) * block
    x = jax.ShapeDtypeStruct((rows, N), jnp.float32, sharding=one_chip)
    enc = jax.jit(lambda v: encode_wire(v, block_rows=block,
                                        interpret=False, **_kw(cfg)))
    assert _kernels(enc.lower(x).compile()) == {"wire_encode": 1}
    w = jax.ShapeDtypeStruct((rows, cfg.wire_bytes(N)), jnp.uint8,
                             sharding=one_chip)
    dec = jax.jit(lambda b: decode_wire(b, n=N, out_dtype=jnp.bfloat16,
                                        block_rows=block, interpret=False,
                                        **_kw(cfg)))
    assert _kernels(dec.lower(w).compile()) == {"wire_decode": 1}


def test_long_row_codec_compiles(one_chip, monkeypatch):
    """A flattened prefill all-reduce message (batch 4 x 512 tokens x
    5120) is one row: the dispatch cuts it into sub-rows for the
    kernels and splices the wire back together."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    cfg = CONFIGS["int8_g128"].with_backend("pallas")
    n = 4 * 512 * 5120
    assert ops._col_split(n, cfg.group, on_tpu=True) > 1
    x = jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda v: codec.decode(codec.encode(v, cfg), cfg, n)
    ).lower(x).compile()
    assert _kernels(compiled) == {"wire_encode": 1, "wire_decode": 1}


@pytest.mark.parametrize("name", ["int8_g128", "int2_g32_spike"])
def test_rdma_allreduce_compiles(name, mesh4):
    """Both phase kernels at a decode-step message (batch 4 x 5120)."""
    cfg = CONFIGS[name]
    n = 4 * 5120

    def body(x):
        return rdma_allreduce.fused_all_reduce_rdma(
            x.reshape(-1), "model", cfg, mesh_axes=("data", "model")
        ).reshape(x.shape)

    f = jax.shard_map(body, mesh=mesh4, in_specs=P(None, "model"),
                      out_specs=P(None, "model"), check_vma=False)
    x = jax.ShapeDtypeStruct((1, 4 * n), jnp.float32,
                             sharding=NamedSharding(mesh4, P(None, "model")))
    assert _kernels(jax.jit(f).lower(x).compile()) == {
        "rdma_allreduce_scatter": 1, "rdma_allreduce_gather": 1}


def test_rdma_all2all_compiles(mesh4):
    """The MoE dispatch kernel: 8 rows of d=5120 to each of 4 peers."""
    cfg = CONFIGS["int4_g32"]

    def body(x):
        return rdma_all2all.fused_all_to_all_rdma(
            x[0], "model", cfg, mesh_axes=("data", "model"))[None]

    f = jax.shard_map(body, mesh=mesh4, in_specs=P("model"),
                      out_specs=P("model"), check_vma=False)
    x = jax.ShapeDtypeStruct((4, 4, 8, 5120), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, P("model")))
    assert _kernels(jax.jit(f).lower(x).compile()) == {"rdma_all2all": 1}
