"""The benchmark's own counts against what the compiler and the program
say, at smoke sizes: model FLOPs against ``cost_analysis()``, wire bytes
against the program's wire layout, and the codec kernels of a compiled
forward against the HLO of a described TPU v5e."""
import dataclasses

import pytest

import jax
import jax.numpy as jnp

from chipbench import counts, system
from repro.configs import get_config, get_smoke_config
from repro.core.comm_config import CommConfig, default_comm_config
from repro.core.policy import BF16_POLICY, paper_policy, with_backend
from repro.launch.mesh import make_test_mesh
from repro.models import attention
from repro.models import model as model_mod
from repro.parallel.plan import make_plan
from repro.train.serve_step import (decode_cache_specs, make_decode_step,
                                    make_prefill)


def dims(cfg):
    return counts.Dims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.d_ff, cfg.vocab, cfg.n_layers)


def abstract_store(cfg, plan, sharding=None):
    return {g: {n: jax.ShapeDtypeStruct((k, plan.tp, sp.flat_len(plan)),
                                        jnp.float32, sharding=sharding)
                for n, sp in specs.items()}
            for g, (k, specs) in model_mod.param_groups(cfg,
                                                        plan).items()}


def xla_flops(compiled) -> float:
    ca = compiled.cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


@pytest.fixture
def unrolled(monkeypatch):
    """Scans unrolled, so that cost_analysis counts every layer and chunk
    (it counts a loop body once)."""
    monkeypatch.setattr(model_mod, "UNROLL_LAYER_SCAN", True)
    monkeypatch.setattr(attention, "UNROLL_ATTN_SCAN", True)


@pytest.mark.parametrize("arch", ["qwen3-14b", "glm4-9b"])
def test_prefill_flops_match_cost_analysis(arch, unrolled):
    """At a whole number of the program's KV chunks, XLA counts every
    (query, key) pair, the causal half too: so its count is ours plus the
    masked half, and a few percent of elementwise work."""
    cfg = get_smoke_config(arch)
    plan, mesh = make_plan(cfg, 1, 1), make_test_mesh(1, 1)
    n = 2 * attention.KV_CHUNK
    fn = make_prefill(cfg, plan, BF16_POLICY, mesh, 1)
    got = xla_flops(fn.lower(abstract_store(cfg, plan), {
        "tokens": jax.ShapeDtypeStruct((1, n), jnp.int32)}).compile())
    dm = dims(cfg)
    ours = counts.prefill_flops(dm, n)
    masked = counts.attention_flops(dm, n * n - n * (n + 1) // 2)
    assert ours < got
    assert got == pytest.approx(ours + masked, rel=0.06)


def test_decode_flops_match_cost_analysis(unrolled):
    """The decode step attends to its whole cache, masked: at a full
    cache XLA's count is ours plus its elementwise work, most of it the
    per-step casts of the float32 store (one op per weight). At published
    widths (one layer), where that work is a small share as it is on the
    chip; at smoke widths it outweighs the matrix products."""
    cfg = dataclasses.replace(get_config("glm4-9b"), pattern_repeats=1)
    plan, mesh = make_plan(cfg, 1, 1), make_test_mesh(1, 1)
    b, c = 32, 1024
    gshapes, _ = decode_cache_specs(cfg, plan, mesh, b, c)
    fn = make_decode_step(cfg, plan, BF16_POLICY, mesh, b, c)
    got = xla_flops(fn.lower(abstract_store(cfg, plan), gshapes, {
        "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}).compile())
    ours = counts.decode_step_flops(dims(cfg), [c] * b)
    assert ours < got < 1.1 * ours


@pytest.mark.parametrize("bits", [8, 6, 4, 3, 2])
def test_wire_bytes_match_program_layout(bits):
    cfg = default_comm_config(bits)
    for n in (cfg.group, 5120, 131072):
        assert counts.wire_bytes(n, bits, cfg.group, cfg.spike) == \
            cfg.wire_bytes(n)
    spiked = CommConfig(bits=3, group=32, spike=True)
    assert counts.wire_bytes(4096, 3, 32, True) == spiked.wire_bytes(4096)


def test_codec_calls_of_a_forward():
    """Three all-reduces in a 1-layer forward (embedding, attention, MLP),
    each two encodes and two decodes; phase-1 bytes cover the whole
    message both ways."""
    dm = counts.Dims(256, 4, 2, 64, 512, 512, 1)
    f = counts.forward_codec(dm, tokens=16, tp=4, bits=8, group=128)
    assert f["encode_calls"] == f["decode_calls"] == 6
    n = 16 * 256
    w = counts.wire_bytes(n // 4, 8, 128)
    one = (n * 4 + 4 * w) + (n // 4 * 4 + w) + 2 * (4 * w + n * 4)
    assert f["bytes"] == 3 * one


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("tp", [1, 4])
def test_hlo_codec_kernels_match_count(topo, tp, monkeypatch):
    """The compiled forward for a described v5e holds the codec kernels
    the count says: the embedding's all-reduce and the scanned block's
    two, each two encodes and two decodes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_smoke_config("qwen3-14b"),
                              pattern_repeats=2)
    mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp),
                ("data", "model"))
    plan = make_plan(cfg, tp, 1)
    store = abstract_store(cfg, plan, NamedSharding(mesh, P(None, "model")))
    toks = jax.ShapeDtypeStruct((1, 256), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    policy = with_backend(paper_policy(), "auto")
    hlo = make_prefill(cfg, plan, policy, mesh, 1).lower(
        store, {"tokens": toks}).compile().as_text()
    assert dict(system.custom_kernels(hlo)) == counts.hlo_codec_sites(1)
