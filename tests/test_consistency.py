"""System-invariant tests: decode==prefill consistency, MoE invariants,
optimizer behaviour, checkpoint roundtrip."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core.policy import BF16_POLICY
from repro.launch.mesh import make_test_mesh
from repro.models.model import forward, init_caches, param_groups
from repro.parallel.plan import make_plan
from repro.parallel.shardings import STORE_SPEC, build_store
from repro.models.layers import vocab_parallel_logits


def _last_logits_full(cfg, plan, store, mesh, toks, enc=None):
    def f(views, tokens, enc_embeds):
        hidden, unemb, _, _ = forward(views, tokens, cfg, plan,
                                      BF16_POLICY, enc_embeds=enc_embeds,
                                      dtype=jnp.float32)
        return vocab_parallel_logits(hidden[:, -1], unemb)
    sm = jax.shard_map(f, mesh=mesh, in_specs=(STORE_SPEC, P(), P()),
                       out_specs=P(None, "model"), check_vma=False)
    return np.asarray(jax.jit(sm)(store, toks, enc))


def _last_logits_decode(cfg, plan, store, mesh, toks, enc=None):
    b, s = toks.shape
    caches = None

    def step(views, caches, tok, enc_embeds):
        hidden, unemb, _, ncaches = forward(
            views, tok, cfg, plan, BF16_POLICY, enc_embeds=enc_embeds,
            caches=caches, dtype=jnp.float32)
        return vocab_parallel_logits(hidden[:, -1], unemb), ncaches

    def init():
        return init_caches(cfg, plan, b, s, jnp.float32)
    cspec = jax.tree_util.tree_map(lambda _: P(), jax.eval_shape(init))
    caches = jax.jit(jax.shard_map(init, mesh=mesh, in_specs=(),
                                   out_specs=cspec, check_vma=False))()
    sm = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(STORE_SPEC, cspec, P(), P()),
        out_specs=(P(None, "model"), cspec), check_vma=False))
    out = None
    for t in range(s):
        out, caches = sm(store, caches, toks[:, t:t + 1], enc)
    return np.asarray(out)


# decode==prefill across every cache type: KV ring, RG-LRU, m/sLSTM,
# whisper enc-dec, MoE
@pytest.mark.parametrize("arch", ["qwen3-14b", "recurrentgemma-2b",
                                  "xlstm-125m", "whisper-tiny",
                                  "moonshot-v1-16b-a3b"])
def test_decode_matches_prefill(arch):
    cfg = get_smoke_config(arch)
    mesh = make_test_mesh()
    plan = make_plan(cfg, tp=1, fsdp=1)
    store = build_store(param_groups(cfg, plan), plan,
                        jax.random.PRNGKey(0), jnp.float32, mesh)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 12)), jnp.int32)
    enc = None
    if cfg.is_enc_dec or cfg.has_cross:
        enc = jnp.asarray(rng.standard_normal(
            (2, cfg.encoder.n_ctx, cfg.d_model)) * 0.02, jnp.float32)
    full = _last_logits_full(cfg, plan, store, mesh, toks, enc)
    dec = _last_logits_decode(cfg, plan, store, mesh, toks, enc)
    np.testing.assert_allclose(dec, full, rtol=2e-3, atol=2e-3)


def test_moe_identical_experts_equals_dense():
    """If every expert holds the same weights, MoE == that single FFN
    regardless of routing (capacity high enough to keep all tokens)."""
    import dataclasses
    from repro.models import moe as moe_mod
    cfg = get_smoke_config("grok-1-314b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    mesh = make_test_mesh()
    plan = make_plan(cfg, tp=1, fsdp=1)
    rng = np.random.default_rng(1)
    d, f, e = cfg.d_model, cfg.moe.d_ff, cfg.moe.n_experts
    w1 = rng.standard_normal((d, f)).astype(np.float32) * 0.05
    w2 = rng.standard_normal((f, d)).astype(np.float32) * 0.05
    w3 = rng.standard_normal((d, f)).astype(np.float32) * 0.05
    p = {
        "moe_router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32),
        "moe_w1": jnp.asarray(np.broadcast_to(w1, (e, d, f)).copy()),
        "moe_w2": jnp.asarray(np.broadcast_to(w2, (e, f, d)).copy()),
        "moe_w3": jnp.asarray(np.broadcast_to(w3, (e, d, f)).copy()),
    }
    x = jnp.asarray(rng.standard_normal((2, 8, d)), jnp.float32)

    def f_moe(p, x):
        out, aux = moe_mod.moe_apply(p, x, cfg, plan, BF16_POLICY)
        return out
    sm = jax.shard_map(f_moe, mesh=mesh, in_specs=(P(), P()),
                       out_specs=P(), check_vma=False)
    out = np.asarray(jax.jit(sm)(p, x))
    h = np.asarray(x) @ w1
    g = np.asarray(x) @ w3
    from jax.nn import gelu
    want = np.asarray(gelu(jnp.asarray(h), approximate=True) * g @ w2)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


def test_adamw_minimizes_quadratic():
    from repro.train.optim import OptimConfig, adamw_update, init_opt_state
    cfg = OptimConfig(lr=0.1, warmup_steps=1, total_steps=200,
                      weight_decay=0.0, grad_clip=100.0)
    params = {"g": {"w": jnp.asarray([5.0, -3.0, 2.0])}}
    state = init_opt_state(params, cfg)
    for _ in range(150):
        grads = jax.tree_util.tree_map(lambda p: 2 * p, params)
        gn = jnp.sqrt(sum(jnp.sum(g ** 2) for g in
                          jax.tree_util.tree_leaves(grads)))
        params, state, _ = adamw_update(params, grads, state, cfg, gn)
    assert float(jnp.max(jnp.abs(params["g"]["w"]))) < 0.05


def test_lr_schedule_shape():
    from repro.train.optim import OptimConfig, lr_schedule
    cfg = OptimConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] == 0.0
    assert abs(lrs[10] - 1.0) < 0.01
    assert lrs[100] == pytest.approx(0.1, abs=0.01)
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))


def test_checkpoint_roundtrip(tmp_path):
    from repro.train import checkpoint as ck
    from repro.train.optim import OptimConfig, init_opt_state
    cfg = get_smoke_config("glm4-9b")
    mesh = make_test_mesh()
    plan = make_plan(cfg, tp=1, fsdp=1)
    store = build_store(param_groups(cfg, plan), plan,
                        jax.random.PRNGKey(3), jnp.float32, mesh)
    opt = init_opt_state(store, OptimConfig())
    path = str(tmp_path / "ck.npz")
    ck.save(path, store, opt, step=42)
    store2, opt2, step = ck.restore(path, mesh)
    assert step == 42
    a = {str(k): v for k, v in
         jax.tree_util.tree_leaves_with_path(store)}
    b = {str(k): v for k, v in
         jax.tree_util.tree_leaves_with_path(store2)}
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_param_counts_sane():
    """Full configs report plausible parameter counts."""
    from repro.configs import get_config
    expect = {
        "qwen3-14b": (12e9, 18e9),
        "command-r-35b": (30e9, 40e9),
        "grok-1-314b": (250e9, 340e9),
        "glm4-9b": (8e9, 12e9),
        "llama4-maverick-400b-a17b": (330e9, 440e9),
        "xlstm-125m": (100e6, 200e6),
        "llama3-8b": (7e9, 9e9),
        "whisper-tiny": (30e6, 80e6),
        "recurrentgemma-2b": (2e9, 3.5e9),
        # the assigned 48L x 64e config counts ~27.6B total (the HF
        # Moonlight card's 16B uses 27 layers; we implement the assigned
        # 48L exactly) — active stays ~4B ("a3b")
        "moonshot-v1-16b-a3b": (24e9, 31e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"
    # MoE active < total
    for arch in ("grok-1-314b", "llama4-maverick-400b-a17b",
                 "moonshot-v1-16b-a3b"):
        c = get_config(arch)
        assert c.active_param_count() < 0.35 * c.param_count()
