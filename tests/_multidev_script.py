"""Multi-device checks, run in a subprocess with 8 fake CPU devices.

Invoked by test_distributed.py as:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 python _multidev_script.py <check>
Exits non-zero on failure.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import (compressed_psum, default_comm_config,  # noqa: E402
                        dispatch_all_to_all)
from repro.core.codec import qdq_wire  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402


def check_quantized_ar():
    mesh = make_test_mesh(data=1, model=4, pod=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 640), jnp.float32)
    ref = np.sum(np.asarray(x), axis=0)
    for scheme in ("two_step", "fused", "hierarchical", "hier_pp"):
        for bits in (8, 5, 2):
            cfg = default_comm_config(bits, scheme=scheme)

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=P(("pod", "data", "model")),
                     out_specs=P(("pod", "data", "model")),
                     check_vma=False)
            def f(xs):
                return compressed_psum(xs[0], ("model", "pod"), cfg)[None]

            out = np.asarray(f(x))
            err = max(float(np.max(np.abs(out[i] - ref)))
                      for i in range(8))
            agree = max(float(np.max(np.abs(out[i] - out[0])))
                        for i in range(8))
            assert agree == 0.0, (scheme, bits, agree)
            # error bounded by a few quantization steps of the summed scale
            tol = {8: 0.2, 5: 1.5, 2: 8.0}[bits]
            assert err < tol, (scheme, bits, err)
    print("quantized_ar ok")


def check_framed_bridge():
    """Mixed-policy pod bridge: the pod-axis hop runs at its OWN width
    and framed (self-describing header + CRC32C, core/frame.py) while
    the ICI tier keeps the grad site's raw wire — and the numerics are
    BIT-IDENTICAL to the same mixed-width run unframed (the frame is
    pure envelope: byte-identical payload, header stripped on decode).
    """
    import dataclasses

    from repro.core.comm_config import CommConfig
    from repro.core.policy import CommPolicy, uniform, with_framed_bridge
    from repro.train.train_step import pod_grad_config

    mesh = make_test_mesh(data=1, model=4, pod=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 3, 640), jnp.float32)
    ref = np.sum(np.asarray(x), axis=0)
    inner = CommConfig(bits=4, group=32)     # ICI tier: 4-bit raw
    for scheme in ("two_step", "hierarchical", "hier_pp"):
        cfg = dataclasses.replace(inner, scheme=scheme)
        outs = {}
        for framed in (False, True):
            bridge = CommConfig(bits=8, group=128, scheme=scheme,
                                framed=framed)   # pod tier: 8-bit

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=P(("pod", "data", "model")),
                     out_specs=P(("pod", "data", "model")),
                     check_vma=False)
            def f(xs):
                return compressed_psum(xs[0], ("model", "pod"), cfg,
                                       None, None, bridge)[None]

            outs[framed] = np.asarray(jax.jit(f)(x))
        np.testing.assert_array_equal(outs[True], outs[False],
                                      err_msg=scheme)
        err = float(np.max(np.abs(outs[True][0] - ref)))
        assert err < 1.5, (scheme, err)

    # the policy-engine route: with_framed_bridge installs the framed
    # bridge config at the bridge site and pod_grad_config resolves it
    pol = with_framed_bridge(CommPolicy(grad=uniform(inner)), bits=8)
    bcfg = pod_grad_config(pol)
    assert bcfg.framed and bcfg.bits == 8 and bcfg.enabled
    assert pod_grad_config(CommPolicy(grad=uniform(inner))) == inner
    print("framed_bridge ok (bit-identical to unframed, all schemes)")


def check_fused_ar():
    """scheme="fused" (emulation backend on CPU) is numerically identical
    to the XLA two-step on 8 devices: same wire bytes, same reduce order
    — the lockstep guarantee the shared tile bodies provide."""
    from repro.core.comm_config import CommConfig

    mesh = make_test_mesh(data=1, model=8)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 3, 1280), jnp.float32)
    ref = np.sum(np.asarray(x), axis=0)
    for bits, spike, scale_int in ((8, False, False), (4, False, True),
                                   (2, True, True)):
        outs = {}
        for scheme in ("two_step", "fused"):
            cfg = CommConfig(bits=bits, group=32, spike=spike,
                             scale_int=scale_int, scheme=scheme)

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=P(("data", "model")),
                     out_specs=P(("data", "model")), check_vma=False)
            def f(xs):
                return compressed_psum(xs[0], ("model",), cfg)[None]

            outs[scheme] = np.asarray(jax.jit(f)(x))
        np.testing.assert_array_equal(outs["fused"], outs["two_step"])
        err = float(np.max(np.abs(outs["fused"][0] - ref)))
        assert err < {8: 0.3, 4: 12.0, 2: 16.0}[bits], (bits, err)
    print("fused_ar ok (bit-identical to two_step)")


def check_fused_a2a():
    """scheme="fused" A2A (emulation backend on CPU) is bit-identical to
    the XLA quantized_all_to_all on 8 devices: same wire bytes, same
    hop, same dequant — the lockstep guarantee the shared tile bodies
    provide — including the MoE dispatch buffer shapes the policy
    actually sends (models/moe.py capacity logic) and the pad path."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.core.comm_config import CommConfig
    from repro.models.moe import capacity

    mesh = make_test_mesh(data=1, model=8)

    def lockstep(xa, cfg_kw, label):
        outs = {}
        for scheme in ("two_step", "fused"):
            cfg = CommConfig(scheme=scheme, **cfg_kw)

            @partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
                     out_specs=P("model"), check_vma=False)
            def g(xs):
                return dispatch_all_to_all(xs[0], "model", cfg)[None]

            outs[scheme] = np.asarray(
                jax.jit(g)(xa).astype(jnp.float32))
        np.testing.assert_array_equal(outs["fused"], outs["two_step"],
                                      err_msg=label)
        return outs["fused"]

    # width x metadata sweep, incl. a non-group-multiple d (pad path)
    for bits, spike, scale_int in ((8, False, False), (4, False, True),
                                   (2, True, True)):
        for d in (128, 100):
            xa = jax.random.normal(jax.random.PRNGKey(bits + d),
                                   (8, 8, 3, d), jnp.float32) * 2
            lockstep(xa, dict(bits=bits, group=32, spike=spike,
                              scale_int=scale_int),
                     f"bits={bits} d={d}")

    # the real MoE dispatch shape: (ep, e_loc*cap, d_model) blocks in
    # the payload dtype (BF16 combine-direction dtype), capacity logic
    # straight from models/moe.py
    cfg = get_smoke_config("grok-1-314b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=2.0))
    ep = 8
    e_loc = cfg.moe.n_experts // ep if cfg.moe.n_experts >= ep else 1
    t = 24                                   # tokens per rank
    cap = capacity(t, cfg)
    xa = (jax.random.normal(
        jax.random.PRNGKey(0), (8, ep, e_loc * cap, cfg.d_model),
        jnp.float32) * 2).astype(jnp.bfloat16)
    out = lockstep(xa, dict(bits=4, group=32),
                   f"moe ep={ep} cap={cap} d={cfg.d_model}")
    assert np.all(np.isfinite(out))
    print(f"fused_a2a ok (bit-identical to XLA wire; moe cap={cap}, "
          f"d={cfg.d_model})")


def check_a2a_semantics():
    mesh = make_test_mesh(data=2, model=4)
    cfg = default_comm_config(4)
    xa = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 2, 128),
                           jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("model"),
             out_specs=P("model"), check_vma=False)
    def g(xs):
        return dispatch_all_to_all(xs[0], "model", cfg)[None]

    out = np.asarray(g(xa))
    for i in range(4):
        for j in range(4):
            want = np.asarray(qdq_wire(xa[j, i], cfg))
            np.testing.assert_allclose(out[i, j], want, atol=1e-6)

    # regression: last axis not a multiple of cfg.group (pad/unpad path)
    d, dp = 100, 128
    xb = jax.random.normal(jax.random.PRNGKey(5), (4, 4, 2, d), jnp.float32)
    out = np.asarray(g(xb))
    for i in range(4):
        for j in range(4):
            blk = jnp.pad(xb[j, i], ((0, 0), (0, dp - d)))
            want = np.asarray(qdq_wire(blk, cfg))[..., :d]
            np.testing.assert_allclose(out[i, j], want, atol=1e-6)
    print("a2a ok")


def check_train_two_policies():
    """Same init, BF16 vs paper policy: losses must be close (and both
    finite) on a (pod=2, data=2, model=2) mesh -> multi-axis grad path."""
    from repro.configs import get_smoke_config
    from repro.core.policy import BF16_POLICY, paper_policy
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import build_store
    from repro.train.data import DataConfig, make_dataset, to_device
    from repro.train.optim import OptimConfig
    from repro.train.train_step import init_train_state, make_train_step

    mesh = make_test_mesh(data=2, model=2, pod=2)
    cfg = get_smoke_config("qwen3-14b")
    plan = make_plan(cfg, tp=2, fsdp=2)
    opt_cfg = OptimConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=64,
                                 global_batch=8))
    batch = to_device(ds.batch(0))
    losses = {}
    for name, pol in (("bf16", BF16_POLICY), ("paper", paper_policy())):
        # fresh store per policy: the train step donates its inputs
        store = build_store(param_groups(cfg, plan), plan,
                            jax.random.PRNGKey(0), jnp.float32, mesh)
        step = make_train_step(cfg, plan, pol, opt_cfg, mesh,
                               global_batch=8)
        opt = init_train_state(store, opt_cfg)
        s2, o2, m = step(store, opt, batch)
        losses[name] = float(m["loss"])
        assert np.isfinite(losses[name])
        assert float(m["grad_norm"]) > 0
    diff = abs(losses["bf16"] - losses["paper"])
    assert diff < 0.1 * abs(losses["bf16"]) + 0.1, losses
    print("train_two_policies ok", losses)


def check_tp_equivalence():
    """The SAME logical model on (1,1)-mesh vs (2,4)-mesh: losses match.

    Build the tp=4 store, reconstruct each logical parameter on the host,
    rebuild a tp=1 store holding identical values, and compare the BF16
    (no-quantization) training loss. This is the strongest distribution-
    correctness check: manual TP + FSDP + collectives == single device.
    """
    from repro.configs import get_smoke_config
    from repro.core.policy import BF16_POLICY
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import build_store
    from repro.train.data import DataConfig, make_dataset, to_device
    from repro.train.optim import OptimConfig
    from repro.train.train_step import init_train_state, make_train_step

    cfg = get_smoke_config("glm4-9b")
    mesh4 = make_test_mesh(data=2, model=4)
    plan4 = make_plan(cfg, tp=4, fsdp=2)
    store4 = build_store(param_groups(cfg, plan4), plan4,
                         jax.random.PRNGKey(0), jnp.float32, mesh4)

    # reconstruct logical params from the tp=4 flat store -> tp=1 store
    mesh1 = make_test_mesh(data=1, model=1)
    plan1 = make_plan(cfg, tp=1, fsdp=1)
    groups4 = param_groups(cfg, plan4)
    groups1 = param_groups(cfg, plan1)
    store1 = {}
    for gname, (n_stack, specs4) in groups4.items():
        specs1 = groups1[gname][1]
        store1[gname] = {}
        for pname, sp4 in specs4.items():
            arr = np.asarray(store4[gname][pname])   # (k, 4, flat4)
            sp1 = specs1[pname]
            outs = []
            for si in range(arr.shape[0]):
                # per-rank local logical values
                locs = [arr[si, r, :sp4.numel_loc(plan4)]
                        .reshape(sp4.local_shape(plan4))
                        for r in range(plan4.tp)]
                if sp4.moe_fold is not None:
                    mp = plan4.moe
                    # ranks: m = ep_idx*etp + tp_idx
                    eps = []
                    for ei in range(mp.ep):
                        fparts = [locs[ei * mp.etp + ti]
                                  for ti in range(mp.etp)]
                        ax = 2 if sp4.moe_fold == "in" else 1
                        eps.append(np.concatenate(fparts, axis=ax))
                    full = np.concatenate(eps, axis=0)
                elif sp4.tp_dim is None:
                    full = locs[0]
                else:
                    full = np.concatenate(locs, axis=sp4.tp_dim)
                flat = full.reshape(-1)
                pad = sp1.flat_len(plan1) - flat.size
                outs.append(np.pad(flat, (0, pad))[None])  # tp=1 dim
            store1[gname][pname] = jnp.asarray(np.stack(outs))

    opt_cfg = OptimConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=64,
                                 global_batch=8))
    batch = to_device(ds.batch(0))

    step4 = make_train_step(cfg, plan4, BF16_POLICY, opt_cfg, mesh4,
                            global_batch=8)
    _, _, m4 = step4(store4, init_train_state(store4, opt_cfg), batch)
    step1 = make_train_step(cfg, plan1, BF16_POLICY, opt_cfg, mesh1,
                            global_batch=8)
    _, _, m1 = step1(store1, init_train_state(store1, opt_cfg), batch)
    l1, l4 = float(m1["loss"]), float(m4["loss"])
    g1, g4 = float(m1["grad_norm"]), float(m4["grad_norm"])
    assert abs(l1 - l4) < 2e-2 * abs(l1) + 2e-2, (l1, l4)
    assert abs(g1 - g4) < 5e-2 * g1 + 5e-2, (g1, g4)
    print("tp_equivalence ok", l1, l4, g1, g4)


def check_ep_slice():
    """EP token slicing (CommPolicy.ep_slice) is bit-exact vs the naive
    replicated dispatch (the §Perf iteration-1 optimization)."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.core.policy import BF16_POLICY
    from repro.models import moe as moe_mod
    from repro.parallel.plan import make_plan
    from jax import lax

    cfg = get_smoke_config("grok-1-314b")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    mesh = make_test_mesh(data=2, model=4)
    plan = make_plan(cfg, tp=4, fsdp=2)
    rng = np.random.default_rng(0)
    d, f, e = cfg.d_model, cfg.moe.d_ff, cfg.moe.n_experts
    W1 = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    W2 = jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.float32)
    W3 = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    R = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 12, d)), jnp.float32)

    def run(ep_slice):
        pol = dataclasses.replace(BF16_POLICY, ep_slice=ep_slice)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(),) * 5,
                 out_specs=P(), check_vma=False)
        def f_(W1g, W2g, W3g, Rg, xg):
            rank = lax.axis_index("model")
            mp = plan.moe
            ep_idx = rank // mp.etp
            sl = lambda W: lax.dynamic_slice_in_dim(
                W, ep_idx * mp.e_loc, mp.e_loc, 0)
            p = {"moe_router": Rg, "moe_w1": sl(W1g),
                 "moe_w2": sl(W2g), "moe_w3": sl(W3g)}
            out, aux = moe_mod.moe_apply(p, xg, cfg, plan, pol)
            return out
        return np.asarray(jax.jit(f_)(W1, W2, W3, R, x))

    o0, o1 = run(False), run(True)
    np.testing.assert_allclose(o1, o0, atol=2e-5)
    print("ep_slice ok (bit-exact vs replicated dispatch)")


def check_grad_ef_train():
    """2-bit cross-pod gradient sync: with error feedback the toy run
    (a) reaches a LOWER loss after 50 steps than the same policy
    without EF (the SDP4Bit convergence claim, acceptance-tested), and
    (b) tracks the exact-gradient parameter trajectory markedly better
    — the structural EF guarantee (both quantization stages' errors
    are re-injected, so the applied-gradient drift stays bounded).
    """
    from repro.configs import get_smoke_config
    from repro.core.comm_config import CommConfig
    from repro.core.policy import CommPolicy
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import build_store
    from repro.train.data import DataConfig, make_dataset, to_device
    from repro.train.optim import OptimConfig
    from repro.train.train_step import (init_train_state, make_train_step,
                                        wants_grad_ef)

    mesh = make_test_mesh(data=2, model=2, pod=2)
    cfg = get_smoke_config("qwen3-14b")
    plan = make_plan(cfg, tp=2, fsdp=2)
    steps = 50
    opt_cfg = OptimConfig(lr=3e-3, warmup_steps=2, total_steps=steps)
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=32,
                                 global_batch=8))
    # The coarsest 2-bit wire (group 128, no spike reserving): without
    # EF this measurably damages the run — the regime the SDP4Bit claim
    # is about. (With spike reserving + g32 the 2-bit error is small
    # enough that a 50-step toy comparison drowns in trajectory noise;
    # measured EF margins at THIS setting are +0.11..0.31 nats.)
    grad2 = CommConfig(bits=2, group=128, spike=False)
    pols = {
        "exact": CommPolicy(grad=CommConfig(enabled=False, scheme="nccl")),
        "plain": CommPolicy(grad=grad2, grad_ef=False),
        "ef": CommPolicy(grad=grad2, grad_ef=True),
    }
    finals, tails, stores = {}, {}, {}
    for name, pol in pols.items():
        store = build_store(param_groups(cfg, plan), plan,
                            jax.random.PRNGKey(0), jnp.float32, mesh)
        step = make_train_step(cfg, plan, pol, opt_cfg, mesh,
                               global_batch=8)
        opt = init_train_state(store, opt_cfg,
                               grad_ef=wants_grad_ef(pol, mesh))
        losses = []
        for i in range(steps):
            batch = to_device(ds.batch(i))
            store, opt, m = step(store, opt, batch)
            losses.append(float(m["loss"]))
            assert np.isfinite(losses[-1]), (name, i, losses[-1])
        finals[name] = losses[-1]
        tails[name] = float(np.mean(losses[-10:]))
        stores[name] = store

    def dist(name):
        t = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(stores[name]),
                        jax.tree_util.tree_leaves(stores["exact"])):
            d = a.astype(jnp.float32) - b.astype(jnp.float32)
            t += float(jnp.sum(d * d))
        return t ** 0.5

    d_plain, d_ef = dist("plain"), dist("ef")
    # (a) the acceptance loss claim: lower loss after 50 steps (the
    # tail-10 means are reported for context but not asserted — on a
    # 50-step toy they sit inside trajectory noise)
    assert finals["ef"] < finals["plain"], (finals, tails)
    # (b) trajectory tracking: EF must stay closer to the exact-gradient
    # run — measured ratio 0.755-0.760 at this setting, stable across
    # runs, while a broken EF path sits at ~1.0; 0.95 separates them
    # cleanly.
    assert d_ef < 0.95 * d_plain, (d_ef, d_plain)
    print("grad_ef_train ok", finals, tails,
          {"dist_plain": round(d_plain, 4), "dist_ef": round(d_ef, 4)})


def check_qgrad_ef_train():
    """2-bit quantized gradient reduce-scatter on the ZeRO/FSDP axis
    (the fsdp_all_gather transpose, now an explicit post-VJP pass):
    with error feedback the toy run (a) reaches a LOWER loss after 50
    steps than the same qgrad_rs policy without EF, and (b) tracks the
    exact-gradient parameter trajectory markedly better — the de-bias
    claim for the sharded-gradient path. The fsdp=4 axis gives each
    rank a quarter-shard, so the per-rank QDQ residual pytree
    (opt_state["qef"]) is genuinely exercised.
    """
    from repro.configs import get_smoke_config
    from repro.core.comm_config import CommConfig
    from repro.core.policy import CommPolicy
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import build_store
    from repro.train.data import DataConfig, make_dataset, to_device
    from repro.train.optim import OptimConfig
    from repro.train.train_step import (init_train_state, make_train_step,
                                        wants_grad_ef, wants_qgrad_ef)

    mesh = make_test_mesh(data=4, model=2)
    cfg = get_smoke_config("qwen3-14b")
    plan = make_plan(cfg, tp=2, fsdp=4)
    steps = 50
    opt_cfg = OptimConfig(lr=3e-3, warmup_steps=2, total_steps=steps)
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=32,
                                 global_batch=8))
    # Coarsest 2-bit wire (group 128, no spike): the regime where the
    # biased qgrad path visibly hurts a 50-step toy run, so the EF
    # margin clears trajectory noise (same reasoning as grad_ef_train).
    q2 = CommConfig(bits=2, group=128, spike=False)
    pols = {
        "exact": CommPolicy(),
        "plain": CommPolicy(qgrad_rs=q2, grad_ef=False),
        "ef": CommPolicy(qgrad_rs=q2, grad_ef=True),
    }
    finals, tails, stores = {}, {}, {}
    for name, pol in pols.items():
        store = build_store(param_groups(cfg, plan), plan,
                            jax.random.PRNGKey(0), jnp.float32, mesh)
        step = make_train_step(cfg, plan, pol, opt_cfg, mesh,
                               global_batch=8)
        opt = init_train_state(store, opt_cfg,
                               grad_ef=wants_grad_ef(pol, mesh),
                               qgrad_ef=wants_qgrad_ef(pol, plan),
                               fsdp=plan.fsdp)
        if name == "ef":
            assert "qef" in opt, list(opt)     # residual pytree present
        losses = []
        for i in range(steps):
            batch = to_device(ds.batch(i))
            store, opt, m = step(store, opt, batch)
            losses.append(float(m["loss"]))
            assert np.isfinite(losses[-1]), (name, i, losses[-1])
        finals[name] = losses[-1]
        tails[name] = float(np.mean(losses[-10:]))
        stores[name] = store

    def dist(name):
        t = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(stores[name]),
                        jax.tree_util.tree_leaves(stores["exact"])):
            d = a.astype(jnp.float32) - b.astype(jnp.float32)
            t += float(jnp.sum(d * d))
        return t ** 0.5

    d_plain, d_ef = dist("plain"), dist("ef")
    # (a) the acceptance loss claim: 2-bit qgrad with EF beats plain
    # 2-bit qgrad on final loss (measured 3.436 vs 3.468 at this
    # setting; tail-10 means 3.538 vs 3.686 — reported, not asserted).
    assert finals["ef"] < finals["plain"], (finals, tails)
    # (b) trajectory tracking: the EF run's parameters stay closer to
    # the exact run's than the plain run's do — measured ratio ~0.80
    # (15.28 vs 19.14), deterministic seeds; 0.95 separates it cleanly
    # from a broken EF path (~1.0).
    assert d_ef < 0.95 * d_plain, (d_ef, d_plain)
    print("qgrad_ef_train ok", finals, tails,
          {"dist_plain": round(d_plain, 4), "dist_ef": round(d_ef, 4)})


def check_depth_policy_train():
    """A depth-scheduled policy (edge layers INT8 TP, middle INT4, per
    the segmented pattern scan) trains end-to-end on the 8-device mesh
    and stays close to the BF16 loss — the policy-engine layer binding
    exercised through the real train step."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.core.policy import BF16_POLICY, depth_policy
    from repro.models.model import param_groups, policy_segments
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import build_store
    from repro.train.data import DataConfig, make_dataset, to_device
    from repro.train.optim import OptimConfig
    from repro.train.train_step import (init_train_state, make_train_step,
                                        wants_grad_ef)

    mesh = make_test_mesh(data=2, model=2, pod=2)
    cfg = get_smoke_config("qwen3-14b")
    cfg = dataclasses.replace(cfg, pattern_repeats=4)
    plan = make_plan(cfg, tp=2, fsdp=2)
    pol = depth_policy(k=1)                  # layers 0 / N-1 INT8, mid INT4
    assert len(policy_segments(cfg, pol.bind(cfg.n_layers))) == 3
    opt_cfg = OptimConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=64,
                                 global_batch=8))
    batch = to_device(ds.batch(0))
    losses = {}
    for name, p in (("bf16", BF16_POLICY), ("depth", pol)):
        store = build_store(param_groups(cfg, plan), plan,
                            jax.random.PRNGKey(0), jnp.float32, mesh)
        step = make_train_step(cfg, plan, p, opt_cfg, mesh, global_batch=8)
        opt = init_train_state(store, opt_cfg,
                               grad_ef=wants_grad_ef(p, mesh))
        _, _, m = step(store, opt, batch)
        losses[name] = float(m["loss"])
        assert np.isfinite(losses[name])
    diff = abs(losses["bf16"] - losses["depth"])
    assert diff < 0.1 * abs(losses["bf16"]) + 0.1, losses
    print("depth_policy_train ok", losses)


CHECKS = {
    "quantized_ar": check_quantized_ar,
    "fused_ar": check_fused_ar,
    "framed_bridge": check_framed_bridge,
    "fused_a2a": check_fused_a2a,
    "a2a": check_a2a_semantics,
    "train_two_policies": check_train_two_policies,
    "grad_ef_train": check_grad_ef_train,
    "qgrad_ef_train": check_qgrad_ef_train,
    "depth_policy_train": check_depth_policy_train,
    "tp_equivalence": check_tp_equivalence,
    "ep_slice": check_ep_slice,
}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
