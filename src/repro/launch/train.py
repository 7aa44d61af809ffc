"""Training launcher.

Runs real training on whatever devices exist (CPU smoke / a TPU slice);
the mesh shape adapts: ``--mesh data,model`` or ``--production``
(16x16 / 2x16x16, which on this CPU container only makes sense under
``--dryrun`` — use launch/dryrun.py for that path).

Example (CPU, reduced arch, a few hundred steps — deliverable b):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 300 --seq 128 --batch 8 --policy paper --ckpt /tmp/ck.npz
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.analysis import commcheck
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.comm_config import SCHEMES
from repro.core.policy import (BF16_POLICY, aggressive_policy,
                               depth_policy, describe_policy,
                               load_policy_file, paper_policy,
                               with_backend, with_framed_bridge,
                               with_scheme)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.model import param_groups
from repro.parallel.plan import make_plan
from repro.parallel.shardings import build_store
from repro.train import checkpoint as ckpt_lib
from repro.train.data import DataConfig, make_dataset, to_device
from repro.train.optim import OptimConfig
from repro.train.train_step import (init_train_state, make_train_step,
                                    wants_grad_ef, wants_qgrad_ef)

POLICIES = {"paper": paper_policy, "bf16": lambda: BF16_POLICY,
            "aggressive": aggressive_policy, "depth": depth_policy}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1,1",
                    help="data,model[,pod] sizes (devices must exist; "
                         "a pod axis turns on the cross-pod grad sync)")
    ap.add_argument("--policy", default="paper", choices=list(POLICIES))
    ap.add_argument("--policy-file", default=None,
                    help="JSON policy artifact (see configs/policies/); "
                         "overrides --policy — the schedule grammar "
                         "supports per-layer bit allocation")
    ap.add_argument("--framed-bridge", type=int, default=None,
                    metavar="BITS",
                    help="run the cross-pod gradient hop at BITS with "
                         "the self-describing frame header (core/frame) "
                         "while the in-pod tier keeps the policy's raw "
                         "grad config — SDP4Bit-style mixed-tier widths")
    ap.add_argument("--grad-ef", action="store_true",
                    help="error-feedback gradient compression: carry the "
                         "grad AR quantization error in the optimizer "
                         "state and re-inject it next step")
    ap.add_argument("--codec-backend", default="auto",
                    choices=("auto", "ref", "pallas"),
                    help="wire codec backend for every comm site")
    ap.add_argument("--comm-scheme", default=None, choices=SCHEMES,
                    help="override the collective schedule at every "
                         "enabled site: AllReduce sites and the MoE "
                         "dispatch A2A (e.g. 'fused' for the Pallas "
                         "RDMA kernels, 'nccl' for the exact baseline)")
    ap.add_argument("--check", action="store_true",
                    help="run the full commcheck pre-launch pass (site "
                         "lint, choreography, layout/VMEM) and abort "
                         "before compiling anything if a rule fires")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def run(cfg, args: argparse.Namespace):
    """Train ``cfg`` as ``args`` (from :func:`parse_args`) say.
    Returns ``(store, opt_state, history)``."""
    enable_compile_cache()
    mesh_dims = [int(x) for x in args.mesh.split(",")]
    data_n, model_n = mesh_dims[0], mesh_dims[1]
    pod_n = mesh_dims[2] if len(mesh_dims) > 2 else 0
    mesh = make_test_mesh(data=data_n, model=model_n, pod=pod_n)
    plan = make_plan(cfg, tp=model_n, fsdp=data_n)
    base_pol = load_policy_file(args.policy_file) if args.policy_file \
        else POLICIES[args.policy]()
    policy = with_backend(base_pol, args.codec_backend)
    if args.comm_scheme:
        policy = with_scheme(policy, args.comm_scheme)
    if args.framed_bridge is not None:
        policy = with_framed_bridge(policy, args.framed_bridge)
    if args.grad_ef:
        import dataclasses
        policy = dataclasses.replace(policy, grad_ef=True)
    opt_cfg = OptimConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                          total_steps=args.steps)

    pol_name = args.policy_file or args.policy
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params "
          f"({cfg.active_param_count()/1e6:.1f}M active), mesh "
          f"{dict(mesh.shape)}, policy={pol_name}")
    print(describe_policy(policy, cfg.n_layers))

    mesh_shape = {"data": data_n, "model": model_n}
    if pod_n:
        mesh_shape = {"pod": pod_n, **mesh_shape}
    on_tpu = jax.default_backend() == "tpu"
    if args.check:
        rep = commcheck.launch_report(
            cfg, plan, policy, mesh_shape, global_batch=args.batch,
            seq=args.seq, n_micro=args.n_micro, mode="train", tpu=on_tpu,
            subject=f"{args.arch}/{pol_name}")
        print(rep.format("[train] commcheck", max_warnings=10))
        if not rep.ok:
            raise SystemExit(2)
    # always on: fused-scheme launches that the RDMA kernels cannot
    # serve fail here with diagnostics, not deep inside pallas_call
    commcheck.check_fused_request(
        cfg, plan, policy, mesh_shape, global_batch=args.batch,
        seq=args.seq, n_micro=args.n_micro, mode="train", tpu=on_tpu,
        context=f"{args.arch}/{pol_name}")

    grad_ef = wants_grad_ef(policy, mesh)
    qgrad_ef = wants_qgrad_ef(policy, plan)
    if args.resume:
        store, opt, start = ckpt_lib.restore(args.resume, mesh)
        if grad_ef and "ef" not in opt:
            # older checkpoint without a residual: start EF from zero
            opt["ef"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), store)
        elif not grad_ef:
            # EF checkpoint resumed with EF off: the step's opt_spec has
            # no "ef" leaf, so a stale residual would be a pytree
            # mismatch
            opt.pop("ef", None)
        if qgrad_ef and "qef" not in opt:
            opt["qef"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(
                    (p.shape[0], p.shape[1], p.shape[2] * plan.fsdp),
                    jnp.float32), store)
        elif not qgrad_ef:
            opt.pop("qef", None)
        print(f"[train] resumed from {args.resume} @ step {start}")
    else:
        store = build_store(param_groups(cfg, plan), plan,
                            jax.random.PRNGKey(0), jnp.float32, mesh)
        opt = init_train_state(store, opt_cfg, grad_ef=grad_ef,
                               qgrad_ef=qgrad_ef, fsdp=plan.fsdp)
        start = 0

    step_fn = make_train_step(cfg, plan, policy, opt_cfg, mesh,
                              global_batch=args.batch,
                              n_micro=args.n_micro)
    enc = cfg.encoder.n_ctx if (cfg.is_enc_dec or cfg.has_cross) else None
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                 global_batch=args.batch, enc_ctx=enc,
                                 d_model=cfg.d_model))
    t0 = time.time()
    history = []
    for i in range(start, args.steps):
        batch = to_device(ds.batch(i))
        store, opt, metrics = step_fn(store, opt, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            history.append({"step": i, "loss": loss,
                            "grad_norm": float(metrics["grad_norm"]),
                            "lr": float(metrics["lr"])})
            dt = time.time() - t0
            print(f"[train] step {i:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"lr {float(metrics['lr']):.2e} ({dt:6.1f}s)",
                  flush=True)
    if args.ckpt:
        ckpt_lib.save(args.ckpt, store, opt, args.steps)
        print(f"[train] saved checkpoint to {args.ckpt}")
    print(json.dumps({"first_loss": history[0]["loss"],
                      "last_loss": history[-1]["loss"]}))
    return store, opt, history


def main(argv=None):
    args = parse_args(argv)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return run(cfg, args)


if __name__ == "__main__":
    main()
