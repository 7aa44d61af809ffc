"""Serving launcher: batched prefill + decode loop (greedy).

Example (CPU, reduced arch — deliverable b):
  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
      --batch 4 --prompt-len 32 --gen 16 --policy paper
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import commcheck
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.comm_config import SCHEMES
from repro.core.policy import (BF16_POLICY, aggressive_policy,
                               describe_policy, load_policy_file,
                               paper_policy, with_backend, with_scheme)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.model import param_groups
from repro.parallel.plan import make_plan
from repro.parallel.shardings import build_store
from repro.train.data import DataConfig, make_dataset, to_device
from repro.train.serve_step import (make_cache_init, make_decode_step,
                                    make_prefill)

POLICIES = {"paper": paper_policy, "bf16": lambda: BF16_POLICY,
            "aggressive": aggressive_policy}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--policy", default="paper", choices=list(POLICIES))
    ap.add_argument("--policy-file", default=None,
                    help="JSON policy artifact (see configs/policies/); "
                         "overrides --policy")
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
    return ap.parse_args(argv)


def run(cfg, args: argparse.Namespace) -> dict:
    """Serve one batch of ``cfg`` as ``args`` (from :func:`parse_args`)
    say: prefill, then the decode loop over the prompt and ``args.gen``
    new tokens. Returns the tokens and the host-clock timings."""
    enable_compile_cache()
    data_n, model_n = (int(x) for x in args.mesh.split(","))
    mesh = make_test_mesh(data=data_n, model=model_n)
    plan = make_plan(cfg, tp=model_n, fsdp=data_n)
    base_pol = load_policy_file(args.policy_file) if args.policy_file \
        else POLICIES[args.policy]()
    policy = with_backend(base_pol, args.codec_backend)
    if args.comm_scheme:
        policy = with_scheme(policy, args.comm_scheme)
    print(describe_policy(policy, cfg.n_layers))
    cache_len = args.prompt_len + args.gen

    pol_name = args.policy_file or args.policy
    mesh_shape = {"data": data_n, "model": model_n}
    on_tpu = jax.default_backend() == "tpu"
    if args.check:
        rep = commcheck.launch_report(
            cfg, plan, policy, mesh_shape, global_batch=args.batch,
            seq=args.prompt_len, mode="prefill", tpu=on_tpu,
            subject=f"{args.arch}/{pol_name}")
        print(rep.format("[serve] commcheck", max_warnings=10))
        if not rep.ok:
            raise SystemExit(2)
    # always on: fused-scheme launches that the RDMA kernels cannot
    # serve fail here with diagnostics, not deep inside pallas_call
    commcheck.check_fused_request(
        cfg, plan, policy, mesh_shape, global_batch=args.batch,
        seq=args.prompt_len, mode="prefill", tpu=on_tpu,
        context=f"{args.arch}/{pol_name}")

    store = build_store(param_groups(cfg, plan), plan,
                        jax.random.PRNGKey(0), jnp.float32, mesh)

    enc = cfg.encoder.n_ctx if (cfg.is_enc_dec or cfg.has_cross) else None
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                                 global_batch=args.batch, enc_ctx=enc,
                                 d_model=cfg.d_model))
    batch = to_device(ds.batch(0))
    prompts = batch["tokens"]

    # ---- TTFT: prefill (paper Fig. 2 site) ----
    prefill = make_prefill(cfg, plan, policy, mesh, args.batch)
    pb = {"tokens": prompts}
    if enc:
        pb["enc_embeds"] = batch["enc_embeds"]
    t0 = time.time()
    first = prefill(store, pb)
    first.block_until_ready()
    ttft = time.time() - t0
    print(f"[serve] TTFT (prefill {args.prompt_len} toks x{args.batch}, "
          f"policy={args.policy}): {ttft*1000:.1f} ms (incl. compile)")

    # ---- decode loop: feed prompt tokens into the cache, then generate --
    init = make_cache_init(cfg, plan, mesh, args.batch, cache_len)
    caches = init()
    step = make_decode_step(cfg, plan, policy, mesh, args.batch, cache_len)
    out = []
    tok = prompts[:, :1]
    t_compile = t_steady = 0.0
    for i in range(args.prompt_len + args.gen - 1):
        db = {"tokens": tok.astype(jnp.int32)}
        if enc:
            db["enc_embeds"] = batch["enc_embeds"]
        t0 = time.time()
        nt, caches = step(store, caches, db)
        jax.block_until_ready(nt)
        if i == 0:                    # first call traces + compiles
            t_compile = time.time() - t0
        else:
            t_steady += time.time() - t0
        if i + 1 < args.prompt_len:
            tok = prompts[:, i + 1:i + 2]       # teacher-forced prompt
        else:
            tok = jnp.asarray(nt)[:, None]
            out.append(np.asarray(nt))
    gen = np.stack(out, 1) if out else np.zeros((args.batch, 0), np.int32)
    steps = args.prompt_len + args.gen - 1
    steady = (f"{t_steady / (steps - 1) * 1000:.1f} ms/step steady-state"
              if steps > 1 else "n/a")
    print(f"[serve] {steps} decode steps: first step (compile) "
          f"{t_compile*1000:.1f} ms, {steady}")
    # Cache-seeding drift check: after the decode cache has consumed the
    # whole prompt token-by-token, its first generated token must agree
    # with prefill's full-sequence prediction — the two paths share
    # weights and greedy argmax, so any mismatch means the cache was
    # seeded or rolled wrong.
    if out:
        first_np = np.asarray(first)
        assert np.array_equal(out[0], first_np), (
            f"decode's first post-prompt token {out[0]} != prefill's "
            f"{first_np} — KV-cache seeding drift")
        print("[serve] prefill/decode agreement: first generated token "
              "matches prefill")
    print(f"[serve] generated tokens (first row): {gen[0][:16]}")
    assert np.all((gen >= 0) & (gen < cfg.vocab))
    print("[serve] OK")
    return {"first": np.asarray(first), "generated": gen,
            "prefill_first_call_s": ttft, "decode_first_step_s": t_compile,
            "decode_steady_s": t_steady, "decode_steps": steps}


def main(argv=None) -> dict:
    args = parse_args(argv)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return run(cfg, args)


if __name__ == "__main__":
    main()
