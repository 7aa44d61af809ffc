import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For every (architecture x input shape) pair, lower + compile the real
step function (train_step / prefill / serve_step) on the production mesh
with ShapeDtypeStruct inputs — no allocation — and record:

  * memory_analysis()      bytes per device (proves it fits)
  * cost_analysis()        HLO FLOPs / bytes accessed
  * collective bytes       parsed from the compiled HLO (all-gather /
                           all-reduce / reduce-scatter / all-to-all /
                           collective-permute output sizes)
  * the three roofline terms for TPU v5e (197 TF/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
      --shape train_4k [--multi-pod] [--policy paper|bf16|aggressive]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, all_pairs, get_config, lowering_plan)
from repro.core.policy import BF16_POLICY, CommPolicy, aggressive_policy, \
    describe_policy, optimized_policy, paper_policy, with_framed_bridge
from repro.launch.mesh import make_production_mesh
from repro.models.config import INPUT_SHAPES, ModelConfig
from repro.models.model import param_groups
from repro.parallel.plan import make_plan
from repro.parallel.shardings import store_shapes
from repro.train.optim import OptimConfig
from repro.train.serve_step import decode_cache_specs, make_decode_step, \
    make_prefill
from repro.train.train_step import make_train_step

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

# op-name detector; result types are extracted by string split (robust
# to tuple types and /*index=N*/ comments in long operand lists)
_COLL_OP_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(", re.IGNORECASE)

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|"
                       r"pred|c64|c128)\[([0-9,]*)\]")


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind *result* bytes summed over the module.

    Robust to tuple result types and embedded /*index=N*/ comments: for
    every `%name = <TYPE> <op>(...)` line the TYPE segment between the
    first '=' and the op keyword is scanned for dtype[shape] tokens.
    """
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_OP_RE.search(line)
        if not m or m.group(2) == "-done":
            continue
        eq = line.find("=")
        if eq < 0 or eq > m.start():
            continue
        kind = m.group(1).lower()
        out[kind] = out.get(kind, 0) + _tensor_bytes(line[eq + 1:m.start()])
    return out


def input_specs(cfg: ModelConfig, shape_name: str, mesh,
                cache_len: Optional[int] = None):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    shp = INPUT_SHAPES[shape_name]
    b, s = shp.global_batch, shp.seq_len
    tok_s = 1 if shp.mode == "decode" else s
    batch = {"tokens": jax.ShapeDtypeStruct((b, tok_s), jnp.int32)}
    if shp.mode == "train":
        batch["labels"] = jax.ShapeDtypeStruct((b, tok_s), jnp.int32)
    if cfg.is_enc_dec or cfg.has_cross:
        batch["enc_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder.n_ctx, cfg.d_model), jnp.bfloat16)
    return batch


def abstract_store(cfg, plan):
    return store_shapes(param_groups(cfg, plan), plan, jnp.bfloat16)


def abstract_opt(store, moment_dtype=jnp.float32):
    cast = lambda s: jax.ShapeDtypeStruct(s.shape, moment_dtype)
    return {"m": jax.tree_util.tree_map(cast, store),
            "v": jax.tree_util.tree_map(cast, store),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _policy(name: str) -> CommPolicy:
    return {"paper": paper_policy(), "bf16": BF16_POLICY,
            "optimized": optimized_policy(),
            "aggressive": aggressive_policy()}[name]


def _depth_reduced(cfg: ModelConfig, n: int) -> ModelConfig:
    """Same architecture with pattern_repeats=n (and encoder depth n) —
    used by the slope-corrected roofline (see analyse_roofline)."""
    import dataclasses
    kw = {"pattern_repeats": n}
    if cfg.is_enc_dec:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=n)
    return dataclasses.replace(cfg, **kw)


def _lstm_seq_flops(cfg: ModelConfig, plan, shape, mode: str) -> float:
    """Analytic per-device FLOPs of the m/sLSTM *sequence* scans, which
    XLA's cost model counts once regardless of trip count. Per step:
    mLSTM ~ 6*dh^2 per head (C update + read), sLSTM ~ 8*dh^2 + O(dh)
    (4 block-diag recurrent matmuls). Training multiplies by 3 (fwd +
    bwd recompute + bwd)."""
    if not any(k in ("mlstm", "slstm") for k in cfg.layer_kinds):
        return 0.0
    dh = cfg.d_model // cfg.n_heads
    b_loc = max(shape.global_batch // 16, 1)
    s = 1 if mode == "decode" else shape.seq_len
    per_step = {"mlstm": 6 * dh * dh, "slstm": 8 * dh * dh}
    tot = 0.0
    for k in cfg.layer_kinds:
        if k in per_step:
            tot += b_loc * plan.nh_lstm_loc * s * per_step[k]
    return tot * (3.0 if mode == "train" else 1.0)


def _fused_memory_estimate(cfg: ModelConfig, plan, shape, mode: str,
                           cache_len: int) -> float:
    """Per-device HBM traffic (bytes) under ideal TPU fusion.

    The CPU-backend HLO "bytes accessed" counts every unfused op's
    operands (~50-100x what a fused TPU pass moves), so the memory
    roofline term uses this analytic estimate instead (the raw HLO
    number is still reported as t_memory_hlo, an upper bound):

      weights: every TP-local parameter is read once per forward
               (+ once in bwd, + once in the remat replay for train),
               + ZeRO optimizer state traffic on the 1/fsdp shard;
      activations: ~10 fused passes over (tokens_loc x d) per layer
               (qkv, scores, av, out, norms, mlp up/gate/down,
               residuals), x3 for train (fwd + remat + bwd);
      kv-cache: decode reads the full per-device cache per step and
               writes one slot.
    """
    groups = param_groups(cfg, plan)
    w_bytes = 0
    for gname, (n_stack, specs) in groups.items():
        for name, sp in specs.items():
            w_bytes += n_stack * sp.numel_loc(plan) * 2      # bf16
    dp = 16
    b_loc = max(shape.global_batch // dp, 1)
    s = 1 if mode == "decode" else shape.seq_len
    toks = b_loc * s
    act = 10 * toks * cfg.d_model * 2 * max(cfg.n_layers, 1)
    if mode == "train":
        total = 3 * (w_bytes + act)
        total += (w_bytes // plan.fsdp) * 14   # fp32 p/m/v read+write
    else:
        total = w_bytes + act
    if mode == "decode":
        kv_kinds = sum(1 for k in cfg.layer_kinds
                       if k in ("dense", "local", "moe", "enc", "dec"))
        if plan.kv_mode == "shard":
            c_loc = cache_len
        else:
            c_loc = cache_len // plan.tp
        win = min(cache_len, cfg.window) if cfg.window else cache_len
        c_loc = min(c_loc, win)
        total += kv_kinds * b_loc * c_loc * plan.kv_loc * cfg.hd * 2 * 2
    return float(total)


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               policy_name: str = "paper", verbose: bool = True,
               policy: Optional[CommPolicy] = None,
               n_micro: Optional[int] = None,
               framed_bridge: Optional[int] = None) -> Dict:
    t0 = time.time()
    lp = lowering_plan(arch, shape_name)
    rec: Dict = {"arch": arch, "shape": shape_name, "mode": lp.mode,
                 "variant": lp.variant, "multi_pod": multi_pod,
                 "policy": policy_name}
    if lp.skip:
        rec["status"] = "skip"
        rec["skip_reason"] = lp.skip
        return rec

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(cfg, tp=16, fsdp=lp.fsdp)
    pol = policy if policy is not None else _policy(policy_name)
    if framed_bridge is not None:
        pol = with_framed_bridge(pol, framed_bridge)
        rec["framed_bridge"] = framed_bridge
    if verbose:
        print(f"[dryrun] policy plan ({policy_name}, {cfg.n_layers} "
              f"layers):")
        print(describe_policy(pol, cfg.n_layers))
    shp = INPUT_SHAPES[shape_name]
    store = abstract_store(cfg, plan)
    batch = input_specs(cfg, shape_name, mesh, lp.cache_len)
    micro = n_micro if n_micro is not None else lp.n_micro

    # comm-safety pre-check: abort before the (expensive) lowering +
    # cost analysis if any commcheck rule fires for this exact tuple
    from repro.analysis.commcheck import launch_report
    crep = launch_report(cfg, plan, pol, dict(mesh.shape),
                         global_batch=shp.global_batch, seq=shp.seq_len,
                         n_micro=micro or 1, mode=lp.mode,
                         subject=f"{arch}/{shape_name}/{policy_name}")
    if not crep.ok:
        print(crep.format("[dryrun] commcheck", max_warnings=10))
        rec.update(status="commcheck_failed",
                   commcheck_errors=[d.format() for d in crep.errors])
        return rec
    rec["commcheck"] = "ok"

    with mesh:
        if lp.mode == "train":
            opt_cfg = OptimConfig()
            fn = make_train_step(cfg, plan, pol, opt_cfg, mesh,
                                 global_batch=shp.global_batch,
                                 n_micro=micro)
            opt = abstract_opt(store)
            lowered = fn.lower(store, opt, batch)
        elif lp.mode == "prefill":
            fn = make_prefill(cfg, plan, pol, mesh, shp.global_batch,
                              window_override=lp.window_override)
            lowered = fn.lower(store, batch)
        else:  # decode
            cshapes, _ = decode_cache_specs(cfg, plan, mesh,
                                            shp.global_batch, lp.cache_len)
            fn = make_decode_step(cfg, plan, pol, mesh, shp.global_batch,
                                  lp.cache_len,
                                  window_override=lp.window_override)
            lowered = fn.lower(store, cshapes, batch)
        compiled = lowered.compile()

    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)

    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))
    # collective bytes parsed from the (per-device SPMD) module
    coll_total = float(sum(coll.values()))

    rec.update({
        "status": "ok",
        "devices": n_dev,
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": flops,
        "bytes_per_device": bytes_hbm,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "mem": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        # roofline terms, seconds (per-device quantities / per-chip rates)
        "t_compute": flops / PEAK_FLOPS,
        "t_memory": bytes_hbm / HBM_BW,
        "t_collective": coll_total / ICI_BW,
    })
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
             "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)

    # useful-compute ratio: MODEL_FLOPS / total HLO FLOPs
    tokens = shp.global_batch * (1 if lp.mode == "decode" else shp.seq_len)
    n_active = cfg.active_param_count()
    mf = (6 if lp.mode == "train" else 2) * n_active * tokens
    rec["model_flops"] = mf
    rec["model_flops_ratio"] = (mf / (flops * n_dev)) if flops else None

    if verbose:
        print(json.dumps(rec, indent=2, default=str))
    return rec


def _measure(cfg, shape_name, lp, pol, mesh, micro) -> Dict:
    """Compile one config and return per-device (flops, bytes, coll)."""
    plan = make_plan(cfg, tp=16, fsdp=lp.fsdp)
    shp = INPUT_SHAPES[shape_name]
    store = abstract_store(cfg, plan)
    batch = input_specs(cfg, shape_name, mesh, lp.cache_len)
    with mesh:
        if lp.mode == "train":
            fn = make_train_step(cfg, plan, pol, OptimConfig(), mesh,
                                 global_batch=shp.global_batch,
                                 n_micro=micro)
            lowered = fn.lower(store, abstract_opt(store), batch)
        elif lp.mode == "prefill":
            fn = make_prefill(cfg, plan, pol, mesh, shp.global_batch,
                              window_override=lp.window_override)
            lowered = fn.lower(store, batch)
        else:
            cshapes, _ = decode_cache_specs(cfg, plan, mesh,
                                            shp.global_batch, lp.cache_len)
            fn = make_decode_step(cfg, plan, pol, mesh, shp.global_batch,
                                  lp.cache_len,
                                  window_override=lp.window_override)
            lowered = fn.lower(store, cshapes, batch)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(sum(coll.values())),
            "coll_by_kind": coll}


def analyse_roofline(arch: str, shape_name: str, *,
                     policy_name: str = "paper",
                     policy: Optional[CommPolicy] = None,
                     n_micro: Optional[int] = None,
                     force_fsdp: Optional[int] = None,
                     verbose: bool = True) -> Dict:
    """Slope-corrected roofline (single-pod).

    XLA's cost_analysis counts while-loop bodies ONCE (verified
    empirically), so a scanned-layer model under-reports by ~n_layers.
    We therefore compile the SAME architecture at pattern depth 1 and 2,
    take the per-layer slope, and extrapolate: total = f1 + slope*(R-1).
    The attention kv-chunk scan is fully unrolled for these builds
    (UNROLL_ATTN_SCAN) and the m/sLSTM sequence scans get an analytic
    correction. Memory analysis / lowering proof come from the separate
    full-depth compile (dryrun_one).
    """
    from repro.models import attention as attn_mod
    from repro.models import model as model_mod
    import dataclasses as _dc
    t0 = time.time()
    lp = lowering_plan(arch, shape_name)
    if force_fsdp is not None:
        lp = _dc.replace(lp, fsdp=force_fsdp)
    rec: Dict = {"arch": arch, "shape": shape_name, "mode": lp.mode,
                 "variant": lp.variant, "policy": policy_name,
                 "fsdp": lp.fsdp}
    if lp.skip:
        rec.update(status="skip", skip_reason=lp.skip)
        return rec
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=False)
    pol = policy if policy is not None else _policy(policy_name)
    micro = n_micro if n_micro is not None else lp.n_micro
    shp = INPUT_SHAPES[shape_name]

    attn_mod.UNROLL_ATTN_SCAN = True
    model_mod.UNROLL_LAYER_SCAN = True
    try:
        f1 = _measure(_depth_reduced(cfg, 1), shape_name, lp, pol, mesh,
                      micro)
        f2 = _measure(_depth_reduced(cfg, 2), shape_name, lp, pol, mesh,
                      micro)
    finally:
        attn_mod.UNROLL_ATTN_SCAN = False
        model_mod.UNROLL_LAYER_SCAN = False

    r = cfg.pattern_repeats
    plan = make_plan(cfg, tp=16, fsdp=lp.fsdp)
    out = {}
    for key in ("flops", "bytes", "coll"):
        slope = f2[key] - f1[key]
        out[key] = f1[key] + slope * (r - 1)
        out[key + "_per_layer"] = slope
    out["flops"] += _lstm_seq_flops(cfg, plan, shp, lp.mode)

    coll_kinds = {}
    for k in set(f1["coll_by_kind"]) | set(f2["coll_by_kind"]):
        a, b = f1["coll_by_kind"].get(k, 0), f2["coll_by_kind"].get(k, 0)
        coll_kinds[k] = a + (b - a) * (r - 1)

    n_dev = 256
    mem_est = _fused_memory_estimate(cfg, plan, shp, lp.mode,
                                     lp.cache_len)
    rec.update({
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": out["flops"],
        "bytes_per_device_hlo": out["bytes"],
        "bytes_per_device_fused_est": mem_est,
        "collective_bytes_per_device": out["coll"],
        "collectives": coll_kinds,
        "t_compute": out["flops"] / PEAK_FLOPS,
        "t_memory": mem_est / HBM_BW,
        "t_memory_hlo": out["bytes"] / HBM_BW,
        "t_collective": out["coll"] / ICI_BW,
    })
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
             "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    tokens = shp.global_batch * (1 if lp.mode == "decode" else shp.seq_len)
    mf = (6 if lp.mode == "train" else 2) * cfg.active_param_count() * tokens
    rec["model_flops"] = mf
    rec["model_flops_ratio"] = mf / (out["flops"] * n_dev)         if out["flops"] else None
    if verbose:
        print(json.dumps(rec, indent=2, default=str))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="slope-corrected roofline instead of the full-"
                         "depth lowering proof")
    ap.add_argument("--policy", default="paper",
                    choices=["paper", "bf16", "optimized", "aggressive"])
    ap.add_argument("--framed-bridge", type=int, default=None,
                    metavar="BITS",
                    help="override the cross-pod gradient hop with a "
                         "framed bridge config at BITS (mixed-tier "
                         "widths; pair with --multi-pod)")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful baseline layout: ZeRO fsdp=16 "
                         "everywhere (no serving weight-residency opt)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    results = []
    if args.all:
        pairs = list(all_pairs())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]
    for arch, shape in pairs:
        try:
            if args.roofline:
                rec = analyse_roofline(arch, shape,
                                       policy_name=args.policy,
                                       force_fsdp=16 if args.baseline
                                       else None,
                                       verbose=not args.all)
            else:
                rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                                 policy_name=args.policy,
                                 framed_bridge=args.framed_bridge,
                                 verbose=not args.all)
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        results.append(rec)
        status = rec.get("status")
        print(f"[dryrun] {arch:28s} {shape:12s} {status}"
              + (f" bottleneck={rec.get('bottleneck')}"
                 if status == "ok" else ""), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1, default=str)
    bad = [r for r in results if r.get("status") == "error"]
    print(f"[dryrun] done: {len(results)} pairs, {len(bad)} errors")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
