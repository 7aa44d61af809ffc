"""Smoke run of the main path on a TPU: does the system start on the chip?

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one four-chip host (v5e 2x2)

One chip (the default), in this one process:

* serving: Qwen3-14B at its published widths with depth cut to 2 of its
  40 layers, through the serving launcher's own ``run`` (batch 4, prompt
  512, 16 new tokens, ``paper`` policy, pallas codec). The launcher
  asserts that prefill and the decode loop agree on the first token.
* codec: (2048, 5120) activations at int8 g128, int4 g32, int3 g32 (a
  bit-split width) and int2 g32 with spike reserving, encoded and
  decoded by the compiled pallas kernels and by the jnp reference; the
  wire bytes and the decoded values must be identical.

Four chips (``--chips 4``), only what exists across chips:

* serving at TP=4 (mesh ``1,4``), the same widths with 8 of 40 layers,
  under the exact ``bf16`` policy (plain ``psum``), ``paper`` +
  ``two_step`` and ``paper`` + ``fused`` (the RDMA kernels). ``fused``
  and ``two_step`` must give identical tokens and logits; each quantized
  policy's largest logit difference from the exact one is printed.
* training: 3 steps through the training launcher's own ``run`` at mesh
  ``2,2`` (1 of 40 layers, batch 4, sequence 256) under ``bf16`` and
  ``paper``; every loss must be finite.

Weights and data are random, from fixed seeds. The script exits non-zero
and prints no result when JAX finds no TPU. Its last line of output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-14b"


def say(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


class CompileClock:
    """Seconds spent in backend compiles (persistent-cache reads
    included) and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.secs, self.hits

    def since(self, mark) -> str:
        return (f"compile {self.secs - mark[0]:.1f} s, "
                f"persistent-cache hits {self.hits - mark[1]}")


def custom_kernels(hlo: str) -> Counter:
    """Names of the Pallas TPU kernels (``tpu_custom_call``) in HLO."""
    names = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    return Counter(names)


def cut(cfg_pub, repeats: int):
    say("cut:", json.dumps({"key": "pattern_repeats",
                            "published": cfg_pub.pattern_repeats,
                            "used": repeats}))
    cfg = dataclasses.replace(cfg_pub, pattern_repeats=repeats)
    say(f"widths as published: d_model {cfg.d_model}, heads {cfg.n_heads} "
        f"q / {cfg.n_kv_heads} kv, head_dim {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}; {cfg.n_layers} of "
        f"{cfg_pub.n_layers} layers, {cfg.param_count() / 1e9:.3f} B "
        f"params")
    return cfg


def peak_bytes(jax) -> None:
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        say(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serving_one_chip(jax, clock) -> None:
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.policy import paper_policy, with_backend
    from repro.launch import serve
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.train.serve_step import make_prefill

    say("== serving, one chip ==")
    cfg = cut(get_config(ARCH), 2)
    args = serve.parse_args([
        "--arch", ARCH, "--batch", "4", "--prompt-len", "512",
        "--gen", "16", "--policy", "paper", "--codec-backend", "pallas"])
    mark = clock.mark()
    res = serve.run(cfg, args)
    say(f"serving: {clock.since(mark)}; prefill first call "
        f"{res['prefill_first_call_s']:.1f} s, decode first step "
        f"{res['decode_first_step_s']:.1f} s (host clock, compile "
        f"included)")
    peak_bytes(jax)

    # does the TP all-reduce site run the codec on a 1x1 mesh? Compile
    # the launcher's prefill for the same shapes and look for kernels.
    mesh = make_test_mesh(1, 1)
    plan = make_plan(cfg, tp=1, fsdp=1)
    store = {g: {n: jax.ShapeDtypeStruct((k, 1, sp.flat_len(plan)),
                                         jnp.float32)
                 for n, sp in specs.items()}
             for g, (k, specs) in param_groups(cfg, plan).items()}
    toks = jax.ShapeDtypeStruct((4, 512), jnp.int32)
    policy = with_backend(paper_policy(), "pallas")
    mark = clock.mark()
    hlo = make_prefill(cfg, plan, policy, mesh, 4).lower(
        store, {"tokens": toks}).compile().as_text()
    kernels = custom_kernels(hlo)
    say(f"TP site on a 1x1 mesh: compiled prefill holds Pallas kernels "
        f"{dict(kernels)} ({clock.since(mark)}) -> the codec "
        f"{'runs' if kernels else 'does not run'} with no peer to "
        f"exchange with")


def codec_real_sizes(jax, clock) -> None:
    import jax.numpy as jnp

    from repro.core import codec
    from repro.core.comm_config import CommConfig

    say("== codec, (2048, 5120) activations ==")
    rng = np.random.default_rng(20251017)
    x = rng.standard_normal((2048, 5120)).astype(np.float32)
    x[rng.integers(0, 2048, 64), rng.integers(0, 5120, 64)] *= 40.0
    x = jnp.asarray(x)
    for bits, group, spike in [(8, 128, False), (4, 32, False),
                               (3, 32, False), (2, 32, True)]:
        base = CommConfig(bits=bits, group=group, spike=spike)
        out = {}
        for backend in ("pallas", "ref"):
            cfg = base.with_backend(backend)
            mark = clock.mark()
            enc = jax.jit(lambda v, cfg=cfg: codec.encode(v, cfg))
            dec = jax.jit(lambda w, cfg=cfg: codec.decode(w, cfg, 5120))
            enc_c = enc.lower(x).compile()
            wire = enc_c(x)
            dec_c = dec.lower(wire).compile()
            y = dec_c(wire)
            kern = (custom_kernels(enc_c.as_text())
                    + custom_kernels(dec_c.as_text()))
            out[backend] = (np.asarray(wire), np.asarray(y), kern,
                            clock.since(mark))
        pw, py, pk, pt = out["pallas"]
        rw, ry, rk, rt = out["ref"]
        tag = f"int{bits} g{group}{' spike' if spike else ''}"
        assert pk.get("wire_encode") and pk.get("wire_decode"), (tag, pk)
        assert not rk, (tag, rk)
        same_wire = np.array_equal(pw, rw)
        same_out = np.array_equal(py, ry)
        err = float(np.max(np.abs(py - np.asarray(x))))
        say(f"{tag}: wire {pw.shape[1]} B/row; pallas kernels {dict(pk)} "
            f"({pt}); ref kernels {dict(rk)} ({rt}); wires byte-identical "
            f"{same_wire}; decoded values identical {same_out}; "
            f"max |decode - x| {err:.4g}")
        if not same_wire:
            lay = base.wire_layout(5120)
            cols = np.nonzero((pw != rw).any(axis=0))[0]
            spans = {n: s for n, s in [("plane%d" % i, sp) for i, (_, sp)
                                        in enumerate(lay.planes)]
                     + [("scale", lay.scale), ("zero", lay.zero),
                        ("spike_vals", lay.spike_vals),
                        ("spike_idx", lay.spike_idx)] if s is not None}
            where = Counter(n for c in cols for n, sp in spans.items()
                            if sp.offset <= c < sp.end)
            say(f"{tag}: {int((pw != rw).sum())} bytes differ, by section "
                f"{dict(where)}")
        if not same_out:
            say(f"{tag}: {int((py != ry).sum())} decoded values differ, max "
                f"|pallas - ref| {float(np.max(np.abs(py - ry))):.4g}")
        assert same_wire, f"{tag}: pallas and ref wires differ"
        assert same_out, f"{tag}: pallas and ref decodes differ"


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def serving_tp4(jax, clock) -> None:
    import jax.numpy as jnp

    from repro.analysis.commcheck import CommCheckError, check_fused_request
    from repro.configs import get_config
    from repro.core.policy import (BF16_POLICY, paper_policy, with_backend,
                                   with_scheme)
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import build_store
    from repro.train.data import DataConfig, make_dataset, to_device
    from repro.train.serve_step import (make_cache_init, make_decode_step,
                                        make_prefill)

    say("== serving, TP=4 (mesh 1,4) ==")
    cfg = cut(get_config(ARCH), 8)
    batch, seq, steps = 4, 512, 32
    mesh = make_test_mesh(1, 4)
    plan = make_plan(cfg, tp=4, fsdp=1)
    store = build_store(param_groups(cfg, plan), plan,
                        jax.random.PRNGKey(0), jnp.float32, mesh)
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                 global_batch=batch, d_model=cfg.d_model))
    prompts = to_device(ds.batch(0))["tokens"]
    paper = with_backend(paper_policy(), "pallas")
    policies = {"bf16": BF16_POLICY,
                "paper+two_step": with_scheme(paper, "two_step"),
                "paper+fused": with_scheme(paper, "fused")}
    mesh_shape = {"data": 1, "model": 4}
    prefill, decode = {}, {}
    for name, pol in policies.items():
        try:
            check_fused_request(cfg, plan, pol, mesh_shape,
                                global_batch=batch, seq=seq,
                                mode="prefill", tpu=True, context=name)
            run_prefill = True
        except CommCheckError as e:
            say(f"{name}: commcheck refuses it at prefill size "
                f"({str(e).splitlines()[0]}); it runs at decode only")
            run_prefill = False
        check_fused_request(cfg, plan, pol, mesh_shape, global_batch=batch,
                            seq=1, mode="decode", tpu=True, context=name)
        if run_prefill:
            mark = clock.mark()
            fn = make_prefill(cfg, plan, pol, mesh, batch, logits=True)
            fn = fn.lower(store, {"tokens": prompts}).compile()
            tok, lg = fn(store, {"tokens": prompts})
            prefill[name] = (np.asarray(tok), np.asarray(lg))
            say(f"{name}: prefill kernels "
                f"{dict(custom_kernels(fn.as_text()))} ({clock.since(mark)})")
        # decode: the first `steps` prompt tokens, teacher-forced, so
        # every policy sees the same inputs at every step
        mark = clock.mark()
        init = make_cache_init(cfg, plan, mesh, batch, steps)
        step = make_decode_step(cfg, plan, pol, mesh, batch, steps,
                                logits=True)
        caches = init()
        db = {"tokens": prompts[:, :1]}
        step = step.lower(store, caches, db).compile()
        kernels = custom_kernels(step.as_text())
        toks, lgs = [], []
        for i in range(steps):
            (tok, lg), caches = step(store, caches,
                                     {"tokens": prompts[:, i:i + 1]})
            toks.append(np.asarray(tok))
            lgs.append(np.asarray(lg))
        decode[name] = (np.stack(toks, 1), np.stack(lgs, 1), kernels)
        say(f"{name}: {steps} decode steps; kernels {dict(kernels)} "
            f"({clock.since(mark)})")
    peak_bytes(jax)

    fused_k = decode["paper+fused"][2]
    assert fused_k.get("rdma_allreduce_scatter") and \
        fused_k.get("rdma_allreduce_gather"), fused_k
    assert not any(k.startswith("rdma") for k in decode["paper+two_step"][2])
    say("the fused decode step runs the RDMA all-reduce kernels "
        "(rdma_allreduce_scatter / rdma_allreduce_gather in its HLO)")

    def compare(a, b, what):
        same_tok = np.array_equal(a[0], b[0])
        same_lg = np.array_equal(a[1], b[1])
        d = np.abs(a[1] - b[1])
        finite = np.isfinite(d)
        say(f"{what}: tokens identical {same_tok}, logits identical "
            f"{same_lg}, max |dlogit| {float(np.max(d[finite])):.6g}")
        return same_tok and same_lg

    for name in ("paper+two_step", "paper+fused"):
        compare(decode[name], decode["bf16"], f"decode {name} vs bf16")
        if name in prefill:
            compare(prefill[name], prefill["bf16"],
                    f"prefill {name} vs bf16")
    assert compare(decode["paper+fused"], decode["paper+two_step"],
                   "decode fused vs two_step"), \
        "fused and two_step decode differ"
    if "paper+fused" in prefill:
        assert compare(prefill["paper+fused"], prefill["paper+two_step"],
                       "prefill fused vs two_step"), \
            "fused and two_step prefill differ"


def training_2x2(jax, clock) -> None:
    from repro.configs import get_config
    from repro.launch import train

    say("== training, mesh 2,2 ==")
    cfg = cut(get_config(ARCH), 1)
    for pol in ("bf16", "paper"):
        args = train.parse_args([
            "--arch", ARCH, "--steps", "3", "--seq", "256", "--batch", "4",
            "--mesh", "2,2", "--policy", pol, "--codec-backend", "pallas",
            "--log-every", "1"])
        mark = clock.mark()
        _, _, history = train.run(cfg, args)
        losses = [h["loss"] for h in history]
        say(f"train {pol}: losses {losses} ({clock.since(mark)})")
        assert len(losses) == 3 and all(np.isfinite(losses)), losses
    peak_bytes(jax)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache  # needs the repo
    say(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}")
    say(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    t0 = time.time()
    if args.chips == 1:
        serving_one_chip(jax, clock)
        codec_real_sizes(jax, clock)
    else:
        serving_tp4(jax, clock)
        training_2x2(jax, clock)
    say(f"total: {time.time() - t0:.1f} s host clock, compile "
        f"{clock.secs:.1f} s, persistent-cache hits {clock.hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
