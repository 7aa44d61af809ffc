"""Collective-level benchmark: the full collective schedules, not just
the codec.

bench_kernels times encode/decode in isolation; this bench times the
whole quantized AllReduce — chunk + QDQ + hop + reduce + hop — for every
scheme (uncompressed ``nccl`` psum baseline, XLA ``two_step``, the fused
Pallas ``fused`` path, and the ``hierarchical`` variants), the
error-feedback grad sync (``grad_ef``), the ZeRO-sharded quantized
gradient reduce-scatter (``qgrad`` at 4/2 bit, plus the
``qgrad_rot``-vs-``qgrad``@2 rotated-vs-spike A/B) AND the MoE
dispatch All2All (``a2a_nccl`` exact baseline, ``a2a_two_step`` codec
around ``lax.all_to_all``, ``a2a_fused`` single-kernel path) on 8 fake
CPU devices, plus the exact per-rank wire footprint each scheme puts on
the link. CPU wall times are schedule-overhead proxies (no real ICI),
but they make scheme regressions visible and give the fused paths a
tracked number; rows land in benchmarks/results/collectives.json like
every other bench.

XLA pins the device count at first jax init, so the measurement runs in
a subprocess with ``--xla_force_host_platform_device_count=8`` (same
pattern as tests/test_distributed.py).

Per (size) batch, every scheme is measured ROUND-ROBIN (interleaved
reps, best-of per scheme) so scheme-vs-scheme comparisons share the
same ambient load — this container's two cores are shared and medians
of back-to-back blocks drift by 2x otherwise.

``--check`` compares a fresh run against the committed
``results/collectives.json`` and exits non-zero on >25% regressions
(with an absolute floor so sub-millisecond rows don't trip on
scheduler jitter); the CI smoke-bench lane runs exactly this.
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

SIZES = (1 << 16, 1 << 18)
FAST_SIZES = (1 << 14,)
BITS = (8, 4)


def _worker(fast: bool):
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import (compressed_psum, compressed_psum_ef,
                            default_comm_config, dispatch_all_to_all)
    from repro.core.collectives import quantized_reduce_scatter_ef
    from repro.launch.mesh import make_test_mesh

    rows = []
    sizes = FAST_SIZES if fast else SIZES
    mesh = make_test_mesh(data=1, model=4, pod=2)
    dev = 8
    a2a_tp = 4                                # the "model" axis size
    reps, warm = 11, 3

    def interleaved(cases):
        """Measure a batch of (label, fn, x) ROUND-ROBIN: every rep of
        every scheme sees the same ambient load, so scheme-vs-scheme
        comparisons don't depend on when in the run the machine was
        busy. Best-of-reps per scheme (see benchmarks.common.timeit)."""
        for _, fn, x in cases:
            for _ in range(warm):
                fn(x).block_until_ready()
        ts = {label: [] for label, _, _ in cases}
        for _ in range(reps):
            for label, fn, x in cases:
                t0 = time.perf_counter()
                fn(x).block_until_ready()
                ts[label].append((time.perf_counter() - t0) * 1e6)
        return {label: float(np.min(v)) for label, v in ts.items()}

    def ar_case(cfg, axes, n, outer_cfg=None):
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("pod", "data", "model")),
                           out_specs=P(("pod", "data", "model")),
                           check_vma=False)
        def f(xs):
            return compressed_psum(xs[0], axes, cfg,
                                   None, None, outer_cfg)[None]

        x = jax.random.normal(jax.random.PRNGKey(0), (dev, n), jnp.float32)
        return jax.jit(f), x

    def ef_case(cfg, n):
        # error-feedback grad AR over the single pod axis (the
        # train_step cross-pod sync path: two-step + residual
        # re-injection + both-stage error capture) — the rows track EF
        # overhead vs the plain compressed psum at 2/4 bit
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(("pod", "data", "model")),) * 2,
                           out_specs=P(("pod", "data", "model")),
                           check_vma=False)
        def f(xs, es):
            out, res = compressed_psum_ef(xs[0], es[0], ("pod",), cfg)
            return jnp.stack([out, res])[None]

        x = jax.random.normal(jax.random.PRNGKey(2), (dev, n), jnp.float32)
        e = jnp.zeros_like(x)
        return jax.jit(lambda v: f(v, e)), x

    def qgrad_case(cfg, n):
        # ZeRO-sharded gradient sync (the explicit post-VJP qgrad_rs
        # pass in train_step): quantized+EF reduce-scatter over the
        # 4-wide model axis standing in for the fsdp axis — rows track
        # the qgrad wire cost and the rotated-vs-spike A/B at 2 bits
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(("pod", "data", "model")),) * 2,
                           out_specs=P(("pod", "data", "model")),
                           check_vma=False)
        def f(xs, es):
            out, res = quantized_reduce_scatter_ef(xs[0], es[0],
                                                   "model", cfg)
            # out is the 1/tp shard, res the full-length residual;
            # concatenate so both stages are materialized in the timing
            return jnp.concatenate([out, res])[None]

        x = jax.random.normal(jax.random.PRNGKey(3), (dev, n), jnp.float32)
        e = jnp.zeros_like(x)
        return jax.jit(lambda v: f(v, e)), x

    def a2a_case(cfg, n):
        # MoE-dispatch shape: tp per-peer blocks of n/tp values, d=512
        d = 512
        m = n // (a2a_tp * d)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(("pod", "data", "model")),
                           out_specs=P(("pod", "data", "model")),
                           check_vma=False)
        def f(xs):
            return dispatch_all_to_all(xs[0], "model", cfg)[None]

        x = jax.random.normal(jax.random.PRNGKey(1),
                              (dev, a2a_tp, m, d), jnp.float32)
        return jax.jit(f), x

    for n in sizes:
        d = 512
        cases, meta = [], {}

        def add(label, bits, cfg, fn, x, wire):
            cases.append((label, fn, x))
            meta[label] = (bits, wire)

        cfg = default_comm_config(8, scheme="nccl")
        add("nccl", 32, cfg, *ar_case(cfg, ("model", "pod"), n), 4 * n)
        for bits in BITS:
            for scheme in ("two_step", "fused", "hierarchical", "hier_pp"):
                cfg = default_comm_config(bits, scheme=scheme)
                add(f"{scheme}@{bits}", bits, cfg,
                    *ar_case(cfg, ("model", "pod"), n), cfg.wire_bytes(n))
        # framed pod bridge (core/frame.py): hier_pp with the pod hop
        # carrying the self-describing header + CRC32C — read against
        # the raw hier_pp@bits rows above for the framing overhead
        for bits in BITS:
            cfg = default_comm_config(bits, scheme="hier_pp")
            add(f"hier_pp_framed@{bits}", bits, cfg,
                *ar_case(cfg, ("model", "pod"), n, cfg.with_framed()),
                cfg.wire_bytes(n))
        for bits in (4, 2):   # EF gradient sync: the sub-4-bit regime
            cfg = default_comm_config(bits)
            add(f"grad_ef@{bits}", bits, cfg, *ef_case(cfg, n),
                cfg.wire_bytes(n))
        for bits in (4, 2):   # ZeRO qgrad reduce-scatter (post-VJP pass)
            cfg = default_comm_config(bits)
            add(f"qgrad@{bits}", bits, cfg, *qgrad_case(cfg, n),
                cfg.wire_bytes(n))
        # rotated-vs-spike A/B at the 2-bit qgrad site: same transport,
        # Hadamard-rotated quantizer instead of spike reserving — pair
        # with qgrad@2 above (spike) to read the A/B; note the shorter
        # wire (no spike sections)
        cfg = default_comm_config(2).with_rotation()
        add("qgrad_rot@2", 2, cfg, *qgrad_case(cfg, n),
            cfg.wire_bytes(n))
        cfg = default_comm_config(8, scheme="nccl")
        add("a2a_nccl", 32, cfg, *a2a_case(cfg, n), 4 * n)
        for bits in BITS:
            for scheme in ("two_step", "fused"):
                cfg = default_comm_config(bits, scheme=scheme)
                add(f"a2a_{scheme}@{bits}", bits, cfg, *a2a_case(cfg, n),
                    a2a_tp * (n // (a2a_tp * d)) * cfg.wire_bytes(d))

        us = interleaved(cases)
        for label, (bits, wire) in meta.items():
            rows.append({"scheme": label.split("@")[0], "bits": bits,
                         "n": n, "wire_bytes_per_rank": wire,
                         "value": round(us[label], 1), "unit": "us"})
    print(json.dumps(rows))


def run(fast: bool = False):
    env = dict(os.environ)
    # 8 host-platform devices on the CPU: the child must not reach for a
    # TPU, which the parent (it has imported jax) may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
    if fast:
        cmd.append("--fast")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=900, cwd=root)
    if r.returncode != 0:
        raise RuntimeError(
            f"collectives worker failed:\n{r.stdout[-2000:]}\n"
            f"{r.stderr[-3000:]}")
    # last stdout line is the JSON row dump
    return json.loads(r.stdout.strip().splitlines()[-1])


def _merged_with_committed(rows):
    """Fresh rows merged over the committed baseline by (scheme, bits,
    n), so saving a run at different sizes never drops the baseline keys
    the CI regression guard checks against."""
    merged = {}
    if os.path.exists(COMMITTED):
        try:
            with open(COMMITTED) as f:
                merged = {_row_key(r): r for r in json.load(f)}
        except (ValueError, KeyError):
            merged = {}
    for r in rows:
        merged[_row_key(r)] = r
    return list(merged.values())


def bench_collectives(fast: bool = False):
    """run.py entry point (its generic save() writes what we return)."""
    return _merged_with_committed(run(fast))


# ---------------------------------------------------------------------------
# regression guard: fresh numbers vs the committed results
# ---------------------------------------------------------------------------

# >25% slower than the committed number fails the check. CPU wall noise
# on shared cores is real, so an absolute floor keeps sub-millisecond
# rows from tripping the guard on scheduler jitter alone.
CHECK_TOL = 0.25
CHECK_ABS_FLOOR_US = 1500.0

COMMITTED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "results", "collectives.json")


def _row_key(r):
    return (r["scheme"], r["bits"], r["n"])


def check_regressions(fresh, committed_path: str = COMMITTED,
                      tol: float = CHECK_TOL):
    """Compare fresh rows to the committed baseline; return regressions.

    Rows are matched on (scheme, bits, n); a fresh row regresses when it
    is more than ``tol`` slower than the committed value AND the excess
    clears the absolute noise floor. New rows never fail — but if NO
    fresh row matches any committed key the guard has rotted (e.g. the
    baseline file was regenerated with disjoint sizes) and we raise
    instead of waving a vacuous green flag.
    """
    with open(committed_path) as f:
        committed = {_row_key(r): r["value"] for r in json.load(f)}
    regressions = []
    matched = 0
    for r in fresh:
        old = committed.get(_row_key(r))
        if old is None:
            continue
        matched += 1
        new = r["value"]
        if new > old * (1 + tol) and new - old > CHECK_ABS_FLOOR_US:
            regressions.append((_row_key(r), old, new))
    if fresh and matched == 0:
        raise RuntimeError(
            f"bench guard matched 0 of {len(fresh)} fresh rows against "
            f"{committed_path} — the baseline keys have rotted; "
            "regenerate the committed file at the checked sizes")
    return regressions




def main(argv):
    fast = "--fast" in argv
    rows = run(fast)
    from benchmarks.common import emit
    if "--check" in argv:
        regs = check_regressions(rows)
        for key, old, new in regs:
            print(f"REGRESSION {key}: {old} us -> {new} us "
                  f"(+{(new / old - 1) * 100:.0f}%)")
        if regs:
            return 1
        print(f"check ok: {len(rows)} rows within "
              f"{CHECK_TOL * 100:.0f}% of committed baselines")
    else:
        from benchmarks.common import save
        save("collectives", _merged_with_committed(rows))
    emit("collectives", rows)
    return 0


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker("--fast" in sys.argv)
    else:
        sys.exit(main(sys.argv[1:]))
