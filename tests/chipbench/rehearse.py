"""Drive one cell of the on-chip benchmark on the CPU at smoke size.

A copy of the benchmark's files goes into a temporary root, with each
configuration cut to the program's smoke sizes and each traffic mix to
a batch and a window a CPU can serve in about a second. Beside every
one-chip cell the copy gains the same cell on a tensor-parallel mesh of
four devices, named ``<config>-tp4.<traffic>``, so that the path across
chips is rehearsed too. The program is linked from the repository.
``run`` then drives the cell as a chip run would, past the harness's look
for a chip.

    python tests/chipbench/rehearse.py <workload> [--devices 4] [--fault F]

prints the result line. ``--fault`` breaks the timed path underneath:
``token`` alters each served token where it is produced, ``exchange``
leaves the exchange between chips out of every all-reduce, ``half``
serves the second half of a decode batch the first half's tokens,
``state`` returns the decode cache unchanged from every step, and
``control`` judges the reference in float8 in the program's place.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))

SMOKE_TRAFFIC = {
    "batch_decode": {"batch": 4, "context": 16, "cache_len": 256,
                     "trace_seconds": 1},
}

# Arguments for a four-device run: every rank holds whole kv heads, as the
# published model's 8 kv heads do at TP=4 (the smoke configuration's 2
# would be shared by two ranks each).
TP4 = ["--devices", "4", "--widths", json.dumps({"n_kv_heads": 4})]


def smoke_config(arch: str, widths: dict):
    from repro.configs import get_smoke_config
    return dataclasses.replace(get_smoke_config(arch), **widths)


def smoke_root(dest: Path, widths: Optional[dict] = None,
               traffic: Optional[dict] = None) -> Path:
    """The benchmark's files under ``dest``, cut to smoke size (left as
    they are where ``dest`` holds them already). ``widths`` replaces
    sizes of the program's smoke configuration; ``traffic`` sets
    parameters of every traffic mix."""
    widths = widths or {}
    if (dest / "BENCHMARK.json").exists():
        return dest
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    (dest / "src").symlink_to(REPO / "src")
    for path in (dest / "chipbench" / "configs").glob("*.json"):
        conf = json.loads(path.read_text())
        cfg = smoke_config(conf["program"]["arch"], widths)
        conf["as_run"].update(
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
            vocab=cfg.vocab, n_layers=cfg.pattern_repeats,
            rope_theta=cfg.rope_theta, rotary_dim=cfg.hd)
        path.write_text(json.dumps(conf))
    for path in (dest / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t.update(SMOKE_TRAFFIC[t["kind"]])
        t.update((traffic or {}).get(t["kind"], {}))
        path.write_text(json.dumps(t))
    add_tp4_cells(dest)
    return dest


def add_tp4_cells(root: Path) -> None:
    """Each one-chip cell again on a mesh of four devices (model 4), as
    new files and entries: ``<config>-tp4.<traffic>``."""
    cb = root / "chipbench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    confs = {c["name"]: c for c in spec["configs"]}
    tp4 = {}
    for w in [w for w in spec["workloads"] if w["chips"] == 1]:
        c = dict(confs[w["config"]], name=w["config"] + "-tp4",
                 file=f"chipbench/configs/{w['config']}-tp4.json")
        conf = json.loads((root / confs[w["config"]]["file"]).read_text())
        conf["program"]["mesh"] = [1, 4]
        (root / c["file"]).write_text(json.dumps(conf))
        name = tp4[w["name"]] = f"{c['name']}.{w['traffic']}"
        shutil.copy(cb / "limits" / f"{w['name']}.json",
                    cb / "limits" / f"{name}.json")
        if c["name"] not in confs:
            spec["configs"].append(c)
        spec["workloads"].append(dict(w, name=name, config=c["name"],
                                      chips=4))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [tp4[w] for w in m["workloads"] if w in tp4]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def break_path(fault: str) -> None:
    """Break the timed path underneath the harness."""
    import jax
    import jax.numpy as jnp
    from chipbench.generators import batch_decode
    if fault == "token":
        orig = batch_decode.Loop.__init__

        def init(self, *a, **k):
            orig(self, *a, **k)
            step = self.step
            self.step = lambda s, c, b: (
                lambda o: ((o[0] + 1) % self.vocab, o[1]))(step(s, c, b))
        batch_decode.Loop.__init__ = init
    elif fault == "exchange":
        from repro.models import attention, layers
        layers.tp_psum = attention.tp_psum = lambda x, *a, **k: x
    elif fault == "half":
        orig = batch_decode.Loop.__init__

        def init(self, *a, **k):
            orig(self, *a, **k)
            step = self.step

            def half(s, c, b):
                out, c = step(s, c, b)
                h = out.shape[0] // 2
                return jnp.concatenate([out[:h], out[:out.shape[0] - h]]), c
            self.step = half
        batch_decode.Loop.__init__ = init
    elif fault == "state":
        orig = batch_decode.Loop.__init__

        def init(self, *a, **k):
            orig(self, *a, **k)
            step = self.step

            def unchanged(s, c, b):
                keep = jax.tree.map(jnp.copy, c)     # c is donated
                return step(s, c, b)[0], keep
            self.step = unchanged
        batch_decode.Loop.__init__ = init
    else:
        assert fault in ("none", "control"), fault


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 12345)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default="none")
    ap.add_argument("--root", default=None)
    ap.add_argument("--widths", default="{}",
                    help="JSON: smoke configuration sizes to replace")
    ap.add_argument("--traffic", default="{}",
                    help="JSON: {generator kind: {parameter: value}}")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{args.devices}")
    t0 = time.perf_counter()
    import tempfile
    tmp = Path(args.root) if args.root else Path(tempfile.mkdtemp())
    root = smoke_root(tmp, json.loads(args.widths),
                      json.loads(args.traffic))
    sys.path.insert(0, str(REPO))
    import jax
    import repro.configs
    from chipbench import harness
    # past the look for a chip, and the program's configurations at the
    # widths the copied configuration files state
    peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
    harness.find_devices = lambda chips, root: (
        jax.devices(), peaks["devices"]["TPU v5 lite"])
    widths = json.loads(args.widths)
    repro.configs.get_config = lambda arch: smoke_config(arch, widths)
    break_path(args.fault)
    cell = harness.load_cell(root, args.workload)
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace), t0,
                      control="fp8" if args.fault == "control" else None)
    harness.emit(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
