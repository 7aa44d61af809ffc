"""The benchmark harness: finds a cell's files by name, builds it, warms it
up, measures one window, checks what it served and prints one result.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``chipbench/configs/<config>.json``: the model as run and how the program
  is driven (architecture id, mesh), with the cut from the published
  configuration;
* ``chipbench/traffic/<traffic>.json``: its generator (``kind``, a module
  under ``chipbench/generators/``) and that generator's parameters;
* ``chipbench/metrics/<metric>.py``: a ``read(measures)`` that returns the
  per-layer metric, or None where the cell has nothing to read;
* ``chipbench/limits/<workload>.json``: the limit of each number compared
  with the reference, with the readings it was set from.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Optional

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ".jax_cache"            # under the checkout: fixed path
TRACE_DIR = ".chipbench/trace"


class NoChip(RuntimeError):
    """JAX finds no TPU, too few chips, or one the peaks table lacks."""


def say(*parts) -> None:
    print("[chipbench]", *parts, flush=True)


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    spec: Dict

    @property
    def as_run(self) -> Dict:
        return self.config["as_run"]

    def metrics(self, section: str):
        """This cell's entries of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.spec[section]
                if "workloads" not in m or self.name in m["workloads"]]


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=_json(root / conf["file"]),
                traffic=_json(root / "chipbench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=_json(root / "chipbench" / "limits"
                             / f"{workload}.json"),
                spec=spec)


def peaks_for(root: Path, kind: str) -> Dict:
    table = _json(root / "chipbench" / "peaks.json")
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table["devices"][kind]


def find_devices(chips: int, root: Path):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no accelerator: platform "
                     f"{devs[0].platform!r}, not 'tpu'")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs, peaks_for(root, devs[0].device_kind)


def read_metric(root: Path, name: str) -> Callable:
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Backend compiles (persistent-cache reads included), their seconds,
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@dataclasses.dataclass
class Measures:
    """What a per-layer metric's reader is given."""
    trace: object            # chipbench.trace.Reduced
    work: Dict               # the generator's counts for the traced window
    peaks: Dict
    chips: int


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at ``<root>/.jax_cache``, for
    every compile however small; the program finds the same directory in
    ``JAX_COMPILATION_CACHE_DIR``."""
    import jax
    path = str(root / CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)   # no eviction
    return path


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, control: Optional[str] = None,
        readings: Optional[Dict] = None) -> Dict:
    """One run of one cell. Returns the result line as a dict, with the
    compared numbers last.

    ``control`` (a precision of the configuration's reference, such as
    ``"fp8"``) puts that reference in the program's place for the check:
    its first tokens are judged instead of the served ones; ``readings``,
    where given, receives what the check read for the program and for
    ``control`` both."""
    import jax

    from chipbench import system, weights

    say("compile cache:", use_compile_cache(cell.root))
    devices, peaks = find_devices(cell.chips, cell.root)
    devices = devices[:cell.chips]
    clock = CompileClock()
    say("cut:", json.dumps({k: {"published": v,
                                "used": cell.config.get(k)}
                            for k, v in cell.config["published"].items()}))
    prog = system.Program(cell.root, cell.config)
    tp = prog.tp
    tab = weights.table(cell.as_run, tp)
    shapes = prog.store_shapes()
    weights.check_layout(tab, shapes, tp)
    store = weights.make_store(tab, shapes, tp, seed, prog.store_sharding())
    jax.block_until_ready(store)
    t_weights = time.perf_counter() - t_start
    gen = importlib.import_module(
        f"chipbench.generators.{cell.traffic['kind']}")
    loop = gen.Loop(prog, cell, seed, store)
    loop.setup()
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f} s (weights ready at {t_weights:.3f} s); "
        f"backend compiles {clock.n} ({clock.secs:.1f} s), persistent-cache "
        f"hits {clock.hits}")

    span = _spans(trace)
    n0 = clock.n
    window = min(seconds, float(cell.traffic.get("trace_seconds", seconds))) \
        if trace else seconds
    tdir = cell.root / TRACE_DIR
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    try:
        with span("chipbench.window"):
            win = loop.window(window, span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = clock.n - n0
    say(f"window {win['t1'] - win['t0']:.3f} s; compiles in window "
        f"{in_window}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    say(f"memory_peak_bytes {peak} (fullest of {len(devices)} devices)")

    e2e = loop.end_to_end(win)
    work = loop.work()
    loop.release()
    t_ref, n_ref = time.perf_counter(), clock.n
    found = loop.check(("float32",) + ((control,) if control else ()))
    say(f"reference check {time.perf_counter() - t_ref:.1f} s "
        f"({clock.n - n_ref} compiles): {found}")
    if readings is not None:
        readings.update(found)
    compared, correct = {}, True
    for name, lim in sorted(cell.limits["compare"].items()):
        value = found[control or "program"][name]
        compared[name] = {"value": value, "limit": lim["limit"]}
        correct = correct and value <= lim["limit"]
    correct = correct and win["attempted"] > 0 and win["failed"] == 0

    result: Dict = {"correct": bool(correct),
                    "attempted": int(win["attempted"]),
                    "failed": int(win["failed"])}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak)}
    units = {m["name"]: m["unit"] for m in
             cell.spec["end_to_end"] + cell.spec["per_layer"]}
    metrics: Dict = {}
    if not trace:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": units[m["name"]]}
    else:
        from chipbench import trace as tr
        pd = tr.load(tr.find_xplane(str(tdir)))
        red = tr.reduce(pd, spans=gen.SPANS)
        dev["busy_s"] = red.mean_busy_s
        dev["window_s"] = red.window_s
        seen = {k: red.op_count.get(k, 0.0) for k in work["codec_calls"]}
        say(f"codec calls in the trace {seen}, counted "
            f"{work['codec_calls']}")
        ms = Measures(trace=red, work=work, peaks=peaks, chips=len(devices))
        for m in cell.metrics("per_layer"):
            v = read_metric(cell.root, m["name"])(ms)
            if v is not None:
                metrics[m["name"]] = {"value": float(v),
                                      "unit": units[m["name"]]}
        result["breakdown"] = {
            "device_ops": red.top_ops(10),
            "idle_gaps": [[n, s] for n, s in red.idle_gaps[:10]]}
        shutil.rmtree(tdir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = dev
    result["compared"] = compared
    keep = ["correct", "attempted", "failed", "metrics", "device"]
    keep += ["breakdown"] if "breakdown" in result else []
    return {k: result[k] for k in keep + ["compared"]}


def _spans(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)


def emit(result: Dict) -> None:
    """The compared numbers as the last lines on standard error, and the
    result as the last line on standard output."""
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: Optional[list] = None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    try:
        cell = load_cell(ROOT, args.workload)
        res = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    emit(res)
    return 0
