"""A configuration, a traffic mix and a per-layer metric are added to a
copy of the benchmark as new files and entries, and the harness runs them
with no existing file edited."""
import hashlib
import json
from pathlib import Path

from chipbench import harness, trace
import rehearse

METRIC = '''"""Tokens served per decode step in the traced window."""


def read(m):
    if m.work["mode"] != "decode" or not m.work["steps"]:
        return None
    return m.work["tokens"] / m.work["steps"]
'''


def digest(root: Path):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*")) if p.is_file()}


def test_new_files_run(run_rehearsal, tmp_path):
    root = rehearse.smoke_root(tmp_path)
    before = digest(root)
    cb = root / "chipbench"
    conf = json.loads((cb / "configs" / "qwen3-14b.json").read_text())
    conf["as_run"]["n_layers"] = conf["num_hidden_layers"] = 1
    (cb / "configs" / "qwen3-14b-l1.json").write_text(json.dumps(conf))
    traffic = json.loads((cb / "traffic" / "decode-b32.json").read_text())
    traffic.update(batch=2, context=8)
    (cb / "traffic" / "decode-b2.json").write_text(json.dumps(traffic))
    (cb / "metrics" / "tokens_per_step.py").write_text(METRIC)
    cell = "qwen3-14b-l1.decode-b2"
    (cb / "limits" / f"{cell}.json").write_text(
        (cb / "limits" / "qwen3-14b.decode-b32.json").read_text())
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="qwen3-14b-l1",
                                file="chipbench/configs/qwen3-14b-l1.json"))
    spec["workloads"].append({"name": cell, "config": "qwen3-14b-l1",
                              "traffic": "decode-b2", "chips": 1,
                              "why": "one layer, two sequences"})
    spec["per_layer"].append({
        "name": "tokens_per_step", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "model step",
        "moves": "tokens_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    rc, res, out = run_rehearsal(cell, root=root)
    assert rc == 0, out[-3000:]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
    after = digest(root)
    assert {p: after[p] for p in before} == before

    loaded = harness.load_cell(root, cell)
    assert [m["name"] for m in loaded.metrics("per_layer")] == [
        "tokens_per_step"]
    red = trace.Reduced(window_s=1.0, devices=["d"], busy_s=[0.5], op_s={},
                        op_count={}, collective_s=[0.0], codec_s=[0.0],
                        tp_site_s=[0.0], idle_gaps=[])
    ms = harness.Measures(trace=red, work={"mode": "decode", "steps": 4,
                                           "tokens": 8},
                          peaks={}, chips=1)
    assert harness.read_metric(root, "tokens_per_step")(ms) == 2.0
    assert harness.read_metric(root, "device_idle")(ms) == 50.0
