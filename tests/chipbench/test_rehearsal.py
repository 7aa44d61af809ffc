"""Each cell driven on the CPU at smoke size, as a chip run would drive it
past the look for a chip; and the command itself, which refuses the
CPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
from rehearse import TP4

from chipbench import harness, system

REPO = Path(__file__).resolve().parents[2]
CELLS = [("qwen3-14b.decode-b32", 1), ("qwen3-14b-tp4.decode-b32", 4)]


@pytest.mark.parametrize("workload,devices", CELLS)
def test_cell_runs_and_is_correct(run_rehearsal, workload, devices, tmp_path):
    rc, res, out = run_rehearsal(workload, *(TP4 if devices == 4 else []),
                                 root=tmp_path)
    assert rc == 0, out[-3000:]
    assert res["correct"] is True, out[-3000:]
    assert list(res)[-1] == "compared"
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == devices
    cell = harness.load_cell(tmp_path, workload)
    assert set(res["metrics"]) == {m["name"] for m in
                                   cell.metrics("end_to_end")}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert "compiles in window 0" in out


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "qwen3-14b.decode-b32", "--seed", "7", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_program_no_run(tmp_path):
    """A checkout that holds only the benchmark's files cannot run it."""
    with pytest.raises(ImportError):
        system.import_program(tmp_path)


def test_benchmark_files_are_found_by_name():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(REPO, w["name"])
        assert cell.chips == w["chips"]
        kind = cell.traffic["kind"]
        assert (REPO / "chipbench" / "generators" / f"{kind}.py").exists()
        assert cell.limits["compare"]
    for m in spec["per_layer"]:
        assert callable(harness.read_metric(REPO, m["name"]))
