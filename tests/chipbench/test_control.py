"""The control comes out not correct: the plain reference with its linear
layers in float8, judged in the program's place against the cell's own
limit. On the CPU at a size a test run holds: the smoke widths with 8
layers, and a fixed number of served tokens
(the window ends when the cache is full)."""
import json

import pytest

DECODE = {"batch_decode": {"batch": 16, "context": 8, "cache_len": 72}}


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_fails_the_decode_limit(run_rehearsal, seed, tmp_path):
    rc, res, out = run_rehearsal(
        "qwen3-14b.decode-b32", "--fault", "control", "--seed", str(seed),
        "--seconds", "120", "--widths", json.dumps({"pattern_repeats": 8}),
        "--traffic", json.dumps(DECODE), root=tmp_path)
    assert rc == 0, out[-3000:]
    assert "filled after 64 steps" in out
    c = res["compared"]["max_gap"]
    assert res["correct"] is False and c["value"] > c["limit"], out[-2000:]


def test_program_passes_at_the_same_size(run_rehearsal, tmp_path):
    rc, res, out = run_rehearsal(
        "qwen3-14b.decode-b32", "--seconds", "120",
        "--widths", json.dumps({"pattern_repeats": 8}),
        "--traffic", json.dumps(DECODE), root=tmp_path)
    assert rc == 0, out[-3000:]
    assert res["correct"] is True, out[-2000:]
