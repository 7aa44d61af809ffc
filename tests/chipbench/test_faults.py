"""A run with the timed path broken underneath comes out not correct: the
harness drives the rest of the run on the CPU at smoke size, past its look
for a chip."""
import pytest
from rehearse import TP4

FAULTS = [
    ("qwen3-14b.decode-b32", 1, "token"),
    ("qwen3-14b.decode-b32", 1, "state"),
    ("qwen3-14b.decode-b32", 1, "half"),
    ("qwen3-14b-tp4.decode-b32", 4, "token"),
    ("qwen3-14b-tp4.decode-b32", 4, "exchange"),
]


@pytest.mark.parametrize("workload,devices,fault", FAULTS)
def test_broken_path_is_not_correct(run_rehearsal, workload, devices, fault,
                                    tmp_path):
    rc, res, out = run_rehearsal(workload, *(TP4 if devices == 4 else []),
                                 "--fault", fault, root=tmp_path)
    assert rc == 0, out[-3000:]
    assert res["correct"] is False, out[-3000:]
    c = res["compared"]["max_gap"]
    assert c["value"] > c["limit"]
