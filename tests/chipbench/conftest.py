import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def rehearse(workload, *args, root=None, timeout=600):
    """Run ``rehearse.py`` in a process of its own (the CPU with virtual
    devices); returns (return code, result line or None, output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, str(HERE / "rehearse.py"), workload, *args]
    if root is not None:
        cmd += ["--root", str(root)]
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    res = None
    if lines and lines[-1].startswith("{"):
        res = json.loads(lines[-1])
    return p.returncode, res, p.stdout + p.stderr


@pytest.fixture
def run_rehearsal():
    return rehearse
