"""Device time per decode step of the step program's operations under none
of the program's named scopes: those the compiler made with no ``op_name``
at the top level of the program, and any scope a change lost. Own time of
the step program's operations, mean over devices, from the profiler trace
(chipbench/scopes.py)."""
from pathlib import Path

from chipbench import scopes

SCOPE = "unscoped"
ROOT = Path(__file__).resolve().parents[2]


def read(m):
    return scopes.ms_per_step(m, ROOT, SCOPE)
