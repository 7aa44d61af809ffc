"""Device time per decode step under the program's ``weights`` scope
(``parallel/shardings.gather_group``): each parameter group's store view
cast to its logical shape and to bfloat16, embedding and output head
included. Own time of the step program's operations, mean over devices, from
the profiler trace (chipbench/scopes.py)."""
from pathlib import Path

from chipbench import scopes

SCOPE = "weights"
ROOT = Path(__file__).resolve().parents[2]


def read(m):
    return scopes.ms_per_step(m, ROOT, SCOPE)
