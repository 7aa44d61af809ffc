"""Device time per decode step under the program's ``mlp`` scope
(``models/layers.mlp_apply``): w1, w3, the SiLU gate and w2. Own time: the
``tp_site`` nested in it is not counted. Own time of the step program's
operations, mean over devices, from the profiler trace
(chipbench/scopes.py)."""
from pathlib import Path

from chipbench import scopes

SCOPE = "mlp"
ROOT = Path(__file__).resolve().parents[2]


def read(m):
    return scopes.ms_per_step(m, ROOT, SCOPE)
