"""Device time per decode step under the program's ``attention`` scope
(``models/attention.self_attention``): q/k/v projections, qk-norm, RoPE, the
cache update, scores, softmax, context and output projection. Own time: the
``tp_site`` nested in it is not counted. Own time of the step program's
operations, mean over devices, from the profiler trace
(chipbench/scopes.py)."""
from pathlib import Path

from chipbench import scopes

SCOPE = "attention"
ROOT = Path(__file__).resolve().parents[2]


def read(m):
    return scopes.ms_per_step(m, ROOT, SCOPE)
