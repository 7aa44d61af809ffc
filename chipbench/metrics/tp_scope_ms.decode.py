"""Device time per decode step under the program's ``tp_site`` scope
(``models/layers.tp_psum``): the whole tensor-parallel all-reduce site, the
codec's XLA glue (pad, float32 cast, reshape, slice, cast back) as well as
its kernels and any collective. Own time of the step program's operations,
mean over devices, from the profiler trace (chipbench/scopes.py)."""
from pathlib import Path

from chipbench import scopes

SCOPE = "tp_site"
ROOT = Path(__file__).resolve().parents[2]


def read(m):
    return scopes.ms_per_step(m, ROOT, SCOPE)
