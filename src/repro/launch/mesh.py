"""Production mesh builders.

Functions (not module constants) so importing never touches jax device
state. The production topology is a TPU v5e pod of 16x16 = 256 chips;
multi-pod doubles it with a slow inter-pod axis:

    single pod : (data=16, model=16)          256 chips
    multi pod  : (pod=2, data=16, model=16)   512 chips
"""
from __future__ import annotations

import jax


def _make(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh for CPU tests (1x1 default; 2x4 under 8 fake devices)."""
    if pod:
        return _make((pod, data, model), ("pod", "data", "model"))
    return _make((data, model), ("data", "model"))
