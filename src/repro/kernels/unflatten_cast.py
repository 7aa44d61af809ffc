"""Unflatten a flat float32 store row into its logical array, and cast it,
in one pass over HBM.

The serving store keeps every parameter as ``(n_stack, 1, N)`` float32:
row ``[s, 0]`` holds layer ``s``'s tensor flattened and zero-padded (see
:mod:`repro.parallel.shardings`). The plain expression
``row[:n].reshape(shape).astype(bf16)`` compiles on the TPU to two passes
over HBM whatever its form: a cast that writes a padded 1-D array, then
a relayout into the 2-D tiling the matmul reads. This kernel reads the
row once and writes the ``(rows, cols)`` array once: 4 bytes read and 2
written per value.

Leading dimensions fold into rows (``rows = prod(shape[:-1])``); ``cols``
is a multiple of 128 lanes. Logical row ``r``, lanes ``[128 j, 128 j +
128)`` are values ``r * cols + 128 j + [0, 128)`` of the row, so in the
row viewed as ``(N / 128, 128)`` they are view row ``r * k + j`` with
``k = cols / 128``. A grid step copies the whole logical rows ``[tr i,
tr (i + 1))`` into VMEM as one contiguous block, views it as ``tr * k``
rows of 128 lanes, and writes column strip ``j`` from every ``k``-th
view row, starting at ``j``: a strided sublane load.

The layer is a scalar-prefetch index into the stack, so the kernel reads
the one layer straight from the stacked store inside a layer scan: no
per-layer slice is copied, and there is no cast of the whole stack for
the compiler to hoist out of the loop. The cast is ``astype``'s
(round to nearest even), so the result equals the plain expression bit
for bit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Bytes of float32 input one grid step reads; double-buffered input and
# bfloat16 output then take 3x this of VMEM.
_BLOCK_BYTES = 4 << 20
# Output rows come in multiples of the bfloat16 sublane tile.
_ROW_QUANTUM = 16
# Below one default block of values the plain expression's two passes
# cost about what a kernel launch does.
_MIN_VALUES = _BLOCK_BYTES // 4


def _aligned(shape) -> bool:
    """What the kernel needs: two or more dimensions, a last dimension of
    whole 128-lane vregs, and at least 16 rows."""
    return (len(shape) >= 2 and shape[-1] % LANES == 0
            and math.prod(shape[:-1]) >= _ROW_QUANTUM)


def fits(shape) -> bool:
    """Whether serving reads a tensor of logical ``shape`` through the
    kernel: it is aligned and holds at least ``_MIN_VALUES`` values."""
    return _aligned(shape) and math.prod(shape) >= _MIN_VALUES


def block_rows(rows: int, cols: int) -> int:
    """Logical rows a grid step takes: about ``_BLOCK_BYTES`` of float32,
    a multiple of 16, at most ``rows`` rounded up to 16."""
    tr = max(_ROW_QUANTUM, _BLOCK_BYTES // (4 * cols)
             // _ROW_QUANTUM * _ROW_QUANTUM)
    return min(tr, -(-rows // _ROW_QUANTUM) * _ROW_QUANTUM)


def _kernel(layer_ref, x_ref, o_ref, *, k: int):
    del layer_ref
    tr = o_ref.shape[0]
    x = x_ref.reshape(tr * k, LANES)       # the block as view rows
    for j in range(k):
        o_ref[:, j * LANES:(j + 1) * LANES] = x[
            pl.ds(j, tr, stride=k), :].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "tr",
                                             "interpret"))
def unflatten_cast(stack: jnp.ndarray, layer, *, shape, dtype,
                   tr: int | None = None,
                   interpret: bool = False) -> jnp.ndarray:
    """``stack[layer, 0, :n].reshape(shape).astype(dtype)`` in one pass.

    ``stack`` is ``(n_stack, 1, N)`` float32, ``shape`` aligned to it
    (:func:`fits` without the size floor); ``layer`` an int32 scalar
    (traced inside a scan, or a constant); ``tr`` the rows a grid step
    takes (default :func:`block_rows`).
    """
    _, one, flat = stack.shape
    assert one == 1 and _aligned(shape) and flat >= math.prod(shape), \
        (stack.shape, shape)
    cols = shape[-1]
    rows = math.prod(shape[:-1])
    tr = tr or block_rows(rows, cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(pl.cdiv(rows, tr),),
        in_specs=[pl.BlockSpec((1, 1, tr * cols),
                               lambda i, l: (l[0], 0, i))],
        out_specs=pl.BlockSpec((tr, cols), lambda i, l: (i, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, k=cols // LANES), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="unflatten_cast", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), stack)
    return out.reshape(shape)
