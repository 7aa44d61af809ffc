"""Flat ZeRO-3 parameter store + quantized FSDP gather.

Every parameter lives in storage form ``(n_stack, tp, flat)``:

* dim0 — stacked pattern repeats (1 for unstacked groups), scanned over;
* dim1 — the TP rank's local values (heads / hidden / vocab / expert
  slice already applied), flattened;
* dim2 — zero-padded flat payload, sharded over the ``data`` axis.

One PartitionSpec covers the whole tree: ``P(None, "model", "data")``.
Inside ``shard_map`` the per-rank view is ``(n_stack, 1, flat/fsdp)``;
``gather_flat`` all-gathers dim2 (optionally through the paper's wire
codec — ZeRO++-style quantized weight gather, a beyond-paper extension)
and reshapes to the logical local shape. Its transpose is the *exact*
reduce-scatter, which lands gradients exactly where the ZeRO optimizer
shards live.

The quantized gradient RS deliberately does NOT live in that transpose:
a ``custom_vjp`` cannot thread the error-feedback residual state, so a
qgrad inside the backward pass is forever biased (and its early version
silently fell back to the exact psum_scatter on alignment mismatches).
Instead ``gather_param`` accepts a zero-valued full-length ``delta``
added to the (stop-gradiented) gathered weights; differentiating w.r.t.
the deltas hands the train step per-rank *full* gradients, and the
quantized+EF reduce-scatter runs as an explicit post-``value_and_grad``
pass (``train_step.py`` -> ``collectives.quantized_reduce_scatter_ef``)
with its residual pytree in optimizer state.

Serving with resident weights (``fsdp == 1``) needs no gradient and
reads the store through ``serve_param`` instead: each tensor once, the
matrices on the TPU through the ``unflatten_cast`` kernel.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import codec
from repro.core.comm_config import CommConfig
from repro.kernels.unflatten_cast import fits, unflatten_cast
from repro.parallel.plan import ShardingPlan, flat_store_len

STORE_SPEC = P(None, "model", "data")


def store_spec(plan=None):
    """Storage PartitionSpec. fsdp=1 (serving mode for models whose
    TP-local weights fit HBM): dim2 replicated — no per-layer gather."""
    if plan is not None and plan.fsdp == 1:
        return P(None, "model", None)
    return STORE_SPEC


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Global logical shape + how it maps to a TP rank."""
    shape: Tuple[int, ...]
    tp_dim: Optional[int] = None      # dim sharded over model axis
    init: str = "fan_in"              # fan_in | zeros | ones | lru_lambda
    # experts: "in" = (E, d, F) with F over etp; "out" = (E, F, d).
    # E is sharded over ep; rank m = ep_idx*etp + tp_idx.
    moe_fold: Optional[str] = None

    def local_shape(self, plan: ShardingPlan) -> Tuple[int, ...]:
        if self.moe_fold is not None:
            m = plan.moe
            if self.moe_fold == "in":
                e, d, f = self.shape
                return (m.e_loc, d, f // m.etp)
            e, f, d = self.shape
            return (m.e_loc, f // m.etp, d)
        if self.tp_dim is None:
            return self.shape
        s = list(self.shape)
        assert s[self.tp_dim] % plan.tp == 0, (self.shape, self.tp_dim)
        s[self.tp_dim] //= plan.tp
        return tuple(s)

    def numel_loc(self, plan: ShardingPlan) -> int:
        return math.prod(self.local_shape(plan))

    def flat_len(self, plan: ShardingPlan) -> int:
        return flat_store_len(self.numel_loc(plan), plan.fsdp)


def _fill_values(out: np.ndarray, spec: ParamSpec, seed: Tuple[int, ...],
                 rank: int, plan: ShardingPlan) -> None:
    """Write one rank's local f32 values into the flat ``out`` (zeros;
    its tail beyond the values stays zero padding). TP-sliced params add
    the rank to the seed (slices are independent); replicated params
    share the seed so every rank holds identical values."""
    shape = spec.local_shape(plan)
    v = out[:math.prod(shape)]
    if spec.init == "zeros":
        return
    if spec.init == "ones":
        v[:] = 1.0
        return
    if spec.tp_dim is not None or spec.moe_fold is not None:
        seed = seed + (rank,)
    gen = np.random.default_rng(seed)
    if spec.init == "lru_lambda":
        # RG-LRU: a = exp(-c*softplus(L)*r); init recurrence ~U(0.9, 0.999)
        gen.random(dtype=np.float32, out=v)
        u = v * np.float32(0.099) + np.float32(0.9)
        v[:] = np.log(np.exp(-np.log(u) / 8.0) - 1.0)  # inv softplus
        return
    # fan-in normal, drawn in place
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    gen.standard_normal(dtype=np.float32, out=v)
    v *= np.float32(1.0 / math.sqrt(max(fan_in, 1)))


# --------------------------------------------------------------------------
# FSDP gather (differentiable, optionally quantized)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fsdp_all_gather(x: jnp.ndarray, axis: str, cfg: Optional[CommConfig]):
    """(flat/fsdp,) -> (flat,) over the data axis.

    cfg=None/disabled -> plain all_gather. Enabled -> the paper's wire
    codec compresses the gathered weights (ZeRO++-style qAG). Transpose
    is the *exact* reduce-scatter (lands grads ZeRO-sharded); gradient
    compression happens outside the VJP — see the module docstring.
    """
    if cfg is None or not cfg.enabled:
        return lax.all_gather(x, axis, axis=0, tiled=True)
    wire = codec.encode(x, cfg)
    allw = lax.all_gather(wire, axis, axis=0)
    return codec.decode(allw, cfg, x.shape[-1],
                        out_dtype=x.dtype).reshape(-1)


def _ag_fwd(x, axis, cfg):
    return fsdp_all_gather(x, axis, cfg), None


def _ag_bwd(axis, cfg, res, g):
    del res
    return (lax.psum_scatter(g, axis, scatter_dimension=0, tiled=True),)


fsdp_all_gather.defvjp(_ag_fwd, _ag_bwd)


def gather_param(flat_view: jnp.ndarray, spec: ParamSpec,
                 plan: ShardingPlan, dtype,
                 qag: Optional[CommConfig] = None,
                 delta: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-rank storage view (1, flat/fsdp) -> logical local array.

    The differentiable path, which training takes (and serving with
    ``fsdp > 1``): on the TPU the final ``reshape(shape).astype(dtype)``
    compiles to two passes over HBM, a cast to a padded 1-D array and a
    relayout. Serving with resident weights takes :func:`serve_param`.

    ``delta`` (a zero full-length ``(1, flat)`` per-rank array) is the
    gradient tap for the out-of-VJP qgrad path: when given, the gathered
    weights are stop-gradiented and ``delta`` added, so the grad w.r.t.
    the deltas is the *full-length* per-rank parameter gradient — before
    any reduce-scatter — which the train step then syncs explicitly
    through the quantized+EF RS.
    """
    if plan.fsdp == 1:           # serving mode: weights resident
        flat = flat_view.reshape(-1)
    else:
        flat = fsdp_all_gather(flat_view.reshape(-1), "data", qag)
    if delta is not None:
        flat = lax.stop_gradient(flat) + delta.reshape(-1).astype(flat.dtype)
    shape = spec.local_shape(plan)
    n = math.prod(shape)
    return flat[:n].reshape(shape).astype(dtype)


def gather_group(views: Dict[str, jnp.ndarray],
                 specs: Dict[str, ParamSpec], plan: ShardingPlan, dtype,
                 qag: Optional[CommConfig] = None,
                 deltas: Optional[Dict[str, jnp.ndarray]] = None
                 ) -> Dict[str, jnp.ndarray]:
    with jax.named_scope("weights"):
        return {name: gather_param(views[name], specs[name], plan, dtype,
                                   qag,
                                   None if deltas is None else deltas[name])
                for name in specs}


# --------------------------------------------------------------------------
# serving read (resident weights, no gradient)
# --------------------------------------------------------------------------

def _backend() -> str:
    return jax.default_backend()


def serve_param(stack: jnp.ndarray, spec: ParamSpec, plan: ShardingPlan,
                dtype, layer=0) -> jnp.ndarray:
    """Per-rank stacked store ``(n_stack, 1, flat)`` -> layer ``layer``'s
    logical local array, for serving with ``fsdp == 1``.

    On the TPU a tensor the :mod:`~repro.kernels.unflatten_cast` kernel
    fits (two or more dimensions, the last a multiple of 128, at least
    one 4 MiB block of float32) is read once and written once, straight
    from the stack: inside a layer scan ``layer`` is the traced layer
    index, so no per-layer slice is copied and the compiler has no cast
    of the whole stack to hoist out of the loop. Everything else, and every tensor off the TPU, takes
    ``gather_param``'s expression. Both equal it bit for bit. No VJP.
    """
    shape = spec.local_shape(plan)
    if _backend() == "tpu" and fits(shape):
        return unflatten_cast(stack, layer, shape=shape, dtype=dtype)
    row = lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    return row.reshape(-1)[:math.prod(shape)].reshape(shape).astype(dtype)


def serve_group(stacks: Dict[str, jnp.ndarray],
                specs: Dict[str, ParamSpec], plan: ShardingPlan, dtype,
                layer=0) -> Dict[str, jnp.ndarray]:
    with jax.named_scope("weights"):
        return {name: serve_param(stacks[name], specs[name], plan, dtype,
                                  layer)
                for name in specs}


# --------------------------------------------------------------------------
# storage construction (real arrays for tests/examples; abstract for dryrun)
# --------------------------------------------------------------------------

def store_shapes(groups: Dict[str, Tuple[int, Dict[str, ParamSpec]]],
                 plan: ShardingPlan, dtype
                 ) -> Dict[str, Dict[str, jax.ShapeDtypeStruct]]:
    """{group: (n_stack, specs)} -> ShapeDtypeStructs in storage form."""
    out = {}
    for gname, (n_stack, specs) in groups.items():
        out[gname] = {
            name: jax.ShapeDtypeStruct(
                (n_stack, plan.tp, spec.flat_len(plan)), dtype)
            for name, spec in sorted(specs.items())}
    return out


def build_store(groups, plan: ShardingPlan, key, dtype,
                mesh=None) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Materialize storage arrays ``(n_stack, tp, flat)`` from ``key``.

    Values are drawn on the host (numpy), so building compiles nothing;
    the TPU compiler takes minutes to relayout a flat payload into the
    ``(n_stack, 1, flat)`` storage form. With a ``mesh`` each device
    receives only its own TP rank's ``data`` slice: the store is never
    whole on one device (a full-width model's store outgrows any one
    chip long before it outgrows the mesh).
    """
    base = tuple(int(w) for w in np.asarray(
        jax.random.key_data(key) if jax.dtypes.issubdtype(
            key.dtype, jax.dtypes.prng_key) else key).ravel())
    out = {}
    for gi, (gname, (n_stack, specs)) in enumerate(sorted(groups.items())):
        def rank_values(r, n_stack=n_stack, specs=specs, seed=base + (gi,)):
            vals = {}
            for name, spec in sorted(specs.items()):
                # crc32, not hash(): str hashes change between processes
                tag = zlib.crc32(name.encode())
                v = np.zeros((n_stack, 1, spec.flat_len(plan)), np.float32)
                for si in range(n_stack):
                    _fill_values(v[si, 0], spec, seed + (si, tag), r, plan)
                vals[name] = v if np.dtype(dtype) == np.float32 \
                    else v.astype(dtype)
            return vals

        if mesh is None:
            ranks = [rank_values(r) for r in range(plan.tp)]
            out[gname] = {name: jnp.asarray(np.concatenate(
                [v[name] for v in ranks], axis=1)) for name in specs}
            continue
        sharding = jax.sharding.NamedSharding(mesh, STORE_SPEC)
        shapes = {name: (n_stack, plan.tp, spec.flat_len(plan))
                  for name, spec in specs.items()}
        shards = {name: [] for name in specs}
        index_map = sharding.addressable_devices_indices_map(
            next(iter(shapes.values())))
        by_rank = {}
        for dev, idx in index_map.items():
            rank = idx[1].start or 0
            if rank not in by_rank:
                by_rank = {rank: rank_values(rank)}   # one rank on host
            for name in specs:
                cols = sharding.addressable_devices_indices_map(
                    shapes[name])[dev][2]
                shards[name].append(jax.device_put(
                    by_rank[rank][name][:, :, cols], dev))
        out[gname] = {
            name: jax.make_array_from_single_device_arrays(
                shapes[name], sharding, shards[name])
            for name in specs}
    return out
