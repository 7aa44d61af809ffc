"""Quickstart: the FlashCommunication V2 wire format + quantized
collectives in five minutes (runs on CPU with 8 fake devices).

  PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (CommConfig, codec, compressed_psum,
                        default_comm_config)
from repro.core.spike import spike_qdq
from repro.core.quant import qdq
from repro.launch.mesh import make_test_mesh

# ---------------------------------------------------------------- wire ----
print("== 1. any-bit wire format (bit splitting) ==")
x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 2
for bits in (8, 5, 3, 2):
    cfg = default_comm_config(bits)
    buf = codec.encode(x, cfg)
    y = codec.decode(buf, cfg, 4096)
    print(f"  INT{bits}: {buf.nbytes:5d} wire bytes "
          f"({cfg.compression_ratio(4096):.2f}x vs BF16), "
          f"max err {float(jnp.max(jnp.abs(y - x))):.4f}"
          f"{'  [spike reserving]' if cfg.spike else ''}")

# ------------------------------------------------------------- spikes ----
print("== 2. spike reserving beats RTN on outlier-heavy activations ==")
xo = np.asarray(x).copy()
xo[np.random.default_rng(0).integers(0, 4096, 30)] *= 50
xo = jnp.asarray(xo)
for name, fn in (("RTN   ", qdq), ("SpikeR", spike_qdq)):
    mse = float(jnp.mean((fn(xo, 2, 32) - xo) ** 2))
    print(f"  INT2 {name}: MSE {mse:.4f}")

# -------------------------------------------------------- collectives ----
print("== 3. quantized AllReduce across 8 devices ==")
mesh = make_test_mesh(data=1, model=4, pod=2)
xs = jax.random.normal(jax.random.PRNGKey(1), (8, 2048))
ref = np.sum(np.asarray(xs), axis=0)
for scheme, bits in (("two_step", 8), ("hierarchical", 4), ("hier_pp", 2)):
    cfg = default_comm_config(bits, scheme=scheme)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(("pod", "data", "model")),
             out_specs=P(("pod", "data", "model")), check_vma=False)
    def ar(v):
        return compressed_psum(v[0], ("model", "pod"), cfg)[None]

    out = np.asarray(ar(xs))
    err = float(np.max(np.abs(out[0] - ref)))
    wire = cfg.wire_bytes(2048 // 4)
    print(f"  {scheme:13s} INT{bits}: max err {err:.4f}, "
          f"per-hop wire {wire} B vs {2048 // 4 * 2} B BF16")
print("OK — see examples/train_moe_e2e.py for the full training driver.")
