"""Trained proxy models for the accuracy benchmarks (Tables 1-3, 7, 8).

The paper evaluates Llama-3-8B/70B and Qwen MoEs on C4; this container
cannot run those, so the accuracy benches reproduce the paper's
*qualitative* claims on small models trained on a synthetic Markov
language: INT5 ~ INT8; RTN collapses at INT2 under AllReduce while
SpikeReserving survives; All2All dispatch quantization is far more
tolerant than AllReduce quantization. Trained stores are cached on disk.
"""
from __future__ import annotations

import os
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.policy import BF16_POLICY, CommPolicy
from repro.launch.mesh import make_test_mesh
from repro.models.model import forward, lm_loss, param_groups
from repro.parallel.plan import make_plan
from repro.parallel.shardings import STORE_SPEC, build_store
from repro.train import checkpoint as ck
from repro.train.data import DataConfig, make_dataset, to_device
from repro.train.optim import OptimConfig
from repro.train.train_step import init_train_state, make_train_step
from jax.sharding import PartitionSpec as P

CACHE = os.path.join(os.path.dirname(__file__), "_cache")
SEQ = 128
BATCH = 8
STEPS = 120

PROXIES = {"dense": "llama3-8b", "moe": "moonshot-v1-16b-a3b"}


def get_trained(kind: str) -> Tuple:
    """-> (cfg, plan, mesh, store, dataset). Trains once, caches npz."""
    arch = PROXIES[kind]
    cfg = get_smoke_config(arch)
    mesh = make_test_mesh()
    plan = make_plan(cfg, tp=1, fsdp=1)
    path = os.path.join(CACHE, f"proxy_{kind}.npz")
    ds = make_dataset(DataConfig(vocab=cfg.vocab, seq_len=SEQ,
                                 global_batch=BATCH, seed=7))
    if os.path.exists(path):
        store, _, _ = ck.restore(path, mesh)
        return cfg, plan, mesh, store, ds

    store = build_store(param_groups(cfg, plan), plan,
                        jax.random.PRNGKey(0), jnp.float32, mesh)
    opt_cfg = OptimConfig(lr=2e-3, warmup_steps=10, total_steps=STEPS)
    opt = init_train_state(store, opt_cfg)
    step = make_train_step(cfg, plan, BF16_POLICY, opt_cfg, mesh,
                           global_batch=BATCH)
    for i in range(STEPS):
        store, opt, m = step(store, opt, to_device(ds.batch(i)))
    print(f"# proxy[{kind}] trained {STEPS} steps, "
          f"final loss {float(m['loss']):.3f}")
    os.makedirs(CACHE, exist_ok=True)
    ck.save(path, store, None, STEPS)
    return cfg, plan, mesh, store, ds


def eval_loss(cfg, plan, mesh, store, ds, policy: CommPolicy,
              n_batches: int = 4) -> float:
    """Eval CE (proxy for the paper's perplexity columns) under a given
    communication-compression policy."""
    def f(views, batch):
        hidden, unemb, aux, _ = forward(views, batch["tokens"], cfg, plan,
                                        policy, dtype=jnp.float32)
        return lm_loss(hidden, unemb, batch["labels"], cfg, plan, aux,
                       aux_weight=0.0)
    bs = {"tokens": P(), "labels": P()}
    sm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(STORE_SPEC, bs),
                               out_specs=P(), check_vma=False))
    tot = 0.0
    for i in range(1000, 1000 + n_batches):      # held-out batches
        b = to_device(ds.batch(i))
        tot += float(sm(store, {"tokens": b["tokens"],
                                "labels": b["labels"]}))
    return tot / n_batches
