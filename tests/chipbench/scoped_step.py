"""Compile the decode step at the rehearsal's smoke size on virtual CPU
devices and print, for each mesh, every instruction of the compiled step
with its opcode, its scope (chipbench/scopes.py) and whether it carries an
``op_name``.

    python tests/chipbench/scoped_step.py 1 4

prints one JSON object: {"<model axis size>": {name: [opcode, scope,
named]}}. A four-device mesh uses the rehearsal's four kv heads.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def compiled_step(tp: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from rehearse import SMOKE_TRAFFIC, TP4, smoke_config
    from repro.core.policy import paper_policy, with_backend
    from repro.models.model import param_groups
    from repro.parallel.plan import make_plan
    from repro.parallel.shardings import store_spec
    from repro.train.serve_step import decode_cache_specs, make_decode_step
    from repro.train.train_step import batch_spec

    widths = json.loads(TP4[-1]) if tp == 4 else {}
    cfg = dataclasses.replace(smoke_config("qwen3-14b", widths),
                              pattern_repeats=2)
    traffic = SMOKE_TRAFFIC["batch_decode"]
    batch, cache_len = traffic["batch"], traffic["cache_len"]
    mesh = Mesh(np.array(jax.devices()[:tp]).reshape(1, tp),
                ("data", "model"))
    plan = make_plan(cfg, tp=tp, fsdp=1)
    ss = NamedSharding(mesh, store_spec(plan))
    store = {g: {n: jax.ShapeDtypeStruct((k, tp, sp.flat_len(plan)),
                                         jnp.float32, sharding=ss)
                 for n, sp in specs.items()}
             for g, (k, specs) in param_groups(cfg, plan).items()}
    shapes, specs = decode_cache_specs(cfg, plan, mesh, batch, cache_len)
    caches = jax.tree.map(lambda s, p: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=NamedSharding(mesh, p)), shapes, specs)
    toks = jax.ShapeDtypeStruct(
        (batch, 1), jnp.int32,
        sharding=NamedSharding(mesh, batch_spec(batch, mesh)))
    step = make_decode_step(cfg, plan, with_backend(paper_policy(), "auto"),
                            mesh, batch, cache_len)
    return step.lower(store, caches, {"tokens": toks}).compile()


def main() -> int:
    sizes = [int(a) for a in sys.argv[1:]]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count="
                               f"{max(sizes)}")
    sys.path[:0] = [str(REPO / "src"), str(REPO),
                    str(Path(__file__).resolve().parent)]
    import jax
    from chipbench import scopes
    jax.config.update("jax_enable_compilation_cache", False)
    out = {}
    for tp in sizes:
        compiled = compiled_step(tp)
        named = set(re.findall(r'%([\w.-]+) = [^\n]*op_name="[^"]',
                               compiled.as_text()))
        proto = compiled.runtime_executable().hlo_modules()[0] \
            .as_serialized_hlo_module_proto()
        out[str(tp)] = {n: [op, s, n in named] for n, (op, s) in
                        scopes.program_scopes(proto).items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
