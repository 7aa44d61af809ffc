"""The system under test, reached through the same calls its serving
launcher makes (``repro.launch.serve.run``): ``make_test_mesh``,
``make_plan``, the ``paper`` policy with the ``auto`` codec backend,
``make_cache_init`` and ``make_decode_step``, over the launcher's float32
store.

The program is imported from ``<root>/src`` of the checkout the benchmark
runs in, and from nowhere else. What this module takes from it: the model
configuration by architecture id, the store's shapes, the compiled entry
points, and the Pallas kernel names in their HLO.
"""
from __future__ import annotations

import dataclasses
import re
import sys
from collections import Counter
from pathlib import Path
from typing import Dict


def import_program(root: Path):
    """Put ``<root>/src`` first on the path and import the program; refuse
    a ``repro`` package from anywhere else."""
    src = (root / "src").resolve()
    if not (src / "repro" / "launch" / "serve.py").exists():
        raise ImportError(f"no program under {src}")
    sys.path.insert(0, str(src))
    import repro
    where = {Path(p).resolve() for p in repro.__path__}
    if where != {src / "repro"}:
        raise ImportError(f"repro imported from {where}, not {src}")
    return repro


def custom_kernels(hlo: str) -> Counter:
    """Names of the Pallas TPU kernels (``tpu_custom_call``) in HLO."""
    names = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    return Counter(names)


# the as_run keys the program's ModelConfig must show
_CFG_FIELDS = {"d_model": "d_model", "n_heads": "n_heads",
               "n_kv_heads": "n_kv_heads", "head_dim": "hd",
               "d_ff": "d_ff", "vocab": "vocab", "n_layers": "n_layers",
               "rope_theta": "rope_theta", "qk_norm": "qk_norm"}


class Program:
    """One configuration of the program on this process's devices."""

    def __init__(self, root: Path, config: Dict):
        import_program(root)
        from repro.configs import get_config
        from repro.core.policy import paper_policy, with_backend
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.plan import make_plan

        prog = config["program"]
        run = config["as_run"]
        base = get_config(prog["arch"])
        self.cfg = dataclasses.replace(base, pattern_repeats=run["n_layers"])
        got = {key: getattr(self.cfg, attr)
               for key, attr in _CFG_FIELDS.items()}
        want = {key: run[key] for key in _CFG_FIELDS}
        plain = (base.pattern == ("dense",) and not base.prefix
                 and not base.suffix and base.act == "swiglu"
                 and base.norm == "rms" and not base.use_bias
                 and not base.tie_embeddings)
        if got != want or not plain:
            raise ValueError(f"the program's {prog['arch']} is not the "
                             f"configuration file's: {got} != {want}, or "
                             f"not a plain dense SwiGLU/RMSNorm stack")
        data, model = prog["mesh"]
        self.tp = model
        self.mesh = make_test_mesh(data=data, model=model)
        self.plan = make_plan(self.cfg, tp=model, fsdp=data)
        self.policy = with_backend(paper_policy(), "auto")
        self.tp_site = self.policy.resolve("tp", 0)

    # ----- store -----------------------------------------------------------
    def store_shapes(self) -> Dict[str, Dict[str, tuple]]:
        from repro.models.model import param_groups
        return {g: {n: (k, self.plan.tp, sp.flat_len(self.plan))
                    for n, sp in specs.items()}
                for g, (k, specs) in param_groups(self.cfg,
                                                  self.plan).items()}

    def store_sharding(self):
        import jax
        from repro.parallel.shardings import store_spec
        return jax.sharding.NamedSharding(self.mesh, store_spec(self.plan))

    def batch_sharding(self, batch: int):
        import jax
        from repro.train.train_step import batch_spec
        return jax.sharding.NamedSharding(self.mesh,
                                          batch_spec(batch, self.mesh))

    def codec(self) -> Dict:
        c = self.tp_site
        return {"enabled": bool(c and c.enabled and c.scheme != "nccl"),
                "bits": c.bits, "group": c.group, "spike": c.spike}

    # ----- entry points ----------------------------------------------------
    def decode(self, batch: int, cache_len: int):
        """The cache initialiser and the decode step, after the launcher's
        always-on commcheck of fused-scheme requests."""
        import jax
        from repro.analysis import commcheck
        from repro.train.serve_step import make_cache_init, make_decode_step
        commcheck.check_fused_request(
            self.cfg, self.plan, self.policy,
            {"data": self.mesh.shape["data"],
             "model": self.mesh.shape["model"]},
            global_batch=batch, seq=1, mode="decode",
            tpu=jax.default_backend() == "tpu", context="chipbench")
        return (make_cache_init(self.cfg, self.plan, self.mesh, batch,
                                cache_len),
                make_decode_step(self.cfg, self.plan, self.policy, self.mesh,
                                 batch, cache_len))
