"""Batched greedy decode: a fixed batch of sequences, each step one token
per sequence, streamed to the host.

Traffic parameters (``chipbench/traffic/<name>.json``):

* ``batch``: sequences decoded together.
* ``context``: prompt tokens per sequence. Set-up feeds them through the
  decode step one position at a time (prefill does not fill the cache),
  which also warms the step up.
* ``cache_len``: positions the cache holds. The window decodes until its
  time is up; a cache that fills first ends the window early, and the run
  says so.

Prompt token ids are uniform over the vocabulary; after the prompt every
sequence is fed its own greedy tokens.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from chipbench import counts, reference
from chipbench.generators import seed64

SPANS = ("submit", "wait", "host_copy", "feed")


@jax.jit
def _feed(tok):
    return tok[:, None]


class Loop:
    mode = "decode"

    def __init__(self, prog, cell, seed: int, store):
        t = cell.traffic
        self.prog, self.cell, self.store = prog, cell, store
        self.seed = seed64(seed)
        self.batch = int(t["batch"])
        self.context = int(t["context"])
        self.cache_len = int(t["cache_len"])
        self.vocab = cell.as_run["vocab"]
        self.init, self.step = prog.decode(self.batch, self.cache_len)
        self.sharding = prog.batch_sharding(self.batch)
        self.prompt = np.random.default_rng([self.seed, 2]).integers(
            0, self.vocab, (self.batch, self.context), dtype=np.int32)
        self.served: List[np.ndarray] = []     # (B,) per step, from g0
        self.times: List[float] = []
        self.full = False

    def setup(self) -> None:
        self.caches = self.init()
        out = None
        for j in range(self.context):
            tok = jax.device_put(self.prompt[:, j:j + 1], self.sharding)
            out, self.caches = self.step(self.store, self.caches,
                                         {"tokens": tok})
        self.served = [np.asarray(out)]
        self.tok = _feed(out)
        self.tok.block_until_ready()

    def window(self, seconds: float, span) -> Dict:
        room = self.cache_len - self.context
        times = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if len(times) >= room:
                self.full = True
                break
            t_submit = time.perf_counter()
            with span("submit"):
                out, self.caches = self.step(self.store, self.caches,
                                             {"tokens": self.tok})
            with span("wait"):
                out.block_until_ready()
            with span("host_copy"):
                self.served.append(np.asarray(out))
            times.append(time.perf_counter() - t_submit)
            with span("feed"):
                self.tok = _feed(out)
        t1 = time.perf_counter()
        self.times = times
        if self.full:
            print(f"[chipbench] the cache ({self.cache_len} positions) "
                  f"filled after {len(times)} steps, before the window's "
                  f"end", flush=True)
        return {"t0": t0, "t1": t1, "attempted": len(times) * self.batch,
                "failed": 0}

    def end_to_end(self, win: Dict) -> Dict[str, float]:
        tpot = [t * 1e3 for t in self.times]
        return {"tpot_p95_ms": float(np.percentile(tpot, 95)),
                "tokens_per_s": len(self.times) * self.batch
                / (win["t1"] - win["t0"])}

    def work(self) -> Dict:
        dm = counts.Dims.of(self.cell.as_run)
        c = self.prog.codec()
        steps = len(self.times)
        flops = 0
        for k in range(steps):
            ctx = self.context + k + 1        # position fed, and before it
            flops += counts.decode_step_flops(dm, [ctx] * self.batch)
        out = {"mode": self.mode, "steps": steps,
               "tokens": steps * self.batch, "flops": flops,
               "codec_bytes": 0,
               "codec_calls": {"wire_encode": 0, "wire_decode": 0}}
        if c["enabled"]:
            f = counts.forward_codec(dm, self.batch, self.prog.tp,
                                     c["bits"], c["group"], c["spike"])
            out["codec_bytes"] = steps * f["bytes"]
            out["codec_calls"] = {"wire_encode": steps * f["encode_calls"],
                                  "wire_decode": steps * f["decode_calls"]}
        return out

    def release(self) -> None:
        self.caches = None
        self.step = self.init = None

    def check(self, precisions=("float32",)) -> Dict[str, Dict]:
        """Per precision: the widest gap by which a served token's logit
        lies below the float32 reference's best, over every token served
        to every sequence."""
        served = np.stack(self.served, axis=1)        # (B, steps + 1)
        n = served.shape[1]
        rows, want = [], []
        length = -(-(self.context + n - 1) // 256) * 256
        for b in range(self.batch):
            seq = np.zeros(length, np.int32)      # zeros after: causal
            seq[:self.context] = self.prompt[b]
            seq[self.context:self.context + n - 1] = served[b, :n - 1]
            rows.append((seq, list(range(self.context - 1,
                                         self.context - 1 + n))))
            want.append([int(t) for t in served[b]])
        ref = reference.Runner(self.store, self.cell.as_run, self.prog.tp)
        return ref.gaps(rows, want, precisions)
