"""Runs a configuration's plain reference over served sequences and reads
the gap that decides ``correct``.

The reference module is the one the configuration file names
(``chipbench/references/<name>.py``). Its weights are the benchmark's own
draw (:mod:`chipbench.weights`), read back in logical form layer by layer;
nothing the program computed reaches it. Sequences are spread over the
process's devices, one whole sequence to a device, and every layer's
weights are copied whole to each device that holds a sequence.
"""
from __future__ import annotations

import importlib
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights


class Runner:
    def __init__(self, store, as_run: Dict, tp: int):
        self.mod = importlib.import_module(
            f"chipbench.references.{as_run['reference']}")
        self.arch = self.mod.Arch.of(as_run)
        self.store, self.tp = store, tp
        self.tab = weights.table(as_run, tp)
        self.devices = sorted(jax.local_devices(), key=lambda d: d.id)

    def hidden(self, rows: Sequence[Tuple[np.ndarray, List[int]]],
               precision: str) -> jax.Array:
        """Final-normed hidden rows at the asked positions of every
        sequence, stacked in order, on the first device."""
        devs = [self.devices[j % len(self.devices)] for j in range(len(rows))]
        used = sorted(set(devs), key=lambda d: d.id)
        xs = [weights.embed(self.store, self.tab, self.tp, seq, dev)
              for (seq, _), dev in zip(rows, devs)]
        t_w = t_x = 0.0
        for lyr in range(self.arch.n_layers):
            t0 = time.perf_counter()
            w = {d: weights.layer(self.store, self.tab, self.tp, lyr, d)
                 for d in used}
            jax.block_until_ready(list(w.values()))
            t1 = time.perf_counter()
            xs = [self.mod.layer(x, w[d], self.arch, precision)
                  for x, d in zip(xs, devs)]
            jax.block_until_ready(xs)
            t_w, t_x = t_w + t1 - t0, t_x + time.perf_counter() - t1
            del w
        print(f"[chipbench] reference {precision}: {len(rows)} sequences "
              f"on {len(used)} devices, weights {t_w:.1f} s, layers "
              f"{t_x:.1f} s", flush=True)
        key = ("out", "nf_gain")
        gain = {d: weights.logical(self.store["out"]["nf_gain"], self.tab,
                                   key, self.tp, 0, d) for d in used}
        hs = []
        for x, (_, pos), d in zip(xs, rows, devs):
            idx = jax.device_put(_padded(pos), d)
            h = self.mod.final_norm(x[idx], gain[d], self.arch)
            hs.append(jax.device_put(h, self.devices[0]))
        return jnp.concatenate(hs, axis=0)

    def gaps(self, rows, want: Sequence[Sequence[int]],
             precisions: Sequence[str] = ("float32",)) -> Dict[str, Dict]:
        """``rows``: (token ids, positions whose next token was served);
        ``want``: the served tokens, one per position.

        Returns, for ``"program"`` (the served tokens) and each control
        precision other than float32 (the tokens that precision puts
        first), the widest gap ``max logit - logit of that token`` of the
        float32 reference, and how many tokens were not its first."""
        served = np.concatenate([_padded(ts) for ts in want])
        real = np.concatenate([np.arange(len(_padded(ts))) < len(ts)
                               for ts in want])
        h32 = self.hidden(rows, "float32")
        best, _, got = weights.head_stats(self.store, self.tab, self.tp, h32,
                                          served, "float32")
        out = {"program": _gap(best[real], got[real])}
        for p in precisions:
            if p == "float32":
                continue
            hp = self.hidden(rows, p)
            _, first, _ = weights.head_stats(self.store, self.tab, self.tp,
                                             hp, served, p)
            _, _, got_p = weights.head_stats(self.store, self.tab, self.tp,
                                             h32, first, "float32")
            out[p] = _gap(best[real], got_p[real])
        return out


def _padded(xs: Sequence[int], unit: int = 256) -> np.ndarray:
    """``xs`` as int32, its last entry repeated up to a multiple of
    ``unit`` (or left at one entry), so that the programs that read it
    take few distinct shapes."""
    n = len(xs) if len(xs) == 1 else -(-len(xs) // unit) * unit
    out = np.full(n, xs[-1], np.int32)
    out[:len(xs)] = xs
    return out


def _gap(best: np.ndarray, got: np.ndarray) -> Dict:
    g = best - got
    return {"max_gap": float(np.max(g)), "tokens": int(g.size),
            "not_first": int(np.sum(g > 0))}
