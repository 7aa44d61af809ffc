"""Seeded weights: made on the device in the program's storage form, and
read back in logical form for the reference.

The program serves from a flat store: every parameter is an array
``(n_stack, tp, flat)`` whose row ``[s, r]`` holds tensor-parallel rank
``r``'s slice of layer ``s``'s tensor, flattened and zero-padded to
``flat``. This module is the benchmark's loader for that form. Its own table
(:func:`table`) says, from the configuration's sizes alone, which tensors a
dense GQA block has, their logical shapes, the dimension split over ranks
and how each is drawn. The program's store shapes only say how long each
flat row is; a tensor the program has and the table lacks, or the reverse,
is an error.

Draws: embeddings N(0, 1); matrices N(0, 1/fan_in) with the logical fan-in;
norm gains 1 + 0.1 N(0, 1). A tensor that every rank holds whole is drawn
once and copied to every rank. Padding stays zero. One jitted call makes
the whole store from the seed.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.references import dense_gqa

# (group, name) -> (logical shape, dim split over ranks or None, draw)
Table = Dict[Tuple[str, str], Tuple[Tuple[int, ...], Optional[int], str]]


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def table(as_run: Dict, tp: int) -> Table:
    d, hd = as_run["d_model"], as_run["head_dim"]
    hq, hkv = as_run["n_heads"], as_run["n_kv_heads"]
    f, v = as_run["d_ff"], as_run["vocab"]
    for what, n in (("q heads", hq), ("d_ff", f), ("vocab", v)):
        if n % tp:
            raise ValueError(f"{what} {n} does not split over {tp} ranks")
    kv_split = 1 if hkv % tp == 0 else None
    t: Table = {
        ("embed", "tok"): ((v, d), 0, "embed"),
        ("out", "nf_gain"): ((d,), None, "gain"),
        ("out", "unemb"): ((v, d), 0, f"fan_in:{d}"),
        ("pattern", "L0_n1_gain"): ((d,), None, "gain"),
        ("pattern", "L0_wq"): ((d, hq * hd), 1, f"fan_in:{d}"),
        ("pattern", "L0_wk"): ((d, hkv * hd), kv_split, f"fan_in:{d}"),
        ("pattern", "L0_wv"): ((d, hkv * hd), kv_split, f"fan_in:{d}"),
        ("pattern", "L0_wo"): ((hq * hd, d), 0, f"fan_in:{hq * hd}"),
        ("pattern", "L0_n2_gain"): ((d,), None, "gain"),
        ("pattern", "L0_w1"): ((d, f), 1, f"fan_in:{d}"),
        ("pattern", "L0_w3"): ((d, f), 1, f"fan_in:{d}"),
        ("pattern", "L0_w2"): ((f, d), 0, f"fan_in:{f}"),
    }
    if as_run["qk_norm"]:
        t[("pattern", "L0_qnorm")] = ((hd,), None, "gain")
        t[("pattern", "L0_knorm")] = ((hd,), None, "gain")
    return t


def local_shape(shape, split, tp: int) -> Tuple[int, ...]:
    if split is None:
        return tuple(shape)
    s = list(shape)
    s[split] //= tp
    return tuple(s)


def check_layout(tab: Table, store_shapes: Dict, tp: int) -> None:
    """The program's store holds exactly the table's tensors, each row
    long enough for its rank's slice."""
    have = {(g, n) for g, leaves in store_shapes.items() for n in leaves}
    if have != set(tab):
        raise ValueError(
            f"store tensors differ from the benchmark's table: program only "
            f"{sorted(have - set(tab))}, table only {sorted(set(tab) - have)}")
    for (g, n), (shape, split, _) in tab.items():
        _, r, flat = store_shapes[g][n]
        if r != tp or flat < math.prod(local_shape(shape, split, tp)):
            raise ValueError(f"store tensor {g}/{n} {store_shapes[g][n]} "
                             f"cannot hold {shape} over {tp} ranks")


def _draw(z: jnp.ndarray, how: str) -> jnp.ndarray:
    if how == "embed":
        return z
    if how == "gain":
        return 1.0 + 0.1 * z
    fan_in = int(how.split(":")[1])
    return z * np.float32(1.0 / math.sqrt(fan_in))


def make_store(tab: Table, store_shapes: Dict, tp: int, seed: int,
               sharding) -> Dict[str, Dict[str, jax.Array]]:
    """The whole store from ``seed``, in one jitted call on the devices."""
    keys = sorted(tab)

    def gen(key):
        out: Dict[str, Dict[str, jax.Array]] = {}
        for i, (g, n) in enumerate(keys):
            shape, split, how = tab[(g, n)]
            full = store_shapes[g][n]
            # a tensor every rank holds whole is drawn once for all ranks
            draw = full if split is not None else (full[0], 1, full[2])
            z = jax.random.normal(jax.random.fold_in(key, i), draw,
                                  jnp.float32)
            numel = math.prod(local_shape(shape, split, tp))
            live = lax.broadcasted_iota(jnp.int32, draw, 2) < numel
            v = jnp.where(live, _draw(z, how), 0.0)
            out.setdefault(g, {})[n] = jnp.broadcast_to(v, full)
        return out

    shard_tree = {g: {n: sharding for n in leaves}
                  for g, leaves in store_shapes.items()}
    return jax.jit(gen, out_shardings=shard_tree)(seed_key(seed))


# ---------------------------------------------------------------------------
# logical weights for the reference
# ---------------------------------------------------------------------------

def rank_shards(leaf: jax.Array):
    """(rank, per-device (n_stack, 1, flat) array) for each rank once."""
    seen = {}
    for sh in leaf.addressable_shards:
        r = sh.index[1].start or 0
        seen.setdefault(r, sh.data)
    return sorted(seen.items())


@functools.partial(jax.jit, static_argnames=("numel",))
def _row(a: jax.Array, s, numel: int) -> jax.Array:
    return lax.dynamic_index_in_dim(a, s, 0, keepdims=False)[0, :numel]


def logical(leaf: jax.Array, tab: Table, key, tp: int, stack: int,
            device) -> jax.Array:
    """Layer ``stack`` of one tensor, whole, on ``device``."""
    shape, split, _ = tab[key]
    loc = local_shape(shape, split, tp)
    numel = math.prod(loc)
    parts = [jax.device_put(_row(a, stack, numel), device).reshape(loc)
             for _, a in rank_shards(leaf)]
    if split is None:
        return parts[0]
    return jnp.concatenate(parts, axis=split)


def layer(store, tab: Table, tp: int, stack: int, device) -> Dict:
    """Every tensor of pattern layer ``stack``, keyed as the reference
    names them (``n1_gain``, ``wq``, ...)."""
    return {n[len("L0_"):]: logical(store[g][n], tab, (g, n), tp, stack,
                                    device)
            for (g, n) in tab if g == "pattern"}


@functools.partial(jax.jit, static_argnames=("d", "v_loc"))
def _embed_rows(a: jax.Array, ids: jax.Array, base, d: int,
                v_loc: int) -> jax.Array:
    flat = a[0, 0]
    loc = ids - base
    ok = (loc >= 0) & (loc < v_loc)
    rows = jax.vmap(lambda i: lax.dynamic_slice(flat, (i * d,), (d,)))(
        jnp.clip(loc, 0, v_loc - 1))
    return jnp.where(ok[:, None], rows, 0.0)


def embed(store, tab: Table, tp: int, ids: np.ndarray, device) -> jax.Array:
    """Embedding rows (T, d) of token ids, on ``device``: each rank looks
    up the ids in its vocabulary slice, and the slices are added."""
    (v, d), _, _ = tab[("embed", "tok")]
    v_loc = v // tp
    total = None
    for r, a in rank_shards(store["embed"]["tok"]):
        ids_here = jax.device_put(np.asarray(ids, np.int32),
                                  next(iter(a.devices())))
        rows = _embed_rows(a, ids_here, r * v_loc, d=d, v_loc=v_loc)
        rows = jax.device_put(rows, device)
        total = rows if total is None else total + rows
    return total


@functools.partial(jax.jit, static_argnames=("rows", "d", "precision"))
def _head_block(a, start, h, ids, rows: int, d: int, precision: str):
    w = lax.dynamic_slice(a, (0, 0, start * d), (1, 1, rows * d))
    lg = dense_gqa.logits(h, w.reshape(rows, d), precision)    # (R, rows)
    loc = ids - start                         # ids local to this rank
    inside = (loc >= 0) & (loc < rows)
    got = jnp.take_along_axis(lg, jnp.clip(loc, 0, rows - 1)[:, None],
                              axis=1)[:, 0]
    return (jnp.max(lg, axis=1), jnp.argmax(lg, axis=1) + start,
            jnp.where(inside, got, -jnp.inf))


def head_stats(store, tab: Table, tp: int, h: jax.Array, ids: np.ndarray,
               precision: str, block: int = 8192):
    """For normed hidden rows ``h`` (R, d): each row's largest logit, the
    token that has it, and the logit of ``ids[r]``, over the whole
    vocabulary. Each rank's slice of the output embedding is read in
    blocks of rows on the rank's own device; no row of logits leaves it."""
    (v, d), _, _ = tab[("out", "unemb")]
    v_loc = v // tp
    best = np.full(h.shape[0], -np.inf, np.float32)
    first = np.zeros(h.shape[0], np.int64)
    got = np.full(h.shape[0], -np.inf, np.float32)
    for r, a in rank_shards(store["out"]["unemb"]):
        dev = next(iter(a.devices()))
        hd = jax.device_put(h, dev)
        ids_r = jax.device_put(np.asarray(ids, np.int32) - r * v_loc, dev)
        for s in range(0, v_loc, block):
            rows = min(block, v_loc - s)
            mx, am, g = (np.asarray(o) for o in _head_block(
                a, s, hd, ids_r, rows=rows, d=d, precision=precision))
            take = mx > best
            first = np.where(take, am + r * v_loc, first)
            best = np.maximum(best, mx)
            got = np.maximum(got, g)
    return best, first, got
