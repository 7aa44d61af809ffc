"""Model composition: blocks -> layer layout -> forward/decode.

Runs entirely inside ``shard_map`` on the (data, model) mesh. Parameters
arrive as per-rank storage views (flat ZeRO-3 shards, see
``repro.parallel.shardings``); each block group FSDP-gathers its weights
(optionally through the quantized wire codec), applies the block with
``jax.checkpoint`` (remat), and every activation crossing the model axis
goes through the paper's quantized collectives.

The repeated ``pattern`` is executed with ``lax.scan`` over stacked
parameters so HLO size is O(pattern period), not O(layers) — with 512
host devices this is what keeps multi-pod compiles tractable.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.policy import CommPolicy
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models.config import ModelConfig
from repro.models.layers import (apply_norm, embed_lookup, mlp_apply,
                                 vocab_parallel_ce, vocab_parallel_logits)
from repro.parallel.plan import ShardingPlan, make_plan
from repro.parallel.shardings import ParamSpec, gather_group

# Roofline builds set this so the pattern/encoder scans fully unroll and
# XLA's cost_analysis (which counts while bodies once) sees every layer.
# Real runs keep scans rolled: HLO stays O(pattern period).
UNROLL_LAYER_SCAN = False

# ===========================================================================
# parameter specs
# ===========================================================================

def _norm_specs(cfg: ModelConfig, name: str) -> Dict[str, ParamSpec]:
    s = {name + "gain": ParamSpec((cfg.d_model,), init="ones")}
    if cfg.norm == "ln":
        s[name + "bias"] = ParamSpec((cfg.d_model,), init="zeros")
    return s


def _mlp_specs(cfg: ModelConfig, plan: ShardingPlan) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, plan.f_loc * plan.tp
    s = {"w1": ParamSpec((d, f), tp_dim=1),
         "w2": ParamSpec((f, d), tp_dim=0, init="zeros")}
    if cfg.act in ("swiglu", "geglu"):
        s["w3"] = ParamSpec((d, f), tp_dim=1)
    if cfg.use_bias:
        s["b1"] = ParamSpec((f,), tp_dim=0, init="zeros")
        s["b2"] = ParamSpec((d,), init="zeros")
        if cfg.act in ("swiglu", "geglu"):
            s["b3"] = ParamSpec((f,), tp_dim=0, init="zeros")
    return s


def block_specs(kind: str, cfg: ModelConfig,
                plan: ShardingPlan) -> Dict[str, ParamSpec]:
    s = dict(_norm_specs(cfg, "n1_"))
    if kind in ("dense", "local", "moe", "enc", "dec"):
        s.update(attn.attn_specs(cfg, plan))
    if kind in ("dec", "xattn"):
        s.update(attn.attn_specs(cfg, plan, cross=True, prefix="x"))
    if kind == "dec":
        s.update(_norm_specs(cfg, "n3_"))
    if kind in ("dense", "local", "enc", "dec", "xattn", "rec"):
        s.update(_norm_specs(cfg, "n2_"))
        s.update(_mlp_specs(cfg, plan))
    if kind == "moe":
        s.update(_norm_specs(cfg, "n2_"))
        s.update(moe_mod.moe_specs(cfg, plan))
    if kind == "rec":
        s.update(rec_mod.rglru_specs(cfg, plan))
    if kind == "mlstm":
        s.update(rec_mod.mlstm_specs(cfg, plan))
    if kind == "slstm":
        s.update(rec_mod.slstm_specs(cfg, plan))
    return s


def param_groups(cfg: ModelConfig, plan: ShardingPlan
                 ) -> Dict[str, Tuple[int, Dict[str, ParamSpec]]]:
    """{group_name: (n_stack, {param: spec})} for the whole model."""
    d = cfg.d_model
    groups: Dict[str, Tuple[int, Dict[str, ParamSpec]]] = {}

    emb = {"tok": ParamSpec((plan.vocab_pad, d), tp_dim=0)}
    if cfg.rope_theta is None and cfg.learned_pos:
        emb["pos"] = ParamSpec((cfg.max_pos, d))
    groups["embed"] = (1, emb)

    out = dict(_norm_specs(cfg, "nf_"))
    if not cfg.tie_embeddings:
        out["unemb"] = ParamSpec((plan.vocab_pad, d), tp_dim=0)
    groups["out"] = (1, out)

    if cfg.is_enc_dec:
        enc = block_specs("enc", cfg, plan)
        groups["encoder"] = (cfg.encoder.n_layers, enc)
        extra = dict(_norm_specs(cfg, "ef_"))
        extra["enc_pos"] = ParamSpec((cfg.encoder.n_ctx, d))
        groups["encoder_extra"] = (1, extra)

    for i, kind in enumerate(cfg.prefix):
        groups[f"pre{i}_{kind}"] = (1, block_specs(kind, cfg, plan))
    if cfg.pattern_repeats:
        merged: Dict[str, ParamSpec] = {}
        for j, kind in enumerate(cfg.pattern):
            for n, sp in block_specs(kind, cfg, plan).items():
                merged[f"L{j}_{n}"] = sp
        groups["pattern"] = (cfg.pattern_repeats, merged)
    for i, kind in enumerate(cfg.suffix):
        groups[f"suf{i}_{kind}"] = (1, block_specs(kind, cfg, plan))
    return groups


# ===========================================================================
# block application
# ===========================================================================

def _norm(p, x, cfg, name):
    prm = {"gain": p[name + "gain"]}
    if cfg.norm == "ln":
        prm["bias"] = p[name + "bias"]
    return apply_norm(x, prm, cfg.norm)


def apply_block(kind: str, p: Dict, x: jnp.ndarray, *,
                positions, enc_out, cfg: ModelConfig, plan: ShardingPlan,
                policy: CommPolicy, window_override: Optional[int],
                cache: Optional[Dict], layer: Optional[int] = None):
    """-> (x, new_cache, aux_loss)

    ``layer`` is the global block index (prefix + pattern*repeats +
    suffix numbering); every comm site inside the block resolves its
    config at ``(site, layer)``, which is what makes depth-scheduled
    policies bind.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache: Any = {}

    if kind in ("dense", "local", "moe", "enc", "dec"):
        h = _norm(p, x, cfg, "n1_")
        causal = kind != "enc"
        window = cfg.window if kind == "local" else window_override
        a, kv = attn.self_attention(
            p, h, positions, cfg, plan, policy, causal=causal,
            window=window, cache=cache.get("kv") if cache else None,
            layer=layer)
        x = x + a
        if kv is not None:
            new_cache["kv"] = kv
        if kind == "dec":
            h = _norm(p, x, cfg, "n3_")
            x = x + attn.cross_attention(p, h, enc_out, cfg, plan, policy,
                                         prefix="x", layer=layer)
        h = _norm(p, x, cfg, "n2_")
        if kind == "moe":
            f, aux = moe_mod.moe_apply(p, h, cfg, plan, policy,
                                       layer=layer)
        else:
            f = mlp_apply(p, h, cfg.act, policy, cfg.use_bias, layer=layer)
        x = x + f

    elif kind == "xattn":
        h = _norm(p, x, cfg, "n1_")
        x = x + attn.cross_attention(p, h, enc_out, cfg, plan, policy,
                                     prefix="x", layer=layer)
        h = _norm(p, x, cfg, "n2_")
        x = x + mlp_apply(p, h, cfg.act, policy, cfg.use_bias, layer=layer)

    elif kind == "rec":
        h = _norm(p, x, cfg, "n1_")
        a, st = rec_mod.rglru_apply(p, h, cfg, plan, policy,
                                    state=cache.get("rg") if cache else None,
                                    layer=layer)
        x = x + a
        if st is not None:
            new_cache["rg"] = st
        h = _norm(p, x, cfg, "n2_")
        x = x + mlp_apply(p, h, cfg.act, policy, cfg.use_bias, layer=layer)

    elif kind in ("mlstm", "slstm"):
        h = _norm(p, x, cfg, "n1_")
        fn = rec_mod.mlstm_apply if kind == "mlstm" else rec_mod.slstm_apply
        a, st = fn(p, h, cfg, plan, policy,
                   state=cache.get("st") if cache else None, layer=layer)
        x = x + a
        if st is not None:
            new_cache["st"] = st
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def init_block_cache(kind: str, cfg: ModelConfig, plan: ShardingPlan,
                     batch: int, cache_len: int, dtype) -> Dict:
    if kind in ("dense", "local", "moe", "enc", "dec"):
        clen = min(cache_len, cfg.window) if (kind == "local"
                                              and cfg.window) else cache_len
        return {"kv": attn.init_kv_cache(cfg, plan, batch, clen, dtype)}
    if kind == "rec":
        return {"rg": rec_mod.rglru_init_state(cfg, plan, batch)}
    if kind == "mlstm":
        return {"st": rec_mod.mlstm_init_state(cfg, plan, batch)}
    if kind == "slstm":
        return {"st": rec_mod.slstm_init_state(cfg, plan, batch)}
    return {}


# ===========================================================================
# forward
# ===========================================================================

def policy_segments(cfg: ModelConfig, policy: CommPolicy):
    """Split the pattern scan into maximal runs of repeats whose resolved
    layer-site configs are identical -> ``[(start, end), ...)`` repeat
    ranges (end exclusive).

    The scanned pattern executes one traced body for all repeats, so a
    config that varies across repeats can't bind inside a single scan
    (bit widths are shape-determining). Depth-scheduled policies instead
    scan each equal-config segment separately; uniform policies resolve
    to ONE segment, keeping HLO size exactly what it was (O(pattern
    period)). First/last-K schedules cost at most 2 extra segments.
    """
    from repro.core.policy import LAYER_SITES
    r_total = cfg.pattern_repeats
    base, period = len(cfg.prefix), len(cfg.pattern)

    def sig(r):
        return tuple(policy.resolve(site, base + r * period + j)
                     for j in range(period) for site in LAYER_SITES)

    segs, start, cur = [], 0, sig(0)
    for r in range(1, r_total):
        s = sig(r)
        if s != cur:
            segs.append((start, r))
            start, cur = r, s
    segs.append((start, r_total))
    return segs


def _take0(tree):
    """Unstack dim0 of every leaf of a name->array dict (or pass None)."""
    return None if tree is None else {k: v[0] for k, v in tree.items()}


def _encode(views, cfg, plan, policy, enc_embeds, qag, deltas=None):
    """Whisper-style encoder over stub frame embeddings (B, n_ctx, d)."""
    has_deltas = deltas is not None
    gx = views["encoder_extra"]
    specs_x = param_groups(cfg, plan)["encoder_extra"][1]
    px = gather_group({k: v[0] for k, v in gx.items()}, specs_x, plan,
                      enc_embeds.dtype, qag,
                      _take0(deltas["encoder_extra"] if has_deltas
                             else None))
    x = enc_embeds + px["enc_pos"][None, :enc_embeds.shape[1]]
    specs = param_groups(cfg, plan)["encoder"][1]
    pos = jnp.arange(enc_embeds.shape[1])

    def body(carry, xs):
        layer_views, layer_deltas = xs
        p = gather_group(layer_views, specs, plan, enc_embeds.dtype, qag,
                         layer_deltas if has_deltas else None)
        y, _, _ = apply_block("enc", p, carry, positions=pos, enc_out=None,
                              cfg=cfg, plan=plan, policy=policy,
                              window_override=None, cache=None)
        return y, None

    xs = (views["encoder"],
          deltas["encoder"] if has_deltas
          else jnp.zeros((cfg.encoder.n_layers,)))
    x, _ = lax.scan(jax.checkpoint(body), x, xs,
                    unroll=cfg.encoder.n_layers if UNROLL_LAYER_SCAN
                    else 1)
    return _norm(px, x, cfg, "ef_")


def forward(views: Dict, tokens: jnp.ndarray, cfg: ModelConfig,
            plan: ShardingPlan, policy: CommPolicy, *,
            enc_embeds: Optional[jnp.ndarray] = None,
            window_override: Optional[int] = None,
            caches: Optional[Dict] = None,
            grad_deltas: Optional[Dict] = None,
            dtype=jnp.bfloat16):
    """tokens (B_loc, S) -> (hidden (B_loc,S,d), aux, new_caches).

    caches=None -> full-sequence (train/prefill). caches given -> S must
    be 1 (single-token decode step).

    ``grad_deltas`` (train-only) mirrors ``views``' nesting with zero
    full-flat-length leaves; when given, every gathered parameter is
    stop-gradiented and its delta added, so differentiating w.r.t. the
    deltas yields full-length per-rank gradients for the explicit
    post-backward quantized+EF reduce-scatter (see
    ``parallel/shardings.py``). The quantized gradient RS therefore no
    longer lives inside the gather's VJP.
    """
    groups = param_groups(cfg, plan)
    policy = policy.bind(cfg.n_layers)   # depth-addressed schedules
    qag = policy.resolve("qag")
    decode = caches is not None
    has_deltas = grad_deltas is not None

    with jax.named_scope("embed"):
        emb_specs = groups["embed"][1]
        pe = gather_group({k: v[0] for k, v in views["embed"].items()},
                          emb_specs, plan, dtype, qag,
                          _take0(grad_deltas["embed"] if has_deltas else None))
        x = embed_lookup(tokens, pe["tok"], policy, dtype)

        if decode:
            # every attn cache holds the same position counter; take the first
            pos_ref = _first_pos(caches)
            positions = pos_ref
        else:
            positions = jnp.arange(tokens.shape[1])
        if cfg.rope_theta is None and cfg.learned_pos:
            if decode:
                pos_id = jnp.clip(positions, 0, cfg.max_pos - 1)
                x = x + jnp.take(pe["pos"], pos_id[None].astype(jnp.int32),
                                 axis=0).astype(dtype)
            else:
                x = x + pe["pos"][None, :tokens.shape[1]].astype(dtype)

    with jax.named_scope("layers"):
        enc_out = None
        if cfg.is_enc_dec:
            assert enc_embeds is not None
            enc_out = _encode(views, cfg, plan, policy,
                              enc_embeds.astype(dtype), qag, grad_deltas)
        elif cfg.has_cross:
            assert enc_embeds is not None
            enc_out = enc_embeds.astype(dtype)

        aux_total = jnp.zeros((), jnp.float32)
        new_caches: Dict[str, Any] = {}

        def run_one(kind, gname, layer, carry_x, cache):
            specs = groups[gname][1]
            p = gather_group({k: v[0] for k, v in views[gname].items()},
                             specs, plan, dtype, qag,
                             _take0(grad_deltas[gname] if has_deltas
                                    else None))
            return apply_block(kind, p, carry_x, positions=positions,
                               enc_out=enc_out, cfg=cfg, plan=plan,
                               policy=policy, window_override=window_override,
                               cache=cache, layer=layer)

        for i, kind in enumerate(cfg.prefix):
            g = f"pre{i}_{kind}"
            x, nc, aux = jax.checkpoint(
                functools.partial(run_one, kind, g, i))(
                    x, caches.get(g) if decode else None)
            aux_total += aux
            if decode:
                new_caches[g] = nc

        if cfg.pattern_repeats:
            specs = groups["pattern"][1]
            base, period = len(cfg.prefix), len(cfg.pattern)

            def make_body(layer0):
                # layer0: first global block index of the segment; the
                # resolved configs are constant across the segment's
                # repeats, so resolving at layer0 + j binds the right
                # config for every repeat the scan covers.
                def body(carry, xs):
                    cx, caux = carry
                    layer_views, layer_deltas, layer_cache = xs
                    p = gather_group(layer_views, specs, plan, dtype, qag,
                                     layer_deltas if has_deltas else None)
                    ncs = {}
                    for j, kind in enumerate(cfg.pattern):
                        pj = {n[len(f"L{j}_"):]: v for n, v in p.items()
                              if n.startswith(f"L{j}_")}
                        cj = layer_cache.get(f"L{j}") if decode else None
                        cx, nc, aux = apply_block(
                            kind, pj, cx, positions=positions, enc_out=enc_out,
                            cfg=cfg, plan=plan, policy=policy,
                            window_override=window_override, cache=cj,
                            layer=layer0 + j)
                        caux += aux
                        ncs[f"L{j}"] = nc
                    return (cx, caux), ncs
                return body

            xs = (views["pattern"],
                  grad_deltas["pattern"] if has_deltas else
                  jnp.zeros((cfg.pattern_repeats,)),
                  caches["pattern"] if decode else
                  jnp.zeros((cfg.pattern_repeats,)))
            seg_caches = []
            for s, e in policy_segments(cfg, policy):
                xs_seg = xs if (s, e) == (0, cfg.pattern_repeats) else \
                    jax.tree_util.tree_map(lambda a: a[s:e], xs)
                (x, aux_total), pc = lax.scan(
                    jax.checkpoint(make_body(base + s * period)),
                    (x, aux_total), xs_seg,
                    unroll=(e - s) if UNROLL_LAYER_SCAN else 1)
                seg_caches.append(pc)
            if decode:
                new_caches["pattern"] = seg_caches[0] if len(seg_caches) == 1 \
                    else jax.tree_util.tree_map(
                        lambda *cs: jnp.concatenate(cs, axis=0), *seg_caches)

        for i, kind in enumerate(cfg.suffix):
            g = f"suf{i}_{kind}"
            layer = (len(cfg.prefix)
                     + len(cfg.pattern) * cfg.pattern_repeats + i)
            x, nc, aux = jax.checkpoint(
                functools.partial(run_one, kind, g, layer))(
                    x, caches.get(g) if decode else None)
            aux_total += aux
            if decode:
                new_caches[g] = nc

    with jax.named_scope("head"):
        out_specs = groups["out"][1]
        po = gather_group({k: v[0] for k, v in views["out"].items()},
                          out_specs, plan, dtype, qag,
                          _take0(grad_deltas["out"] if has_deltas else None))
        x = _norm(po, x, cfg, "nf_")
        unemb = po["unemb"] if not cfg.tie_embeddings else pe["tok"]
    return x, unemb, aux_total, (new_caches if decode else None)


def _first_pos(caches) -> jnp.ndarray:
    """Current decode position: every attn cache carries the same 'pos'
    counter; recurrent-only models fall back to a zero (rope-free)."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        keys = [getattr(p, "key", None) for p in path]
        if keys and keys[-1] == "pos":
            return leaf.reshape(-1)[0] if leaf.ndim else leaf
    return jnp.zeros((), jnp.int32)


def init_caches(cfg: ModelConfig, plan: ShardingPlan, batch_loc: int,
                cache_len: int, dtype) -> Dict:
    caches: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.prefix):
        caches[f"pre{i}_{kind}"] = init_block_cache(
            kind, cfg, plan, batch_loc, cache_len, dtype)
    if cfg.pattern_repeats:
        one = {f"L{j}": init_block_cache(k, cfg, plan, batch_loc,
                                         cache_len, dtype)
               for j, k in enumerate(cfg.pattern)}
        caches["pattern"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a[None], (cfg.pattern_repeats,) + a.shape).copy(), one)
    for i, kind in enumerate(cfg.suffix):
        caches[f"suf{i}_{kind}"] = init_block_cache(
            kind, cfg, plan, batch_loc, cache_len, dtype)
    return caches


# ===========================================================================
# losses / logits
# ===========================================================================

def lm_loss(hidden: jnp.ndarray, unemb: jnp.ndarray,
            labels: jnp.ndarray, cfg: ModelConfig, plan: ShardingPlan,
            aux: jnp.ndarray, aux_weight: float = 0.01):
    """Vocab-parallel CE averaged over all tokens and ranks."""
    t = hidden.shape[0] * hidden.shape[1]
    h = hidden.reshape(t, -1)
    logits = vocab_parallel_logits(h, unemb, cfg.logit_softcap)
    nll = vocab_parallel_ce(logits, labels.reshape(t), cfg.vocab,
                            plan.v_loc)
    # mean over the global batch: sum here, psum over data/pod in caller
    return jnp.mean(nll) + aux_weight * aux


def greedy_next_token(hidden: jnp.ndarray, unemb: jnp.ndarray,
                      cfg: ModelConfig, plan: ShardingPlan) -> jnp.ndarray:
    """(B,1,d) -> (B,) global argmax over vocab-parallel logits."""
    logits = vocab_parallel_logits(hidden[:, -1], unemb,
                                   cfg.logit_softcap)     # (B, v_loc)
    rank = lax.axis_index("model")
    col = jnp.arange(plan.v_loc)[None, :] + rank * plan.v_loc
    logits = jnp.where(col < cfg.vocab, logits, -jnp.inf)
    loc_val = jnp.max(logits, axis=-1)
    loc_idx = jnp.argmax(logits, axis=-1) + rank * plan.v_loc
    vals = lax.all_gather(loc_val, "model", axis=1)       # (B, tp)
    idxs = lax.all_gather(loc_idx, "model", axis=1)
    best = jnp.argmax(vals, axis=1)
    return jnp.take_along_axis(idxs, best[:, None], axis=1)[:, 0]
