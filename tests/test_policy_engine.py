"""Policy engine tests: schedule resolution, map/resolve commutation,
JSON round-trip, shipped policy artifacts, segmentation, and the
error-feedback compressed psum properties.

Covers the PR-5 property wall:
  (a) resolving a schedule per-layer then mapping with with_backend /
      with_scheme equals mapping first then resolving,
  (b) EF-compressed psum over K fake steps has bounded accumulated
      error vs the exact psum and beats no-EF at 2/4 bit,
  (c) policy JSON round-trips (loads(dumps(p)) == p),
plus the fast CI check that every shipped configs/policies/*.json
loads, resolves for a 4-layer model, and describes without error.
"""
import dataclasses
import glob
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _hyp import given, settings, st
from repro.core.codec import qdq_wire
from repro.core.collectives import compressed_psum, compressed_psum_ef
from repro.core.comm_config import (CommConfig, NO_COMPRESSION,
                                    default_comm_config)
from repro.core.policy import (BF16_POLICY, CommPolicy, LAYER_SITES, SITES,
                               aggressive_policy, depth_interp,
                               depth_policy, describe_policy, first_last_k,
                               load_policy_file, optimized_policy,
                               paper_policy, per_layer, policy_from_json,
                               policy_to_json, uniform, with_backend,
                               with_scheme)
from repro.launch.mesh import make_test_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ===========================================================================
# schedule resolution
# ===========================================================================

def test_uniform_spellings_unchanged():
    """The old flat CommPolicy spellings keep working: stock policies
    resolve the same configs at every layer that the flat fields held,
    and attribute access reads through uniform schedules."""
    p = paper_policy()
    for layer in (None, 0, 3, 31):
        assert p.resolve("tp", layer, 32) == default_comm_config(8)
        assert p.resolve("a2a", layer, 32) == default_comm_config(4)
    assert p.resolve("qag") is None
    assert p.tp.bits == 8 and p.tp.backend == "auto"
    assert p.grad.scheme == "hierarchical"
    pb = with_backend(p, "pallas")
    assert pb.tp.backend == "pallas" and pb.grad.backend == "pallas"
    ps = with_scheme(p, "fused")
    assert ps.tp.scheme == "fused" and ps.a2a.scheme == "fused"
    # CommConfig / None promote to uniform schedules (old constructor)
    flat = CommPolicy(tp=CommConfig(bits=5), qag=None)
    assert flat.resolve("tp", 7, 12) == CommConfig(bits=5)


def test_first_last_schedule():
    hi, lo = default_comm_config(8), default_comm_config(4)
    p = CommPolicy(tp=first_last_k(hi, lo, k=2))
    got = [p.resolve("tp", i, 8) for i in range(8)]
    assert got == [hi, hi, lo, lo, lo, lo, hi, hi]
    # representative (layer=None) is the mid config
    assert p.resolve("tp") == lo


def test_per_layer_schedule_clamps():
    cfgs = [default_comm_config(b) for b in (8, 6, 4)]
    p = CommPolicy(tp=per_layer(cfgs))
    assert [p.resolve("tp", i, 6).bits for i in range(6)] == \
        [8, 6, 4, 4, 4, 4]


def test_depth_interp_schedule():
    base = default_comm_config(8, scale_int=True, backend="ref")
    p = CommPolicy(tp=depth_interp(base, 8, 2))
    got = [p.resolve("tp", i, 7) for i in range(7)]
    assert got[0].bits == 8 and got[-1].bits == 2
    bits = [c.bits for c in got]
    assert bits == sorted(bits, reverse=True)     # monotone over depth
    for c in got:
        # transport knobs carry over; group/spike follow paper defaults
        assert c.scale_int and c.backend == "ref"
        assert c.group == (128 if c.bits >= 5 else 32)
        assert c.spike == (c.bits <= 2)


def test_resolve_needs_depth_for_depth_schedules():
    p = CommPolicy(tp=first_last_k(default_comm_config(8),
                                   default_comm_config(4)))
    with pytest.raises(AssertionError):
        p.resolve("tp", 3)          # unbound depth
    assert p.bind(8).resolve("tp", 3) == default_comm_config(4)


# ===========================================================================
# (a) map/resolve commutation (property)
# ===========================================================================

_CFG_POOL = (default_comm_config(8), default_comm_config(4),
             default_comm_config(2, scale_int=True),
             CommConfig(bits=5, group=32, spike=True, scheme="hier_pp"),
             NO_COMPRESSION)


def _mk_schedule(kind_i, a, b, k):
    ca, cb = _CFG_POOL[a], _CFG_POOL[b]
    return [uniform(ca),
            first_last_k(ca, cb, k=k),
            per_layer([ca, cb, ca]),
            depth_interp(ca if ca.enabled else _CFG_POOL[0], 8, 2),
            ][kind_i]


@settings(max_examples=40)
@given(kind_i=st.integers(0, 3), a=st.integers(0, 4), b=st.integers(0, 4),
       k=st.integers(1, 3), n_layers=st.integers(1, 9),
       backend=st.sampled_from(["ref", "pallas", "auto"]),
       scheme=st.sampled_from(["nccl", "two_step", "fused", "hier_pp"]))
def test_map_commutes_with_resolve(kind_i, a, b, k, n_layers, backend,
                                   scheme):
    """schedule.map(f).resolve(l) == f(schedule.resolve(l)) — and hence
    with_backend/with_scheme applied to a whole policy equal applying
    them to every resolved per-layer config."""
    sched = _mk_schedule(kind_i, a, b, k)
    pol = CommPolicy(tp=sched).bind(n_layers)
    for fn, mapped in (
            (lambda c: c.with_backend(backend) if c.enabled else c,
             with_backend(pol, backend)),
            (lambda c: c.with_scheme(scheme) if c.enabled else c,
             with_scheme(pol, scheme))):
        for layer in list(range(n_layers)) + [None]:
            want = pol.resolve("tp", layer)
            want = fn(want) if want is not None else None
            assert mapped.resolve("tp", layer) == want, (layer, sched)


# ===========================================================================
# (c) JSON round trip
# ===========================================================================

@pytest.mark.parametrize("mk", [paper_policy, optimized_policy,
                                aggressive_policy, depth_policy,
                                lambda: BF16_POLICY])
def test_policy_json_roundtrip_stock(mk):
    p = mk()
    assert policy_from_json(policy_to_json(p)) == p


def test_policy_json_roundtrip_all_schedule_kinds():
    p = CommPolicy(
        tp=first_last_k(default_comm_config(8), default_comm_config(4),
                        k=2),
        a2a=per_layer([default_comm_config(4),
                       default_comm_config(2, scale_int=True)]),
        grad=depth_interp(default_comm_config(8, scheme="hier_pp"), 8, 3),
        qag=uniform(default_comm_config(8)),
        qgrad_rs=None, tp_bwd=None, ep_slice=True, grad_ef=True)
    assert policy_from_json(policy_to_json(p)) == p


def test_policy_json_rejects_unknown_fields():
    with pytest.raises(AssertionError):
        policy_from_json('{"sites": {"bogus_site": null}}')
    with pytest.raises(AssertionError):
        policy_from_json(
            '{"sites": {"tp": {"schedule": "uniform", '
            '"config": {"bogus_field": 1}}}}')


# ===========================================================================
# shipped policy artifacts (the fast CI check) + describe
# ===========================================================================

def test_shipped_policy_files_load_and_describe():
    files = sorted(glob.glob(os.path.join(REPO, "configs", "policies",
                                          "*.json")))
    assert len(files) >= 2, "expected shipped policy artifacts"
    for path in files:
        pol = load_policy_file(path).bind(4)        # 4-layer model
        for site in SITES:
            for layer in (None, 0, 1, 2, 3):
                pol.resolve(site, layer)            # must not raise
        text = describe_policy(pol, 4)
        assert "site" in text and "tp" in text and "grad" in text


def test_describe_policy_groups_layer_ranges():
    text = describe_policy(depth_policy(), 8)
    assert "1-6" in text            # the mid range collapses to one row
    assert "grad_ef" in text
    # wire accounting comes from the real layout: INT4 g32 on 4096 nums
    assert str(default_comm_config(4).wire_bytes(4096)) in text


# ===========================================================================
# pattern-scan segmentation
# ===========================================================================

def test_policy_segments():
    from repro.configs import get_smoke_config
    from repro.models.model import policy_segments
    cfg = dataclasses.replace(get_smoke_config("qwen3-14b"),
                              pattern_repeats=6)
    r = cfg.pattern_repeats
    # uniform policy -> one segment (HLO stays O(pattern period))
    assert policy_segments(cfg, paper_policy().bind(cfg.n_layers)) == \
        [(0, r)]
    # depth-scheduled -> exactly [edge | mid | edge]
    pol = depth_policy(k=1).bind(cfg.n_layers)
    assert policy_segments(cfg, pol) == [(0, 1), (1, 5), (5, 6)]
    # a depth so shallow every layer is an edge collapses back to one
    shallow = get_smoke_config("qwen3-14b")        # 2 repeats, k=1
    assert policy_segments(
        shallow, depth_policy(k=1).bind(shallow.n_layers)) == [(0, 2)]


# ===========================================================================
# (b) error-feedback compressed psum
# ===========================================================================

def _ef_stream_errors(bits, steps=16, n=512):
    """Accumulated-sum error trajectories with and without EF on a
    1-device mesh (psum == identity, so the error is purely the
    compressor's — the EF mechanics under test)."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(bits)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def step_ef(g, e):
        return compressed_psum_ef(g, e, ("model",), cfg)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(),
             out_specs=P(), check_vma=False)
    def step_plain(g):
        return compressed_psum(g, ("model",), cfg)

    step_ef = jax.jit(step_ef)          # cache the trace across steps
    step_plain = jax.jit(step_plain)
    rng = np.random.default_rng(0)
    # a fixed "gradient" with a slowly varying component: the regime
    # where naive low-bit quantization bias accumulates linearly
    base = rng.standard_normal(n).astype(np.float32)
    ef_err, plain_err = [], []
    e = jnp.zeros((n,), jnp.float32)
    acc_ef = np.zeros(n, np.float64)
    acc_plain = np.zeros(n, np.float64)
    acc_exact = np.zeros(n, np.float64)
    for t in range(steps):
        g = jnp.asarray(base * (1.0 + 0.01 * t))
        out_ef, e = step_ef(g, e)
        out_plain = step_plain(g)
        acc_ef += np.asarray(out_ef, np.float64)
        acc_plain += np.asarray(out_plain, np.float64)
        acc_exact += np.asarray(g, np.float64)
        ef_err.append(float(np.linalg.norm(acc_ef - acc_exact)))
        plain_err.append(float(np.linalg.norm(acc_plain - acc_exact)))
    return np.asarray(ef_err), np.asarray(plain_err)


@pytest.mark.parametrize("bits", [2, 4])
def test_ef_psum_bounded_and_beats_plain(bits):
    ef_err, plain_err = _ef_stream_errors(bits)
    # EF: the applied-sum error equals the current residual, which is
    # bounded by one step's quantization error — it must NOT grow with
    # the horizon (monotonically bounded), while the no-EF error drifts.
    assert ef_err[-1] <= ef_err.max() <= 2.0 * ef_err[0] + 1e-6, ef_err
    assert ef_err[-1] < plain_err[-1], (bits, ef_err[-1], plain_err[-1])
    # and the gap is structural, not noise: plain drift keeps growing
    assert plain_err[-1] > plain_err[len(plain_err) // 2]


def test_ef_residual_is_local_qdq_error():
    """One EF step's residual == xe - QDQ(xe) with the site's own wire
    format (phase-1 error, exactly)."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(4)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(256),
                    jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def f(g, e):
        return compressed_psum_ef(g, e, ("model",), cfg)

    out, res = f(x, jnp.zeros_like(x))
    want = np.asarray(x) - np.asarray(qdq_wire(x, cfg))
    np.testing.assert_allclose(np.asarray(res), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(qdq_wire(x, cfg)),
                               atol=1e-6)


def test_ef_psum_grad_exact():
    """The EF path's VJP is the exact psum transpose (straight-through),
    matching compressed_psum's gradient contract."""
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(4)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(128),
                    jnp.float32)
    e0 = jnp.zeros_like(x)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=P(), check_vma=False)
    def loss_sm(g, e):
        out, _ = compressed_psum_ef(g, e, ("model",), cfg)
        return jnp.sum(out)[None]

    grad = jax.grad(lambda v: loss_sm(v, e0)[0])(x)
    np.testing.assert_allclose(np.asarray(grad), np.ones(128), atol=1e-6)


def test_ef_reduce_scatter_residual():
    """quantized_reduce_scatter_ef: chunk output + input-shaped residual
    equal to the local phase-1 QDQ error (the scatter-shaped ZeRO++
    gradient site's EF contract)."""
    from repro.core.collectives import quantized_reduce_scatter_ef
    mesh = make_test_mesh(data=1, model=1)
    cfg = default_comm_config(4)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(256),
                    jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def f(g, e):
        return quantized_reduce_scatter_ef(g, e, "model", cfg)

    out, res = f(x, jnp.zeros_like(x))
    qdq = np.asarray(qdq_wire(x, cfg))
    np.testing.assert_allclose(np.asarray(out), qdq, atol=1e-6)
    np.testing.assert_allclose(np.asarray(res), np.asarray(x) - qdq,
                               atol=1e-6)
    # grad: exact all_gather transpose for both inputs
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=P(), check_vma=False)
    def loss_sm(g, e):
        out, _ = quantized_reduce_scatter_ef(g, e, "model", cfg)
        return jnp.sum(out)[None]

    grad = jax.grad(lambda v: loss_sm(v, jnp.zeros_like(x))[0])(x)
    np.testing.assert_allclose(np.asarray(grad), np.ones(256), atol=1e-6)


def test_ef_disabled_site_passthrough():
    mesh = make_test_mesh(data=1, model=1)
    x = jnp.arange(64, dtype=jnp.float32)
    e0 = jnp.full((64,), 0.5, jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def f(g, e):
        return compressed_psum_ef(g, e, ("model",), NO_COMPRESSION)

    out, res = f(x, e0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(res), np.asarray(e0))


# ===========================================================================
# resolver-routed pod grad config (the old hardcoded override)
# ===========================================================================

def test_pod_grad_config_keeps_scheme():
    from repro.train.train_step import pod_grad_config
    pol = aggressive_policy()            # grad scheme = hier_pp
    assert pod_grad_config(pol).scheme == "hier_pp"
    assert pod_grad_config(BF16_POLICY) == NO_COMPRESSION
    # depth-addressed grad schedules resolve at the representative
    pol2 = CommPolicy(grad=per_layer([default_comm_config(2)]))
    assert pod_grad_config(pol2).bits == 2


def test_wants_grad_ef():
    from repro.train.train_step import wants_grad_ef
    pod_mesh = make_test_mesh(data=1, model=1, pod=1)
    flat_mesh = make_test_mesh(data=1, model=1)
    assert wants_grad_ef(depth_policy(), pod_mesh)
    assert not wants_grad_ef(depth_policy(), flat_mesh)   # no pod axis
    assert not wants_grad_ef(paper_policy(), pod_mesh)    # no grad_ef
    off = dataclasses.replace(BF16_POLICY, grad_ef=True)
    assert not wants_grad_ef(off, pod_mesh)               # grad disabled


def test_single_axis_hier_pp_pipelines():
    """hier_pp over one axis batches microchunks through one two-step
    schedule — each microchunk quantized with its own groups (vs the
    flat two_step's whole-vector chunking), and the result still a
    valid psum on a 1-rank axis (QDQ identity-sum)."""
    mesh = make_test_mesh(data=1, model=1)
    n = 1024
    cfg = default_comm_config(4, scheme="hier_pp")
    x = jnp.asarray(np.random.default_rng(3).standard_normal(n),
                    jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
             check_vma=False)
    def f(g):
        return compressed_psum(g, ("model",), cfg)

    out = np.asarray(f(x))
    chunks = cfg.pipeline_chunks
    want = np.asarray(qdq_wire(x.reshape(chunks, n // chunks), cfg)
                      ).reshape(n)
    np.testing.assert_allclose(out, want, atol=1e-6)
