"""The serving read of the weight store equals the training path's, bit
for bit.

Serving (``forward(..., serving=True)`` with ``fsdp == 1``) reads each
tensor through ``shardings.serve_param``: on the TPU the
``unflatten_cast`` kernel for tensors it fits, and an untied embedding
gathers only the tokens' rows (``layers.embed_rows``). Here the kernel
runs in interpret mode, steered onto the kernel path as the TPU would
take it; the TPU compile of the same path is in test_tpu_compile.py.
"""
import functools
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core.policy import BF16_POLICY
from repro.kernels import unflatten_cast as uc
from repro.launch.mesh import make_test_mesh
from repro.models.layers import embed_lookup, embed_rows
from repro.models.model import forward, param_groups
from repro.parallel import shardings
from repro.parallel.plan import make_plan
from repro.parallel.shardings import STORE_SPEC, build_store

# serving paths that differ: dense GQA, GLM's block, MoE experts,
# learned positions with an encoder
ARCHS = ["qwen3-14b", "glm4-9b", "moonshot-v1-16b-a3b", "whisper-tiny"]
SCRIPT = os.path.join(os.path.dirname(__file__), "_multidev_script.py")


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.fixture
def kernel_path(monkeypatch):
    """Take the TPU's choice of path, with the kernel interpreted and
    taking tensors of any size (smoke widths are all under one block);
    counts the kernel calls."""
    calls = []
    monkeypatch.setattr(uc, "_MIN_VALUES", 0)

    def interpreted(*args, **kw):
        calls.append(kw["shape"])
        return uc.unflatten_cast(*args, interpret=True, **kw)

    monkeypatch.setattr(shardings, "_backend", lambda: "tpu")
    monkeypatch.setattr(shardings, "unflatten_cast", interpreted)
    return calls


# ---------------------------------------------------------------------------
# the kernel alone
# ---------------------------------------------------------------------------

SPECIALS = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan,
                     1 + 2 ** -8, 1 + 3 * 2 ** -8, -(1 + 2 ** -8),
                     1 + 2 ** -8 + 2 ** -20, 3.4e38, -3.4e38, 1e-40,
                     -1e-45, 2 ** -126, 2 ** -133], np.float32)


def _stack(n_stack, shape, seed=0):
    n = math.prod(shape)
    flat = -(-n // 1024) * 1024
    st = np.zeros((n_stack, 1, flat), np.float32)
    st[:, :, :n] = np.random.default_rng(seed).standard_normal(
        (n_stack, 1, n), dtype=np.float32)
    # specials at the start, across a block boundary and at the end
    for at in (0, n // 2 - 5, n - SPECIALS.size):
        st[:, 0, at:at + SPECIALS.size] = SPECIALS
    return st, n


@pytest.mark.parametrize("shape,tr", [
    ((40, 256), 16),         # 40 rows: the last block is partial
    ((2, 24, 384), 16),      # leading dimensions fold into rows
    ((64, 128), None),       # one block, the default size
    ((21, 1152), 16),        # a row not a whole number of 1024 values
])
def test_unflatten_cast_equals_astype(shape, tr):
    st, n = _stack(2, shape)
    for layer in range(2):
        got = uc.unflatten_cast(jnp.asarray(st), layer, shape=shape,
                                dtype=jnp.bfloat16, tr=tr, interpret=True)
        want = jnp.asarray(st[layer, 0, :n]).reshape(shape).astype(
            jnp.bfloat16)
        assert got.shape == shape and got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(_bits(got), _bits(want))


def test_unflatten_cast_in_scan():
    """The stacked store closed over by a layer scan, the layer a traced
    index: each step reads its own layer."""
    shape = (48, 256)
    st, n = _stack(3, shape, seed=1)

    @jax.jit
    def run(stack):
        def body(c, i):
            w = uc.unflatten_cast(stack, i, shape=shape, dtype=jnp.bfloat16,
                                  tr=16, interpret=True)
            return c, w
        return lax.scan(body, 0, jnp.arange(3))[1]

    got = run(jnp.asarray(st))
    want = jnp.asarray(st[:, 0, :n]).reshape((3,) + shape).astype(
        jnp.bfloat16)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_fits(monkeypatch):
    assert uc.fits((5120, 17408))
    assert uc.fits((8, 128, 1024))
    assert not uc.fits((5120,))                      # 1-D gain
    assert not uc.fits((5120, 64))                   # unaligned last dim
    assert not uc.fits((256, 512))                   # under one block
    monkeypatch.setattr(uc, "_MIN_VALUES", 0)
    assert uc.fits((256, 512))
    assert not uc.fits((8, 128))                     # under 16 rows


# ---------------------------------------------------------------------------
# every tensor of the serving store
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_serve_param_bit_identical(arch, kernel_path):
    cfg = get_smoke_config(arch)
    plan = make_plan(cfg, tp=1, fsdp=1)
    groups = param_groups(cfg, plan)
    store = build_store(groups, plan, jax.random.PRNGKey(3), jnp.float32)
    for gname, (n_stack, specs) in groups.items():
        for name, spec in specs.items():
            stack = store[gname][name]
            shape = spec.local_shape(plan)
            n = math.prod(shape)
            for layer in range(n_stack):
                got = jax.jit(functools.partial(
                    shardings.serve_param, spec=spec, plan=plan,
                    dtype=jnp.bfloat16))(stack, layer=jnp.int32(layer))
                want = stack[layer, 0, :n].reshape(shape).astype(
                    jnp.bfloat16)
                np.testing.assert_array_equal(
                    _bits(got), _bits(want), err_msg=f"{gname}/{name}")
    assert kernel_path, f"{arch}: no tensor took the kernel"


@pytest.mark.parametrize("arch", ARCHS)
def test_serving_forward_bit_identical(arch, kernel_path):
    """A serving decode step through the kernel and the row gather gives
    the training path's hidden state and head weights bit for bit."""
    cfg = get_smoke_config(arch)
    plan = make_plan(cfg, tp=1, fsdp=1)
    mesh = make_test_mesh()
    store = build_store(param_groups(cfg, plan), plan,
                        jax.random.PRNGKey(4), jnp.float32)
    toks = jnp.asarray([[5], [cfg.vocab - 1]], jnp.int32)
    enc = None
    if cfg.is_enc_dec or cfg.has_cross:
        enc = jax.random.normal(jax.random.PRNGKey(5),
                                (2, cfg.encoder.n_ctx, cfg.d_model))

    def run(serving):
        def f(views, tokens, enc_embeds):
            hidden, unemb, _, _ = forward(views, tokens, cfg, plan,
                                          BF16_POLICY, enc_embeds=enc_embeds,
                                          serving=serving)
            return hidden, unemb
        sm = jax.shard_map(f, mesh=mesh, in_specs=(STORE_SPEC, P(), P()),
                           out_specs=(P(), P()), check_vma=False)
        return jax.jit(sm)(store, toks, enc)

    h_train, u_train = run(False)
    assert not kernel_path
    h_serve, u_serve = run(True)
    assert kernel_path
    np.testing.assert_array_equal(_bits(h_serve), _bits(h_train))
    np.testing.assert_array_equal(_bits(u_serve), _bits(u_train))


# ---------------------------------------------------------------------------
# the row-gather embedding
# ---------------------------------------------------------------------------

def test_embed_rows_equals_lookup_tp1():
    mesh = make_test_mesh()
    v, d = 300, 256
    flat = np.zeros(v * d + 512, np.float32)
    flat[:v * d] = np.random.default_rng(6).standard_normal(v * d) * 3
    flat[d:d + SPECIALS.size] = SPECIALS
    toks = jnp.asarray([[0, 1, 299], [150, 1, 7]], jnp.int32)

    def f(flat, tokens):
        table = flat[:v * d].reshape(v, d).astype(jnp.bfloat16)
        return (embed_rows(tokens, flat, v, d, BF16_POLICY, jnp.bfloat16),
                embed_lookup(tokens, table, BF16_POLICY, jnp.bfloat16))

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    got, want = jax.jit(sm)(jnp.asarray(flat), toks)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_embed_rows_equals_lookup_tp4():
    """Four CPU devices, TP=4: ids in every rank's slice and in the
    padded vocabulary."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(SCRIPT), "..", "src"))
    r = subprocess.run([sys.executable, SCRIPT, "embed_rows"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "embed_rows ok" in r.stdout
