"""The plain reference's own arithmetic."""
import ml_dtypes
import numpy as np

import jax.numpy as jnp

from chipbench.references import dense_gqa


def test_round_e4m3_matches_float8():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.standard_normal(20000) * s
                        for s in (1e-3, 1e-2, 1.0, 30.0, 300.0)])
    x = np.concatenate([x, [0.0, 448.0, -448.0, 2.0 ** -9, 2.0 ** -6,
                            3 * 2.0 ** -10]]).astype(np.float32)
    # saturating at 448, where a plain float8 cast overflows to NaN
    want = np.clip(x, -448, 448).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32)
    got = np.asarray(dense_gqa.round_e4m3(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


def test_fp8_matmul_is_coarser_than_float32():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((64, 256)).astype(np.float32)
    w = (rng.standard_normal((256, 128)) / 16).astype(np.float32)
    exact = x.astype(np.float64) @ w.astype(np.float64)
    f32 = np.asarray(dense_gqa.matmul(jnp.asarray(x), jnp.asarray(w),
                                      "float32"))
    f8 = np.asarray(dense_gqa.matmul(jnp.asarray(x), jnp.asarray(w), "fp8"))
    e32 = np.abs(f32 - exact).max()
    e8 = np.abs(f8 - exact).max()
    assert e32 < 1e-4 < 1e-2 < e8 < 0.5


def test_attention_is_causal():
    rng = np.random.default_rng(2)
    arch = dense_gqa.Arch(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          d_ff=128, vocab=32, n_layers=1, norm_eps=1e-6,
                          rope_theta=1e4, qk_norm=True)
    q, k, v = (jnp.asarray(rng.standard_normal((1500, h, 16)), jnp.float32)
               for h in (4, 2, 2))
    out = dense_gqa.causal_attention(q, k, v, arch)
    k2 = k.at[1200:].set(7.0)
    out2 = dense_gqa.causal_attention(q, k2, v, arch)
    np.testing.assert_array_equal(np.asarray(out[:1200]),
                                  np.asarray(out2[:1200]))
    assert not np.allclose(out[1200:], out2[1200:])
