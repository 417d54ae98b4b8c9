"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm
from repro.kernels.rmsnorm import rmsnorm

RNG = np.random.default_rng(42)


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == np.float16 else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,n,k", [(16, 16, 16), (100, 52, 36), (128, 256, 64),
                                   (33, 17, 9), (8, 8, 200)])
@pytest.mark.parametrize("dt", [np.float32])
def test_gemm_shapes(m, n, k, dt):
    x = RNG.normal(size=(m, k)).astype(dt)
    y = RNG.normal(size=(k, n)).astype(dt)
    out = gemm(x, y, block_m=32, block_n=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), x @ y, **_tol(dt))


def test_gemm_bf16():
    x = RNG.normal(size=(64, 48)).astype(np.float32)
    y = RNG.normal(size=(48, 32)).astype(np.float32)
    xb, yb = jnp.asarray(x, jnp.bfloat16), jnp.asarray(y, jnp.bfloat16)
    out = gemm(xb, yb, block_m=32, block_n=32, block_k=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.matmul(xb, yb), np.float32),
        rtol=5e-2, atol=5e-1,
    )


@pytest.mark.parametrize("bh,bkv,sq,skv,causal,window,off", [
    (4, 4, 32, 32, True, None, 0),
    (4, 2, 64, 64, True, None, 0),      # GQA group 2
    (8, 2, 40, 72, True, 16, 0),        # GQA group 4 + SWA
    (2, 1, 8, 128, True, None, 120),    # decode-like offset
    (2, 2, 48, 48, False, None, 0),     # bidirectional (encoder)
    (2, 2, 17, 33, True, 8, 0),         # ragged, non-multiple shapes
])
def test_flash_attention_sweep(bh, bkv, sq, skv, causal, window, off):
    d = 32
    q = RNG.normal(size=(bh, sq, d)).astype(np.float32)
    k = RNG.normal(size=(bkv, skv, d)).astype(np.float32)
    v = RNG.normal(size=(bkv, skv, d)).astype(np.float32)
    got = flash_attention(q, k, v, causal=causal, window=window, q_offset=off,
                          block_q=16, block_k=16, interpret=True)
    want = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("r,d", [(8, 64), (100, 96), (256, 128), (5, 32)])
def test_rmsnorm_sweep(r, d):
    x = RNG.normal(size=(r, d)).astype(np.float32)
    g = RNG.normal(size=(d,)).astype(np.float32)
    got = rmsnorm(x, g, block_r=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.rmsnorm(x, g)), rtol=1e-5, atol=1e-5
    )


def test_einsum2_contraction_patterns():
    a = RNG.normal(size=(24, 12)).astype(np.float32)
    b = RNG.normal(size=(12, 30)).astype(np.float32)
    got = ops.einsum2("ab", "bc", "ac", jnp.asarray(a), jnp.asarray(b), tile=(16, 16, 16))
    np.testing.assert_allclose(np.asarray(got), a @ b, rtol=2e-4, atol=2e-4)
    got = ops.einsum2("ab", "cb", "ca", jnp.asarray(a), jnp.asarray(b.T.copy()),
                      tile=(16, 16, 16))
    np.testing.assert_allclose(np.asarray(got), (a @ b).T, rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError):
        ops.einsum2("ab", "bc", "abc", jnp.asarray(a), jnp.asarray(b))  # batch letter


def test_ops_backend_switch():
    x = RNG.normal(size=(32, 16)).astype(np.float32)
    y = RNG.normal(size=(16, 8)).astype(np.float32)
    a = ops.matmul(x, y, backend="xla")
    b = ops.matmul(x, y, backend="pallas_interpret", tile=(16, 8, 16))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sq,skv,causal,window,off", [
    (64, 64, True, None, 0), (100, 200, True, 32, 0),
    (33, 128, False, None, 0), (8, 96, True, None, 88),
])
def test_chunked_attention_matches_plain(sq, skv, causal, window, off):
    q = RNG.normal(size=(4, sq, 16)).astype(np.float32)
    k = RNG.normal(size=(2, skv, 16)).astype(np.float32)
    v = RNG.normal(size=(2, skv, 16)).astype(np.float32)
    want = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         causal=causal, window=window, q_offset=off)
    got = ref.attention_chunked(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                causal=causal, window=window, q_offset=off,
                                block_q=16, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
