"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,K,hd,T,window",
    [
        (2, 128, 4, 2, 64, 128, None),
        (1, 256, 8, 8, 32, 256, None),     # MHA
        (2, 128, 4, 1, 64, 128, None),     # MQA
        (1, 128, 6, 2, 128, 128, 64),      # sliding window
        (1, 64, 2, 2, 16, 64, 16),
    ],
)
def test_flash_attention_sweep(B, S, H, K, hd, T, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, T, K, hd), dtype)
    v = _rand(ks[2], (B, T, K, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kv_len", [1, 37, 100, 512])
@pytest.mark.parametrize("window", [None, 64])
def test_decode_attention_sweep(kv_len, window, dtype):
    B, H, K, hd, T = 2, 8, 4, 64, 512
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (B, 1, H, hd), dtype)
    k = _rand(ks[1], (B, T, K, hd), dtype)
    v = _rand(ks[2], (B, T, K, hd), dtype)
    out = ops.decode_attention(q, k, v, kv_len=kv_len, window=window, block_k=128)
    want = ref.decode_attention_ref(q, k, v, kv_len=kv_len, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


@pytest.mark.parametrize("B,nc,Q,H,N,P", [
    (2, 4, 32, 8, 16, 16),
    (1, 2, 64, 4, 64, 64),
    (1, 1, 128, 2, 32, 64),
])
def test_ssd_intra_chunk_sweep(B, nc, Q, H, N, P):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    la = -jnp.abs(jax.random.normal(ks[0], (B, nc, Q, H))) * 0.1
    C = jax.random.normal(ks[1], (B, nc, Q, N))
    Bm = jax.random.normal(ks[2], (B, nc, Q, N))
    x = jax.random.normal(ks[3], (B, nc, Q, H, P))
    y, st, tot = ops.ssd_intra_chunk(la, C, Bm, x)
    yr, str_, totr = ref.ssd_intra_chunk_ref(la, C, Bm, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(tot), np.asarray(totr), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64, 256), (2, 33, 128), (1, 1, 512)])
def test_rmsnorm_sweep(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = _rand(ks[0], shape, dtype)
    s = _rand(ks[1], shape[-1:], dtype)
    out = ops.rmsnorm(x, s)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


def test_flash_attention_grads_flow():
    """The kernel sits on the fwd path only in serving; training uses the
    blockwise jnp path — but interpret-mode kernels must still be jittable
    inside larger graphs."""
    q = jnp.ones((1, 64, 2, 32))
    k = jnp.ones((1, 64, 2, 32))
    v = jnp.ones((1, 64, 2, 32))

    @jax.jit
    def f(q):
        return ops.flash_attention(q, k, v, block_q=32, block_k=32).sum()

    assert jnp.isfinite(f(q))
