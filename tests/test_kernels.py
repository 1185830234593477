"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode on
CPU; TPU is the target)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.tetris_matmul import select_block_shape

RNG = np.random.RandomState(1)


@pytest.mark.parametrize("mnk", [(256, 256, 256), (384, 128, 512),
                                 (100, 60, 40), (129, 257, 130),
                                 (8, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tetris_matmul_sweep(mnk, dtype):
    m, n, k = mnk
    x = jnp.asarray(RNG.randn(m, k), dtype)
    w = jnp.asarray(RNG.randn(k, n), dtype)
    y = np.asarray(ops.matmul(x, w), np.float32)
    r = np.asarray(ref.matmul_ref(x, w), np.float32)
    tol = 1e-4 * k if dtype == jnp.float32 else 0.2 * np.sqrt(k)
    np.testing.assert_allclose(y, r, atol=tol, rtol=1e-2)


@pytest.mark.parametrize("gmdf", [(4, 64, 32, 48), (8, 128, 64, 64),
                                  (3, 50, 20, 30), (1, 16, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(gmdf, dtype):
    g, m, d, f = gmdf
    x = jnp.asarray(RNG.randn(g, m, d), dtype)
    w = jnp.asarray(RNG.randn(g, d, f), dtype)
    y = np.asarray(ops.gmm(x, w), np.float32)
    r = np.asarray(ref.grouped_matmul_ref(x, w), np.float32)
    tol = 1e-4 * d if dtype == jnp.float32 else 0.2 * np.sqrt(d)
    np.testing.assert_allclose(y, r, atol=tol, rtol=1e-2)


def test_select_block_shape_respects_budget():
    bm, bn, bk = select_block_shape(4096, 4096, 4096, dtype_bytes=2)
    assert (bm * bk + bk * bn) * 2 + bm * bn * 4 <= 8 * 1024 * 1024
    assert bm % 128 == 0 and bn % 128 == 0


# --- flash attention -------------------------------------------------------

@pytest.mark.parametrize("cfg", [(4, 128, 128, 64), (2, 256, 256, 32),
                                 (3, 128, 384, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(cfg, causal):
    bh, sq, sk, d = cfg
    q = jnp.asarray(RNG.randn(bh, sq, d), jnp.float32)
    k = jnp.asarray(RNG.randn(bh, sk, d), jnp.float32)
    v = jnp.asarray(RNG.randn(bh, sk, d), jnp.float32)
    y = np.asarray(ops.attention(q, k, v, causal=causal))
    r = np.asarray(ref.flash_attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(y, r, atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.randn(2, 128, 64), jnp.bfloat16)
    k = jnp.asarray(RNG.randn(2, 128, 64), jnp.bfloat16)
    v = jnp.asarray(RNG.randn(2, 128, 64), jnp.bfloat16)
    y = np.asarray(ops.attention(q, k, v), np.float32)
    r = np.asarray(ref.flash_attention_ref(q, k, v), np.float32)
    np.testing.assert_allclose(y, r, atol=5e-2, rtol=5e-2)


def test_mha_flash_gqa():
    from repro.kernels.flash_attention import mha_flash
    q = jnp.asarray(RNG.randn(2, 128, 8, 32), jnp.float32)
    k = jnp.asarray(RNG.randn(2, 128, 2, 32), jnp.float32)
    v = jnp.asarray(RNG.randn(2, 128, 2, 32), jnp.float32)
    y = mha_flash(q, k, v, interpret=True)
    from repro.models.attention import attention as jax_attn
    r = jax_attn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               atol=2e-4, rtol=1e-3)


# --- ssd chunk kernel ------------------------------------------------------

def test_ssd_chunk_matches_oracle():
    from repro.kernels.ssd_chunk import ssd_chunk
    B, S, H, P, N = 2, 128, 4, 16, 8
    x = jnp.asarray(RNG.randn(B, S, H, P), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.randn(B, S, H)) * 0.1 + 0.05, jnp.float32)
    a_log = jnp.asarray(RNG.randn(H) * 0.3, jnp.float32)
    b = jnp.asarray(RNG.randn(B, S, H, N) * 0.3, jnp.float32)
    c = jnp.asarray(RNG.randn(B, S, H, N) * 0.3, jnp.float32)
    y, s = ssd_chunk(x, dt, a_log, b, c, chunk=S, interpret=True)
    r = ref.ssd_intra_chunk_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r), atol=1e-4)
    # chunked intra parts match per-chunk oracle
    y2, s2 = ssd_chunk(x, dt, a_log, b, c, chunk=32, interpret=True)
    for i in range(S // 32):
        sl = slice(32 * i, 32 * (i + 1))
        ri = ref.ssd_intra_chunk_ref(x[:, sl], dt[:, sl], a_log,
                                     b[:, sl], c[:, sl])
        np.testing.assert_allclose(np.asarray(y2[:, sl]), np.asarray(ri),
                                   atol=1e-4)
    assert s2.shape == (B, S // 32, H, P, N)
