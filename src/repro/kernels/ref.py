"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(x, w, preferred_element_type=jnp.float32
                   ).astype(x.dtype)


def grouped_matmul_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x (G, M, D), w (G, D, F) -> (G, M, F): block-diagonal matmul."""
    return jnp.einsum("gmd,gdf->gmf", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def ssd_intra_chunk_ref(x, dt, a_log, b, c) -> jnp.ndarray:
    """Intra-chunk SSD (no inter-chunk state): x (B,L,H,P); dt (B,L,H);
    a_log (H,); b,c (B,L,H,N).  y[i] = sum_{j<=i} C_i.B_j exp(dA(j,i]) x_j dt_j."""
    f32 = jnp.float32
    A = -jnp.exp(a_log.astype(f32))
    dA = dt.astype(f32) * A                                   # (B,L,H)
    cs = jnp.cumsum(dA, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]               # (B,L,L,H)
    L = x.shape[1]
    mask = jnp.tril(jnp.ones((L, L), bool))
    dec = jnp.where(mask[None, :, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bihn,bjhn->bijh", c.astype(f32), b.astype(f32))
    xdt = x.astype(f32) * dt.astype(f32)[..., None]
    y = jnp.einsum("bijh,bijh,bjhp->bihp", cb, dec, xdt)
    return y.astype(x.dtype)


def flash_attention_ref(q, k, v, causal: bool = True,
                        q_offset: int = 0) -> "jnp.ndarray":
    """q (BH, Sq, D); k/v (BH, Sk, D): plain softmax attention oracle."""
    import math
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        pos_q = q_offset + jnp.arange(sq)[:, None]
        pos_k = jnp.arange(sk)[None, :]
        s = jnp.where(pos_k <= pos_q, s, -1e30)
    w = jax.nn.softmax(s, -1)
    return jnp.einsum("bqk,bkd->bqd", w.astype(v.dtype), v)
