"""SSD intra-chunk Pallas kernel (mamba2 hot spot, arXiv:2405.21060).

One grid cell = one (batch, chunk): computes the chunk's masked-decay
attention form entirely in VMEM —

    y[i] = sum_{j<=i} (C_i . B_j) * exp(cumsum dA (j, i]) * dt_j * x[j]

plus the chunk-final state S = sum_j B_j exp(dA_end - dA_j) dt_j x[j]
that the host-side inter-chunk scan consumes (repro.models.ssm does the
O(n_chunks) recurrence; the quadratic work lives here).  The decay matrix
and segment sums never touch HBM — the same traffic argument as flash
attention, applied to the SSD dual form.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, s_ref):
    x = x_ref[0].astype(jnp.float32)          # (L, H, P)
    dt = dt_ref[0].astype(jnp.float32)        # (L, H)
    a = -jnp.exp(alog_ref[...].astype(jnp.float32))   # (H,)
    b = b_ref[0].astype(jnp.float32)          # (L, H, N)
    c = c_ref[0].astype(jnp.float32)          # (L, H, N)
    L = x.shape[0]

    dA = dt * a[None, :]                      # (L, H)
    cs = jnp.cumsum(dA, axis=0)               # (L, H)
    seg = cs[:, None, :] - cs[None, :, :]     # (L, L, H): sum (j, i]
    mask = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    dec = jnp.where(mask[..., None], jnp.exp(seg), 0.0)   # (L, L, H)

    cb = jnp.einsum("ihn,jhn->ijh", c, b)     # (L, L, H)
    xdt = x * dt[..., None]                   # (L, H, P)
    y = jnp.einsum("ijh,jhp->ihp", cb * dec, xdt)
    y_ref[0] = y.astype(y_ref.dtype)

    # chunk-final state for the host-side recurrence
    dec_end = jnp.exp(cs[-1][None, :] - cs)   # (L, H)
    s = jnp.einsum("jhn,jh,jhp->hpn", b, dec_end, xdt)
    s_ref[0] = s.astype(s_ref.dtype)


def ssd_chunk(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
              b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 128,
              interpret: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, H, P); dt (B, S, H) post-softplus; a_log (H,);
    b/c (B, S, H, N) (groups pre-repeated).  S % chunk == 0.
    Returns (y_intra (B,S,H,P), states (B, n_chunks, H, P, N))."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    if S % chunk:
        raise ValueError(f"S {S} % chunk {chunk} != 0")
    nc = S // chunk

    xr = x.reshape(B * nc, chunk, H, P)
    dtr = dt.reshape(B, nc, chunk, H).reshape(B * nc, chunk, H)
    br = b.reshape(B, nc, chunk, H, N).reshape(B * nc, chunk, H, N)
    cr = c.reshape(B, nc, chunk, H, N).reshape(B * nc, chunk, H, N)

    y, s = pl.pallas_call(
        _ssd_kernel,
        grid=(B * nc,),
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda g: (g, 0, 0)),
            pl.BlockSpec((H,), lambda g: (0,)),
            pl.BlockSpec((1, chunk, H, N), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, chunk, H, N), lambda g: (g, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda g: (g, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc, chunk, H, P), x.dtype),
            jax.ShapeDtypeStruct((B * nc, H, P, N), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_chunk",
    )(xr, dtr, a_log, br, cr)
    return (y.reshape(B, S, H, P),
            s.reshape(B, nc, H, P, N))
