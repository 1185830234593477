"""Block-diagonal grouped matmul — the TPU form of the paper's grouped
convolution (§III-B) and of MoE expert compute.

A dense layer computes x (M, G*D) @ W (G*D, G*F); grouping zeroes the
off-diagonal blocks, and the paper's cycle win is exactly *not touching*
them.  On TPU the same win is a grid that iterates only the G diagonal
blocks: flops drop G-fold vs the dense equivalent, and each block is an
MXU-shaped (bm x D x bf) matmul.  The per-group (bm, bf) tiles follow
the same square-inclined rule as tetris_matmul.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(
        x_ref[0], w_ref[0],
        preferred_element_type=jnp.float32).astype(o_ref.dtype)[None]


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                   bm: Optional[int] = None, bf: Optional[int] = None,
                   interpret: bool = False) -> jnp.ndarray:
    """x (G, M, D) @ w (G, D, F) -> (G, M, F), diagonal blocks only."""
    g, m, d = x.shape
    g2, d2, f = w.shape
    assert (g, d) == (g2, d2)
    bm = min(bm or max(8, min(m, 512)), m)
    bf = min(bf or max(8, min(f, 512)), f)
    gm, gf = pl.cdiv(m, bm), pl.cdiv(f, bf)

    def last(dim, blk):
        return (dim - 1) // blk if dim % blk else dim // blk - 1

    return pl.pallas_call(
        _gmm_kernel,
        grid=(g, gm, gf),
        in_specs=[
            pl.BlockSpec((1, bm, d),
                         lambda gi, i, j: (gi, jnp.minimum(i, last(m, bm)),
                                           0)),
            pl.BlockSpec((1, d, bf),
                         lambda gi, i, j: (gi, 0,
                                           jnp.minimum(j, last(f, bf)))),
        ],
        out_specs=pl.BlockSpec(
            (1, bm, bf),
            lambda gi, i, j: (gi, jnp.minimum(i, last(m, bm)),
                              jnp.minimum(j, last(f, bf)))),
        out_shape=jax.ShapeDtypeStruct((g, m, f), x.dtype),
        interpret=interpret,
        name="grouped_matmul",
    )(x, w)
