"""Fused (flash) attention Pallas kernel — the TPU fix for the #1
bottleneck the roofline analysis identified (EXPERIMENTS.md §Perf): the
HLO attention path materialises score/softmax chains to HBM; fused
attention keeps them in VMEM, reducing attention HBM traffic from
O(S^2) to O(S * d).

Grid: (batch*heads, q_blocks, kv_blocks) — kv innermost so the online-
softmax running state (m, l, acc) lives in VMEM scratch across kv steps:

    m_new = max(m, rowmax(s));  alpha = exp(m - m_new)
    l     = alpha * l + rowsum(exp(s - m_new))
    acc   = alpha * acc + exp(s - m_new) @ v

Causal masking by absolute positions (q_offset for decode/continuation);
the epilogue normalises by l on the last kv step.  Validated in
interpret mode against ref.flash_attention_ref for shapes/dtypes in
tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  n_kv: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                    # (bq, d)
    k = k_ref[0]                                    # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal:
        pos_q = q_offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        pos_k = ki * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        s = jnp.where(pos_k <= pos_q, s, NEG_INF)

    m_prev = m_ref[...]                             # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                          # (bq, bk)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = (alpha * acc_ref[...]
                    + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, q_offset: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q (BH, Sq, D); k/v (BH, Sk, D) — heads pre-folded into the leading
    dim (callers vmap/reshape GQA groups).  Returns (BH, Sq, D)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq {sq}/{sk} must tile by {bq}/{bk}")
    n_kv = sk // bk
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_kv=n_kv, q_offset=q_offset),
        grid=(bh, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # running denom
            pltpu.VMEM((bq, d), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def mha_flash(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, interpret: bool = False) -> jnp.ndarray:
    """Convenience wrapper: q (B, S, H, D), k/v (B, S, Hkv, D) with GQA
    head expansion folded into the flash grid."""
    b, sq, hq, dd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1
                    ).reshape(b * hq, k.shape[1], dd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1
                    ).reshape(b * hq, v.shape[1], dd)
    of = flash_attention(qf, kf, vf, causal=causal, interpret=interpret)
    return of.reshape(b, hq, sq, dd).transpose(0, 2, 1, 3)
