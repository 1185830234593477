"""Tetris-tiled matmul — the MXU adaptation of the paper's window search.

The CIM macro analogy (DESIGN.md §2): an MXU pass consumes a (bm x bk)
activation tile against a (bk x bn) weight tile — the 'array' is the
(bm, bn, bk) block, VMEM is the constraint (AR x AC -> VMEM budget), and
the number of grid steps is the computing-cycle count.  The paper's
square-inclined rule (Alg 3, AM-GM) picks bm ~ bn (for a fixed number of
output elements per block, a square block minimises operand traffic
(bm+bn)*bk — same argument as minimising window rows); ragged edges are
the marginal-window case, handled on TPU by clamped overlapping edge
blocks (recompute instead of reshape — uniform tiles are what the MXU
wants; the count matches the ceil form).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tetris import factor_pairs_square_first

VMEM_BUDGET = 8 * 1024 * 1024      # bytes per core we allow operands


def select_block_shape(m: int, n: int, k: int, dtype_bytes: int = 2,
                       vmem_budget: int = VMEM_BUDGET
                       ) -> Tuple[int, int, int]:
    """Square-inclined (bm, bn, bk) under the VMEM constraint.

    Mirrors Alg 3: enumerate near-square factor pairs of the per-block
    output element count (largest first), require MXU alignment (128
    multiples where the dim allows) and the operand working set
    (bm*bk + bk*bn) * bytes + bm*bn*4 <= budget."""
    def align(v: int, d: int) -> int:
        a = 128 if d >= 128 else max(8, d)
        return max(a, (v // a) * a)

    best = None
    for target in (1 << 16, 1 << 15, 1 << 14, 1 << 13, 1 << 12):
        for a, b in factor_pairs_square_first(target):
            bm, bn = align(min(a, m), m), align(min(b, n), n)
            if bm > m or bn > n:
                continue
            bk = align(min(k, vmem_budget // ((bm + bn) * dtype_bytes)), k)
            bk = min(bk, k)
            if bk < min(128, k):
                continue
            ws = (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4
            if ws > vmem_budget:
                continue
            cand = (bm, bn, bk)
            # prefer bigger blocks (fewer grid steps), then squarer
            key = (bm * bn * bk, -abs(bm - bn))
            if best is None or key > best[0]:
                best = (key, cand)
        if best is not None:
            break
    if best is None:
        return (min(m, 128), min(n, 128), min(k, 128))
    return best[1]


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tetris_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                  block: Tuple[int, int, int] = None,
                  interpret: bool = False) -> jnp.ndarray:
    """x (M, K) @ w (K, N); grid = ceil tiles with clamped edge blocks."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = block or select_block_shape(m, n, k, x.dtype.itemsize)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    # K must tile exactly (a clamped K block would double-accumulate);
    # M/N edge tiles are clamped — overlapping rewrites of identical
    # values, the marginal-window analogue.
    while k % bk:
        bk -= 1
    gm, gn, gk = (pl.cdiv(m, bm), pl.cdiv(n, bn), k // bk)

    def xi(i, j, ki):
        return (jnp.minimum(i, _last(m, bm)), ki)

    def wi(i, j, ki):
        return (ki, jnp.minimum(j, _last(n, bn)))

    def oi(i, j, ki):
        return (jnp.minimum(i, _last(m, bm)), jnp.minimum(j, _last(n, bn)))

    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[pl.BlockSpec((bm, bk), xi),
                  pl.BlockSpec((bk, bn), wi)],
        out_specs=pl.BlockSpec((bm, bn), oi),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="tetris_matmul",
    )(x, w)


def _last(dim: int, block: int) -> int:
    return (dim - 1) // block if dim % block else dim // block - 1
