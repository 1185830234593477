"""The ``"matmul"`` plan executor: mapped-IR matmul layers on the MXU
kernels.

A layer spec with ``op == "matmul"`` is the degenerate 1x1 conv
(``core.types.matmul_spec``): x carries M token positions along the
``i_h`` spatial axis and the D feature channels along the channel axis,
so the plan-level layout contract is unchanged — x ``(B, ic, M, 1)``,
kernel ``(1, 1, ic // G, oc)`` in the grouped conv layout every other
executor consumes (oc group-major, matching
``lax.conv feature_group_count`` semantics).  This module adapts that
layout onto the Pallas matmul kernels:

* ``G == 1`` — tokens flatten to one ``(B*M, D)`` operand for
  `kernels.tetris_matmul` (square-inclined block selection, the paper's
  Alg 3 analogue);
* ``G > 1`` — the block-diagonal `kernels.grouped_matmul` grid iterates
  exactly the G diagonal blocks, the paper's §III-B grouped-convolution
  win in MXU form.

Like the sdk executor, this is an MXU stand-in for the mapped schedule:
cycle accounting stays with the ``LayerMapping`` (steps==cycles is
asserted at plan-compile time via `cnn.mapped_net.check_steps`), and
pruned channels follow the reference-executor convention — zero them in
the kernel (`cnn.mapped_net.zero_pruned_kernels`); a dense matmul over
zeroed rows equals the skip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.launch.tracing import MACRO, PLACE

from .grouped_matmul import grouped_matmul
from .tetris_matmul import tetris_matmul


def matmul_layer_traced(mapping, x: jnp.ndarray, kernel: jnp.ndarray, *,
                        interpret: bool = False) -> jnp.ndarray:
    """One mapped matmul layer: x (B, ic, M, 1), kernel
    (1, 1, ic//G, oc) -> (B, oc, M, 1), G = ``mapping.group`` (native
    groups composed with the searched TetrisG grouping)."""
    layer = mapping.layer
    if getattr(layer, "op", "conv") != "matmul":
        raise ValueError(
            f"{layer.name}: executor 'matmul' needs op='matmul' "
            f"(got op={getattr(layer, 'op', 'conv')!r})")
    g = mapping.group
    b = x.shape[0]
    m = layer.i_h
    d_g, f_g = layer.ic // g, layer.oc // g
    if kernel.shape != (1, 1, d_g, layer.oc):
        raise ValueError(
            f"{layer.name}: kernel {kernel.shape} != (1, 1, {d_g}, "
            f"{layer.oc}) — grouped conv layout, G={g}")
    with jax.named_scope(PLACE):
        tok = x[..., 0]                                 # (B, ic, M)
    if g == 1:
        with jax.named_scope(PLACE):
            xm = tok.transpose(0, 2, 1).reshape(b * m, layer.ic)
        with jax.named_scope(MACRO):
            y = tetris_matmul(xm, kernel[0, 0], interpret=interpret)
        with jax.named_scope(PLACE):
            return y.reshape(b, m, layer.oc).transpose(0, 2, 1)[..., None]
    with jax.named_scope(PLACE):
        # channels are group-major on both sides: ic = (g, d_g) in x,
        # oc = (g, f_g) along the kernel's last axis
        xg = (tok.reshape(b, g, d_g, m).transpose(1, 0, 3, 2)
              .reshape(g, b * m, d_g))
        wg = kernel[0, 0].reshape(d_g, g, f_g).transpose(1, 0, 2)
    with jax.named_scope(MACRO):
        y = grouped_matmul(xg, wg, interpret=interpret)  # (g, B*M, f_g)
    with jax.named_scope(PLACE):
        return (y.reshape(g, b, m, f_g).transpose(1, 0, 3, 2)
                .reshape(b, layer.oc, m)[..., None])


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("interpret",))
def matmul_layer_jit(mapping, x, kernel, *, interpret=False):
    return matmul_layer_traced(mapping, x, kernel, interpret=interpret)


def matmul_layer_ref(mapping, x: jnp.ndarray,
                     kernel: jnp.ndarray) -> jnp.ndarray:
    """Einsum oracle of :func:`matmul_layer_traced` — same layout, pure
    jnp (the allclose target of the executor equivalence tests)."""
    layer = mapping.layer
    g = mapping.group
    d_g, f_g = layer.ic // g, layer.oc // g
    tok = x[..., 0].transpose(0, 2, 1)                  # (B, M, ic)
    xg = tok.reshape(*tok.shape[:2], g, d_g)
    wg = kernel[0, 0].reshape(d_g, g, f_g).transpose(1, 0, 2)
    y = jnp.einsum("bmgd,gdf->bmgf", xg, wg,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return (y.reshape(*tok.shape[:2], layer.oc)
            .transpose(0, 2, 1)[..., None])
