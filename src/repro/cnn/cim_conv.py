"""Execute a convolution *exactly as a LayerMapping prescribes* — the
semantic bridge between the mapping search and real compute.

Every array load of the mapping becomes one (patch-vector @ mapped-weight-
matrix) product: the weight matrix is the shifted-and-duplicated kernel
layout of Fig 5 (rows = window pixels x channel tile, columns = kernel
position x output channel), built by :func:`build_weight_matrix`.  Summing
partial products over channel loads and scattering per-position outputs
reconstructs the OFM exactly (up to float summation order) against
``lax.conv_general_dilated`` — asserted in tests/test_cim_conv.py.

Overlap semantics: border-clamped (ceil-form) and marginal windows may
recompute output positions already produced by a neighbouring window of
the same channel pass; recomputed values are identical, so each channel
pass writes into its own buffer with *set* semantics (idempotent), and
buffers accumulate across channel passes (the partial-sum adds of the
shift-and-add peripheral, Fig 3).

Execution strategy (DESIGN.md §2): placements are *batched* — all window
loads of one shape in one (channel x oc) pass are gathered into a single
stacked patch tensor and hit the weight matrix as one batched matmul,
followed by one vectorized scatter.  The weight matrix is hoisted out of
the placement loop entirely (it depends only on the window shape).
Placements stay host-side Python ints, so :func:`cim_conv2d` traces to a
small, static op graph and :func:`cim_conv2d_jit` can treat the mapping
as a static argument.  This is the *reference* path; the TPU performance
path is kernels/im2win_conv.py (``sdk_conv`` consumes the same mapping).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (ConvLayerSpec, LayerMapping, TileMapping)
from repro.launch.tracing import MACRO, PLACE


def window_placements(layer: ConvLayerSpec, tile: TileMapping
                      ) -> List[Tuple[int, int, int, int]]:
    """(y, x, pw_h, pw_w) for every window load of a tile: the regular
    floor-grid, then Alg 4 marginal windows (or, for ceil-form baselines,
    border-clamped overhang windows)."""
    s = layer.stride
    w, k_w, k_h = tile.window, layer.k_w, layer.k_h
    step_x = ((w.pw_w - k_w) // s + 1) * s
    step_y = ((w.pw_h - k_h) // s + 1) * s

    n_x = (layer.i_w - w.pw_w) // step_x + 1
    n_y = (layer.i_h - w.pw_h) // step_y + 1
    ceil_x = math.ceil(((layer.i_w - k_w) // s + 1) / (step_x // s))
    ceil_y = math.ceil(((layer.i_h - k_h) // s + 1) / (step_y // s))

    # border clamps must stay on the stride grid so in-window kernel
    # positions align with the global output raster
    def clamp(v: int, limit: int) -> int:
        return min(v, (limit // s) * s)

    out: List[Tuple[int, int, int, int]] = []
    use_ceil = not tile.marginals
    nx, ny = (ceil_x, ceil_y) if use_ceil else (n_x, n_y)
    for iy in range(ny):
        for ix in range(nx):
            y = clamp(iy * step_y, layer.i_h - w.pw_h)
            x = clamp(ix * step_x, layer.i_w - w.pw_w)
            out.append((y, x, w.pw_h, w.pw_w))

    for mw in tile.marginals:
        if mw.edge == "w":          # right strip
            x = clamp(layer.i_w - mw.mw_w, layer.i_w - mw.mw_w)
            step = ((mw.mw_h - k_h) // s + 1) * s
            for i in range(mw.count):
                y = clamp(i * step, layer.i_h - mw.mw_h)
                out.append((y, x, mw.mw_h, mw.mw_w))
        else:                        # bottom strip
            y = clamp(layer.i_h - mw.mw_h, layer.i_h - mw.mw_h)
            step = ((mw.mw_w - k_w) // s + 1) * s
            for i in range(mw.count):
                x = clamp(i * step, layer.i_w - mw.mw_w)
                out.append((y, x, mw.mw_h, mw.mw_w))
    return out


def placement_groups(layer: ConvLayerSpec, tile: TileMapping
                     ) -> Dict[Tuple[int, int], np.ndarray]:
    """Window placements grouped by congruent shape: {(pw_h, pw_w) ->
    (N, 2) int array of (y, x) origins}.  All N loads of one shape share
    one weight matrix and execute as one batched matmul."""
    groups: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
    for (y, x, ph, pw) in window_placements(layer, tile):
        groups.setdefault((ph, pw), []).append((y, x))
    return {shape: np.asarray(org, np.int32)
            for shape, org in groups.items()}


def gather_patches(xc: jnp.ndarray, origins: np.ndarray, ph: int, pw: int
                   ) -> jnp.ndarray:
    """Stack every congruent placement of one window shape in one gather:
    xc (..., C, H, W), origins (N, 2) of (y, x) -> (..., N, C*ph*pw).
    Row order is channel-major (channel, y, x) — exactly the row order of
    :func:`build_weight_matrix`, so the result multiplies the weight
    matrix directly.  Shared by the reference executor and the
    macro-parallel executor (cnn/mapped_net.py)."""
    ys, xs = origins[:, 0], origins[:, 1]
    Y = ys[:, None, None] + np.arange(ph)[None, :, None]   # (N, ph, 1)
    X = xs[:, None, None] + np.arange(pw)[None, None, :]   # (N, 1, pw)
    p = xc[..., Y, X]                                      # (..., C, N, ph, pw)
    p = jnp.moveaxis(p, -4, -3)                            # (..., N, C, ph, pw)
    return p.reshape(*p.shape[:-3], -1)


def scatter_indices(origins: np.ndarray, py: int, px: int, stride: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Output-raster indices of every placement's (py x px) output tile:
    (OY, OX) broadcastable to (N, py, px), for a vectorized set-semantics
    scatter (overlapping windows recompute identical values)."""
    ys, xs = origins[:, 0], origins[:, 1]
    OY = (ys // stride)[:, None, None] + np.arange(py)[None, :, None]
    OX = (xs // stride)[:, None, None] + np.arange(px)[None, None, :]
    return OY, OX


def build_weight_matrix(layer: ConvLayerSpec, kernel: jnp.ndarray,
                        pw_h: int, pw_w: int) -> jnp.ndarray:
    """Shifted-and-duplicated kernel matrix for one window shape (Fig 5).

    kernel: (k_h, k_w, ic_t, oc_t) slice ->
    matrix: (ic_t * pw_h * pw_w, n_pos * oc_t); rows are channel-major
    window pixels, columns enumerate (position, oc).  Built as a single
    scatter — every (position, kernel-pixel) destination is distinct.
    """
    s = layer.stride
    k_h, k_w = layer.k_h, layer.k_w
    ic_t, oc_t = kernel.shape[2], kernel.shape[3]
    py = (pw_h - k_h) // s + 1
    px = (pw_w - k_w) // s + 1
    kt = jnp.transpose(kernel, (2, 0, 1, 3))   # (ic_t, k_h, k_w, oc_t)

    iy, ix = np.divmod(np.arange(py * px), px)
    ys = (iy * s)[:, None, None] + np.arange(k_h)[None, :, None]  # (P,kh,1)
    xs = (ix * s)[:, None, None] + np.arange(k_w)[None, None, :]  # (P,1,kw)
    p = np.arange(py * px)[:, None, None]
    W = jnp.zeros((ic_t, pw_h, pw_w, py * px, oc_t), kernel.dtype)
    W = W.at[:, ys, xs, p, :].set(
        jnp.broadcast_to(kt[:, None], (ic_t, py * px, k_h, k_w, oc_t)))
    return W.reshape(ic_t * pw_h * pw_w, py * px * oc_t)


def cim_conv2d_traced(mapping: LayerMapping, x: jnp.ndarray,
                      kernel: jnp.ndarray) -> jnp.ndarray:
    """Convolve per the mapping (placement-batched) — the trace-time
    body.  Public plan-consuming entry: `repro.exec.run` inlines it into
    the whole-network program; stand-alone callers use
    :func:`cim_conv2d` / :func:`cim_conv2d_jit`.

    x: (batch, ic, i_h, i_w) pre-padded; kernel in lax grouped layout
    (k_h, k_w, ic // G, oc) with G = mapping.group (for G=1 that is the
    ordinary dense HWIO kernel).  Returns (batch, oc, o_h, o_w).  Pruned
    channels — the trailing slice of each tile's channel range — are
    skipped; callers comparing against an exact conv must zero the
    corresponding kernel slices (see zero_pruned_kernels / tests).
    """
    layer = mapping.layer
    s = layer.stride
    b = x.shape[0]
    o_h, o_w = layer.o_h, layer.o_w

    g = mapping.group
    ic_g, oc_g = layer.ic // g, layer.oc // g

    if kernel.shape != (layer.k_h, layer.k_w, ic_g, layer.oc):
        raise ValueError(f"kernel shape {kernel.shape} != grouped layout "
                         f"{(layer.k_h, layer.k_w, ic_g, layer.oc)}")

    # all G groups are congruent (same tiles, same placements): expose the
    # group axis once and batch it through every gather/matmul/scatter
    with jax.named_scope(PLACE):
        xr = x.reshape(b, g, ic_g, layer.i_h, layer.i_w)
        kr = kernel.reshape(layer.k_h, layer.k_w, ic_g, g, oc_g)
        out = jnp.zeros((b, g, oc_g, o_h, o_w), jnp.result_type(x, kernel))

    c_base = 0
    for tile in mapping.tiles:
        kept = tile.depth            # TileMapping.depth is the KEPT channels
        with jax.named_scope(PLACE):
            xc = xr[:, :, c_base:c_base + kept]  # (b, g, kept, i_h, i_w)
            ks = kr[:, :, c_base:c_base + kept]  # (kh, kw, kept, g, oc_g)
            # one set-semantics buffer per tile: every window (regular or
            # marginal, any shape) writes the tile's full kept-channel
            # partial sum, so overlapping windows recompute identical
            # values and set is idempotent; tiles accumulate into `out`
            buf = jnp.zeros((b, g, oc_g, o_h, o_w), out.dtype)
        for (ph, pw), origins in placement_groups(layer, tile).items():
            py = (ph - layer.k_h) // s + 1
            px = (pw - layer.k_w) // s + 1
            n = len(origins)
            with jax.named_scope(PLACE):
                # The tile's (ic_t x oc_t) array loads batch into ONE
                # matmul per group: channel passes stack along the
                # contraction rows (summing partial products over loads
                # == the shift-and-add accumulation), oc passes
                # concatenate along columns.
                Wm = build_weight_matrix(
                    layer, ks.reshape(layer.k_h, layer.k_w, kept, g * oc_g),
                    ph, pw)
                Wm = Wm.reshape(kept * ph * pw, py * px, g, oc_g)
                Wm = Wm.transpose(2, 0, 1, 3).reshape(
                    g, kept * ph * pw, py * px * oc_g)
                # gather every congruent placement of every group at once
                flat = gather_patches(xc, origins, ph, pw)
            with jax.named_scope(MACRO):
                prod = jnp.einsum("bgnr,grp->bgnp", flat, Wm)
            with jax.named_scope(PLACE):
                prod = prod.reshape(b, g, n, py, px, oc_g)
                prod = prod.transpose(0, 1, 5, 2, 3, 4)  # (b,g,oc_g,N,py,px)
                # vectorized scatter with set semantics; duplicate indices
                # only occur where the recomputed values are identical
                OY, OX = scatter_indices(origins, py, px, s)
                buf = buf.at[:, :, :, OY, OX].set(prod)
        with jax.named_scope(PLACE):
            out = out + buf
        # a tile's nominal channel range is kept + pruned: the pruned
        # trailing slice is skipped here, not shifted into the next tile
        c_base += tile.depth + tile.pruned_channels
    with jax.named_scope(PLACE):
        return out.reshape(b, layer.oc, o_h, o_w)


cim_conv2d_jit = functools.partial(jax.jit, static_argnums=0)(
    cim_conv2d_traced)
cim_conv2d_jit.__doc__ = (
    """jit entry point: the mapping (and with it every placement) is a
    static argument — LayerMapping is a frozen, hashable dataclass — so
    each distinct mapping compiles once to a fully fused program.""")


def cim_conv2d(mapping: LayerMapping, x: jnp.ndarray,
               kernel: jnp.ndarray) -> jnp.ndarray:
    """Convolve per the mapping — see :func:`cim_conv2d_traced` for the
    layout contract.  Dispatches through :func:`cim_conv2d_jit`: one XLA
    compile per distinct (mapping, shapes) instead of per-op eager
    dispatch of every gather/matmul/scatter."""
    return cim_conv2d_jit(mapping, x, kernel)


def reference_conv2d(layer: ConvLayerSpec, x: jnp.ndarray,
                     kernel: jnp.ndarray, groups: int = 1) -> jnp.ndarray:
    """Oracle: lax.conv_general_dilated on the (pre-padded) input; kernel
    in the same grouped layout cim_conv2d consumes."""
    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(layer.stride, layer.stride),
        padding="VALID",
        dimension_numbers=("NCHW", "HWIO", "NCHW"),
        feature_group_count=groups)
