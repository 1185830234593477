"""Macro-parallel mapped-network executor — the paper's P-macro grid
realized as *executed* parallelism, not just cycle bookkeeping.

``TileMapping.cycles`` assumes a grid (r, c) runs ``r`` channel passes and
``c`` oc passes of every window load concurrently:

    cycles = n_windows * ceil(AR_c / r) * ceil(AC_c / c)

This module executes exactly that schedule (DESIGN.md §3).  Per tile, the
(AR_c x AC_c) pass matrix is covered by ``ceil(AR_c/r) * ceil(AC_c/c)``
sequential *super-steps*; within a super-step the (r x c) block of array
passes runs as one macro-grid step — ``jax.vmap`` over the explicit
(row, col) macro axes on a single device, or ``shard_map`` over a
("row", "col") device mesh (launch.mesh.make_macro_mesh /
launch.sharding.macro_pass_specs) when one is available.  Groups follow
``LayerMapping.group_split``: ``gr*gc`` congruent groups run concurrently
on disjoint sub-grids (batched through the group axis), remaining groups
time-multiplex as ``group_rounds`` sequential rounds.

The *executed* step count is derived from the same host-side structures
the executor iterates (placement lists x super-step trip counts x group
rounds) and is asserted equal to ``LayerMapping.cycles`` for every layer
— the equivalence contract that turns the Fig 20 speed-ups from
accounting into execution.

Whole-network entry points (``mapped_net_apply`` /
``reference_net_apply``) are thin wrappers over the compiled-plan path
(``repro.exec``, DESIGN.md §8): the chain is lowered once by
``compile_plan`` (schedule, glue, sharding, steps==cycles — all at
compile time) and executed as one jitted program.  This module owns the
per-layer executor (``mapped_conv2d`` and its traced body) and the
schedule derivation the plan compiler consumes.

Numerics follow cnn/cim_conv.py: window loads of one congruent shape are
gathered and multiplied in one batch (sequential in hardware, counted as
such).  Each shape's array-pass results are summed over the channel
super-steps — the shift-and-add adds of Fig 3, with the cross-row
reduction of a super-step becoming a ``psum`` over the mesh "row" axis in
the sharded path — and each tile is then placed into the output raster
once, by one gather with a host-built index that gives every output pixel
one window that writes it (overlapping border/marginal windows compute
identical partial sums, so any writer will do).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (ConvLayerSpec, LayerMapping, MacroGrid,
                              NetworkMapping, TileMapping)
from repro.launch.sharding import macro_mesh_fits, macro_pass_specs
from repro.launch.tracing import MACRO, PATCHES, PLACE, WEIGHTS
from .cim_conv import (build_weight_matrix, gather_patches,
                       placement_groups, scatter_indices)


# ---------------------------------------------------------------------------
# Execution schedule: the executor's sequential structure, as host ints
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TileSchedule:
    """Sequential structure of one tile's execution (per group round)."""

    window_loads: int          # gathered placements == tile.n_windows
    r_steps: int               # ceil(ar_c / sub_r) channel super-steps
    c_steps: int               # ceil(ac_c / sub_c) oc super-steps

    @property
    def steps(self) -> int:
        return self.window_loads * self.r_steps * self.c_steps


@dataclass(frozen=True)
class LayerSchedule:
    """What :func:`mapped_conv2d` actually executes for one layer."""

    layer: str
    sub: MacroGrid             # macro sub-grid of one group's passes
    group_rounds: int          # sequential rounds of gr*gc-parallel groups
    tiles: Tuple[TileSchedule, ...]

    @property
    def steps(self) -> int:
        """Executed sequential grid steps — the measured counterpart of
        ``LayerMapping.cycles``."""
        return self.group_rounds * sum(t.steps for t in self.tiles)

    @property
    def placements(self) -> int:
        """Placement ops a forward runs for the layer: one gather per
        tile, after its channel super-steps are summed (the group axis
        batches the group rounds)."""
        return len(self.tiles)


@functools.lru_cache(maxsize=None)
def layer_schedule(mapping: LayerMapping) -> LayerSchedule:
    """Derive the executor's schedule from the mapping.  ``window_loads``
    counts the *actual* placement list the executor gathers (floor grid +
    marginals, or the ceil-form clamped raster), not the stored
    ``n_windows`` — the equality of the two is part of the contract.
    Cached per mapping (frozen dataclass): the dispatch-time schedule
    assert in :func:`mapped_conv2d` then costs nothing per step."""
    sub = mapping.sub_grid
    tiles = []
    for tile in mapping.tiles:
        _, ar_c, _, ac_c = mapping.tile_passes(tile)
        loads = sum(len(o) for o in
                    placement_groups(mapping.layer, tile).values())
        tiles.append(TileSchedule(
            window_loads=loads,
            r_steps=math.ceil(ar_c / sub.r),
            c_steps=math.ceil(ac_c / sub.c)))
    return LayerSchedule(layer=mapping.layer.name, sub=sub,
                         group_rounds=mapping.group_rounds,
                         tiles=tuple(tiles))


def executed_steps(mapping: LayerMapping) -> int:
    return layer_schedule(mapping).steps


def network_schedule(net: NetworkMapping) -> Tuple[LayerSchedule, ...]:
    return tuple(layer_schedule(m) for m in net.layers)


def check_steps(mapping: LayerMapping) -> None:
    """Raise unless the executor's schedule matches the mapping's cycle
    count — the per-layer half of the DESIGN.md §3 contract."""
    s = layer_schedule(mapping)
    if s.steps != mapping.cycles:
        raise AssertionError(
            f"{mapping.layer.name}: executed steps {s.steps} != "
            f"cycles {mapping.cycles} (sub-grid {s.sub.r}x{s.sub.c}, "
            f"rounds {s.group_rounds})")


def assert_steps_match(net: NetworkMapping) -> None:
    """Executed grid steps == analytical cycle count for every layer —
    the Fig 20 speed-ups are *executed*, not just counted."""
    for m in net.layers:
        check_steps(m)


# ---------------------------------------------------------------------------
# One macro-grid super-step
# ---------------------------------------------------------------------------

def _one_macro(p: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """ONE macro's array pass: patches (b, g, N, K) against this macro's
    (ic_t x oc_t) weight block (g, K, Po) -> (b, g, N, Po)."""
    return jnp.einsum("bgnk,gko->bgno", p, w)


# grid rows share nothing; grid columns share the row's patch block
_macro_cols = jax.vmap(_one_macro, in_axes=(None, 0))      # over sub_c
_macro_grid = jax.vmap(_macro_cols, in_axes=(0, 0))        # over sub_r


def _macro_step(p_blk: jnp.ndarray, w_blk: jnp.ndarray,
                mesh=None) -> jnp.ndarray:
    """One super-step of the macro grid: an (r x c) block of array passes
    runs concurrently.

    p_blk (sub_r, b, g, N, K): each macro row's channel-pass patch block.
    w_blk (sub_r, sub_c, g, K, Po): each macro's weight block.
    Returns (sub_c, b, g, N, Po) — partial products summed over the grid
    rows (the shift-and-add accumulation across macro rows).

    On a ("row", "col") device mesh whose axes divide (sub_r, sub_c) the
    step runs under shard_map — macros become devices and the row
    reduction a psum; otherwise the macro axes are vmapped on one device.
    A mesh with a leading "data" axis (make_macro_mesh(..., data=n))
    additionally shards the batch axis over ``n`` replicas of the macro
    grid — weights replicate across "data", and the psum stays confined
    to "row", so each replica computes its own batch slice independently.
    """
    if macro_mesh_fits(mesh, p_blk.shape[0], w_blk.shape[1],
                       batch=p_blk.shape[1]):
        p_spec, w_spec, o_spec = macro_pass_specs(mesh)

        def local(p, w):
            part = _macro_grid(p, w).sum(0)          # local rows
            return jax.lax.psum(part, "row")         # cross-device rows

        return jax.shard_map(local, mesh=mesh, in_specs=(p_spec, w_spec),
                             out_specs=o_spec)(p_blk, w_blk)
    return _macro_grid(p_blk, w_blk).sum(0)


# ---------------------------------------------------------------------------
# Layer executor
# ---------------------------------------------------------------------------

def _tile_dims(mapping: LayerMapping, tile: TileMapping
               ) -> Tuple[int, int, int, int]:
    """(R, C, ic_pad, oc_pad) of one tile's super-step blocking — the
    sequential channel/oc super-step counts and the channel paddings
    that make every super-step a full (sub_r x sub_c) macro block."""
    sub = mapping.sub_grid
    ic_t, ar_c, oc_t, ac_c = mapping.tile_passes(tile)
    R = math.ceil(ar_c / sub.r)
    C = math.ceil(ac_c / sub.c)
    return R, C, R * sub.r * ic_t, C * sub.c * oc_t


def _tile_weights(mapping: LayerMapping, tile: TileMapping,
                  ks: jnp.ndarray, R: int, C: int) -> Tuple[jnp.ndarray, ...]:
    """Blocked shifted-weight matrices, one per congruent window shape:
    (R, C, sub_r, sub_c, g, K, npos*oc_t) — the row/oc blocking of the
    Fig 5 shifted-and-duplicated matrix.  Input- and batch-independent:
    co-resident plan tiers can share ONE prepared copy
    (`prepared_layer_weights` / repro.exec.constants)."""
    layer = mapping.layer
    s = layer.stride
    sub = mapping.sub_grid
    ic_t, _, oc_t, _ = mapping.tile_passes(tile)
    g = ks.shape[3]
    ic_pad, oc_pad = ks.shape[2], ks.shape[4]
    out = []
    for (ph, pw), _origins in placement_groups(layer, tile).items():
        py = (ph - layer.k_h) // s + 1
        px = (pw - layer.k_w) // s + 1
        npos = py * px
        K = ic_t * ph * pw
        Wm = build_weight_matrix(
            layer, ks.reshape(layer.k_h, layer.k_w, ic_pad, g * oc_pad),
            ph, pw)                                    # (ic_pad*ph*pw, ...)
        w_all = Wm.reshape(R, sub.r, K, npos, g, C, sub.c, oc_t)
        w_all = w_all.transpose(0, 5, 1, 6, 4, 2, 3, 7).reshape(
            R, C, sub.r, sub.c, g, K, npos * oc_t)
        out.append(w_all)
    return tuple(out)


def _tile_operands(mapping: LayerMapping, tile: TileMapping,
                   xc: jnp.ndarray, ks: Optional[jnp.ndarray],
                   R: int, C: int,
                   prepared: Optional[Sequence[jnp.ndarray]] = None
                   ) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Pass-blocked operands per congruent window shape.

    xc (b, g, ic_pad, H, W) and ks (k_h, k_w, ic_pad, g, oc_pad) are the
    tile's channel slice zero-padded to whole super-steps.  For each
    shape, in `placement_groups` order: patches (R, sub_r, b, g, N, K)
    with K = ic_t*ph*pw, and weights (R, C, sub_r, sub_c, g, K,
    npos*oc_t) — the row/oc blocking
    of the Fig 5 shifted-and-duplicated matrix.  ``prepared`` substitutes
    pre-materialized weight blocks (`_tile_weights` order) for the
    in-trace build — the plan-constant sharing path; ``ks`` may then be
    None.
    """
    layer = mapping.layer
    sub = mapping.sub_grid
    ic_t, _, _, _ = mapping.tile_passes(tile)
    b, g = xc.shape[0], xc.shape[1]
    if prepared is None:
        with jax.named_scope(WEIGHTS):
            weights = _tile_weights(mapping, tile, ks, R, C)
    else:
        weights = tuple(prepared)
    groups = placement_groups(layer, tile)
    if len(weights) != len(groups):
        raise ValueError(f"{layer.name}: {len(weights)} prepared weight "
                         f"blocks for {len(groups)} window shapes")
    out = []
    for (ph, pw), origins in groups.items():
        K = ic_t * ph * pw
        with jax.named_scope(PATCHES):
            flat = gather_patches(xc, origins, ph, pw)  # (b,g,N,ic_pad*ph*pw)
            n = flat.shape[2]
            p_all = flat.reshape(b, g, n, R * sub.r, K)
            p_all = p_all.transpose(3, 0, 1, 2, 4).reshape(
                R, sub.r, b, g, n, K)
        out.append((p_all, weights[len(out)]))
    return out


@functools.lru_cache(maxsize=None)
def placement_index(layer: ConvLayerSpec, tile: TileMapping) -> np.ndarray:
    """Host-built placement of one tile: for every output pixel (row-major
    over (o_h, o_w)), its position in the tile's results flattened over
    (shape, window, y, x) in `placement_groups` order — the last window
    that writes the pixel.  Overlapping windows compute identical values,
    so one writer per pixel places the tile exactly; coverage
    (core.cycles.axis_covers) guarantees every pixel has one."""
    s = layer.stride
    owner = np.full(layer.o_h * layer.o_w, -1, np.int32)
    base = 0
    for (ph, pw), origins in placement_groups(layer, tile).items():
        py = (ph - layer.k_h) // s + 1
        px = (pw - layer.k_w) // s + 1
        OY, OX = scatter_indices(origins, py, px, s)
        pix = np.broadcast_to(OY * layer.o_w + OX,
                              (len(origins), py, px)).ravel()
        owner[pix] = base + np.arange(pix.size)
        base += pix.size
    if (owner < 0).any():
        raise AssertionError(
            f"{layer.name}: {int((owner < 0).sum())} output pixels have no "
            f"window in tile {tile.window}")
    return owner


def prepared_layer_weights(mapping: LayerMapping, kernel: jnp.ndarray
                           ) -> Tuple[Tuple[jnp.ndarray, ...], ...]:
    """Materialize one layer's blocked shifted-weight matrices from its
    kernel — per tile, per congruent window shape, in exactly the order
    :func:`mapped_conv2d_traced` consumes them via ``weights=``.

    The blocks depend only on (mapping, kernel), never on the input or
    the batch, so every tier of a plan ladder — and every co-resident
    plan of the same network — can share ONE prepared copy instead of
    re-deriving the matrices inside each tier's program on every forward
    (repro.exec.constants.fixed_constants owns the sharing handle)."""
    layer = mapping.layer
    g = mapping.group
    ic_g, oc_g = layer.ic // g, layer.oc // g
    if kernel.shape != (layer.k_h, layer.k_w, ic_g, layer.oc):
        raise ValueError(f"kernel shape {kernel.shape} != grouped layout "
                         f"{(layer.k_h, layer.k_w, ic_g, layer.oc)}")
    kr = kernel.reshape(layer.k_h, layer.k_w, ic_g, g, oc_g)
    out = []
    c_base = 0
    for tile in mapping.tiles:
        kept = tile.depth
        R, C, ic_pad, oc_pad = _tile_dims(mapping, tile)
        ks = jnp.pad(kr[:, :, c_base:c_base + kept],
                     ((0, 0), (0, 0), (0, ic_pad - kept), (0, 0),
                      (0, oc_pad - oc_g)))
        out.append(_tile_weights(mapping, tile, ks, R, C))
        c_base += kept + tile.pruned_channels
    return tuple(out)


def mapped_conv2d_traced(mapping: LayerMapping, x: jnp.ndarray,
                         kernel: Optional[jnp.ndarray], *, mesh=None,
                         weights=None) -> jnp.ndarray:
    """Macro-parallel convolution per the mapping — the trace-time body.
    Public plan-consuming entry: `repro.exec.run` inlines it into the
    whole-network program; stand-alone callers use :func:`mapped_conv2d`
    / :func:`mapped_conv2d_jit`.  Same layout contract as
    cnn.cim_conv.cim_conv2d: x (batch, ic, i_h, i_w) pre-padded, kernel
    (k_h, k_w, ic // G, oc) in lax grouped layout, output
    (batch, oc, o_h, o_w); pruned channels (the trailing slice of each
    tile's channel range) are skipped.  ``weights`` substitutes this
    layer's pre-materialized shifted-weight blocks
    (:func:`prepared_layer_weights`) for the in-trace build — the
    plan-constant sharing path; ``kernel`` is then unused (and may be
    None).

    Its work runs in four named scopes (launch/tracing.PHASES):
    ``weights`` (the in-trace weight build), ``patches`` (the window
    gather), ``macro`` (the array passes) and ``place`` (summing their
    results over the channel super-steps, then placing each tile once)."""
    layer = mapping.layer
    b = x.shape[0]
    o_h, o_w = layer.o_h, layer.o_w
    g = mapping.group
    ic_g, oc_g = layer.ic // g, layer.oc // g
    if weights is None:
        if kernel.shape != (layer.k_h, layer.k_w, ic_g, layer.oc):
            raise ValueError(f"kernel shape {kernel.shape} != grouped "
                             f"layout "
                             f"{(layer.k_h, layer.k_w, ic_g, layer.oc)}")
        with jax.named_scope(WEIGHTS):
            kr = kernel.reshape(layer.k_h, layer.k_w, ic_g, g, oc_g)
    else:
        if len(weights) != len(mapping.tiles):
            raise ValueError(f"{layer.name}: {len(weights)} prepared "
                             f"weight tiles for {len(mapping.tiles)}")
        kr = None

    # all groups are congruent: the group axis batches the gr*gc-parallel
    # groups; sequential group rounds only multiply the step count
    with jax.named_scope(PATCHES):
        xr = x.reshape(b, g, ic_g, layer.i_h, layer.i_w)

    sub = mapping.sub_grid
    out = None                               # (o_h*o_w, b, g, oc_g)
    c_base = 0
    for ti, tile in enumerate(mapping.tiles):
        kept = tile.depth
        oc_t = mapping.tile_passes(tile)[2]
        R, C, ic_pad, oc_pad = _tile_dims(mapping, tile)
        with jax.named_scope(PATCHES):
            xc = jnp.pad(xr[:, :, c_base:c_base + kept],
                         ((0, 0), (0, 0), (0, ic_pad - kept), (0, 0),
                          (0, 0)))
        if weights is None:
            with jax.named_scope(WEIGHTS):
                ks = jnp.pad(kr[:, :, c_base:c_base + kept],
                             ((0, 0), (0, 0), (0, ic_pad - kept), (0, 0),
                              (0, oc_pad - oc_g)))
        else:
            ks = None
        shapes = _tile_operands(mapping, tile, xc, ks, R, C,
                                prepared=None if weights is None
                                else weights[ti])

        flat = []              # per shape: (n*py*px, b, g, oc_g) results
        for p_all, w_all in shapes:
            cols = []
            for ci in range(C):
                # the shift-and-add: sum this oc super-step's passes over
                # the R channel super-steps before anything is placed
                acc = None
                for ri in range(R):
                    with jax.named_scope(MACRO):
                        res = _macro_step(p_all[ri], w_all[ri, ci], mesh)
                    with jax.named_scope(PLACE):
                        acc = res if acc is None else acc + res
                cols.append(acc)
            with jax.named_scope(PLACE):
                # (sub_c, b, g, n, py*px*oc_t) per oc super-step -> the
                # C super-steps side by side along oc
                v = jnp.concatenate(
                    [c.reshape(sub.c, b, g, -1, oc_t).transpose(3, 1, 2, 0, 4)
                     .reshape(-1, b, g, sub.c * oc_t) for c in cols],
                    axis=3)
                flat.append(v[..., :oc_g])
        with jax.named_scope(PLACE):
            placed = jnp.take(jnp.concatenate(flat, axis=0),
                              placement_index(layer, tile), axis=0,
                              mode="clip", unique_indices=True)
            out = placed if out is None else out + placed
        # skip the tile's pruned trailing channels instead of shifting
        # the next tile's range onto them
        c_base += kept + tile.pruned_channels
    with jax.named_scope(PLACE):
        return out.transpose(1, 2, 3, 0).reshape(b, layer.oc, o_h, o_w)


mapped_conv2d_jit = functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("mesh",))(
    mapped_conv2d_traced)
mapped_conv2d_jit.__doc__ = (
    """jit entry: mapping (frozen dataclass) and mesh are static — one
    XLA program per distinct (mapping, mesh, shapes).""")


def mapped_conv2d(mapping: LayerMapping, x: jnp.ndarray,
                  kernel: jnp.ndarray, *, mesh=None) -> jnp.ndarray:
    """Execute one layer macro-parallel, asserting the executed schedule
    matches the mapping's cycle count (host-side, cached, free under
    jit)."""
    check_steps(mapping)
    return mapped_conv2d_jit(mapping, x, kernel, mesh=mesh)


# ---------------------------------------------------------------------------
# Network forward pass — thin wrappers over the compiled-plan path
# ---------------------------------------------------------------------------
#
# Whole-network execution lives in repro.exec (DESIGN.md §8): a
# NetworkMapping is lowered ONCE by `compile_plan` (executor choice,
# schedule, inter-layer glue, sharding decisions, steps==cycles — all at
# compile time) and `execute_plan` runs the forward as one jitted
# program.  These wrappers keep the original signatures so every
# equivalence test runs unchanged against the plan.  (repro.exec is
# imported lazily: it consumes this module's traced bodies.)

def mapped_net_apply(net: NetworkMapping, kernels: Sequence[jnp.ndarray],
                     x: jnp.ndarray, *, mesh=None,
                     activation=None) -> jnp.ndarray:
    """Forward an entire ``NetworkMapping`` through the macro-parallel
    executor — now a wrapper over ``compile_plan``/``execute_plan`` with
    every layer pinned to ``"mapped"``.  ``kernels[i]`` is layer i's
    kernel in that mapping's grouped layout ``(k_h, k_w, ic // G_i,
    oc)``.  Executed grid steps == ``LayerMapping.cycles`` is checked at
    plan-compile time (memoized, so repeat calls pay nothing).
    ``activation`` is a static jit argument hashed by identity — pass a
    stable callable, not a fresh lambda per call."""
    from repro.exec import compile_plan, execute_plan
    plan = compile_plan(net, executor_policy="mapped", mesh=mesh,
                        batch=x.shape[0] if mesh is not None else None)
    return execute_plan(plan, kernels, x, mesh=mesh, activation=activation)


def reference_net_apply(net: NetworkMapping,
                        kernels: Sequence[jnp.ndarray], x: jnp.ndarray, *,
                        activation=None) -> jnp.ndarray:
    """Oracle composition: the same compiled chain (glue and all),
    lax.conv per layer (pruned channels must be zeroed in ``kernels``,
    see zero_pruned_kernels)."""
    from repro.exec import compile_plan
    from repro.exec.run import execute_oracle
    plan = compile_plan(net, executor_policy="reference")
    return execute_oracle(plan, kernels, x, activation=activation)


def zero_pruned_kernels(net: NetworkMapping,
                        kernels: Sequence[jnp.ndarray]
                        ) -> List[jnp.ndarray]:
    """Zero each tile's pruned input channels — the trailing slice of
    that tile's nominal (kept + pruned) channel range, which is exactly
    what the executors skip (the retrained-network convention of the
    equivalence tests).  One trailing slice per *tile*, not one per
    layer: with several pruned tiles the pruned channels interleave with
    later tiles' kept ranges, and a single layer-trailing slice would
    zero the wrong channels."""
    out = []
    for m, k in zip(net.layers, kernels):
        c_base = 0
        for t in m.tiles:
            c_base += t.depth
            if t.pruned_channels:
                k = k.at[:, :, c_base:c_base + t.pruned_channels, :].set(0.0)
            c_base += t.pruned_channels
        out.append(k)
    return out


def __getattr__(name: str):
    # back-compat: the inter-layer glue moved to repro.exec.glue
    if name in ("fit_spatial", "_center_crop"):
        from repro.exec import glue
        return glue.fit_spatial if name == "fit_spatial" else \
            glue.center_crop
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
