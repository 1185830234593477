"""SDK convolution — the paper's parallel window executed as a Pallas
kernel (DESIGN.md §2 table).

:func:`sdk_conv` consumes a :class:`LayerMapping` directly.  Per
(group, tile) one ``pallas_call`` whose grid is ``(AR_c, AC_c,
n_windows)`` — the grid size IS the tile's computing-cycle count (ceil
form): every grid step is one parallel-window load of one
``ic_t x oc_t`` array pass.  Marginal / border windows execute as
border-clamped reads of the regular window shape (overlap-recompute,
Alg 4's hardware analogue).  It therefore executes the *same* mapping
as the reference executor (cnn/cim_conv.py) and is cross-checked
against it in tests/test_sdk_conv.py.

Each step covers a (py x px) tile of output positions (the 'kernel
computations inside the parallel window', Fig 9a) against the full
kernel stack, computed as k_h*k_w shift-matmuls on the MXU — the
shifted-and-duplicated kernel matrix of Fig 5 realised as shifted
*reads* instead of duplicated *weights* (VMEM holds one kernel copy;
the crossbar had to duplicate).

**Layout inside the kernel.**  The TPU tiles the last two dimensions of
every VMEM buffer in (8 sublane x 128 lane) blocks, and its compiler
refuses a dynamic slice along them that it cannot prove tile-aligned.
The window origin is a runtime value, so the kernel keeps both spatial
axes *major*: the feature map enters as ``(AR_c, H, W, Bp, ic_t)`` and
the output leaves as ``(AR_c, AC_c, o_h, o_w, Bp, oc_t)`` — batch on
sublanes (padded to ``Bp``, a multiple of 8, with zero rows), channels
on lanes.  Every block's last two dimensions are whole array
dimensions, which the tiling rule always accepts; window slices, window
DMAs and output stores index only the major (H, W) axes.
"""
from __future__ import annotations

import collections
import functools
import math
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import LayerMapping
from repro.launch.tracing import MACRO, PLACE

#: Fallback ``block="auto"`` VMEM budget (bytes) when the environment
#: does not override it.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024
_VMEM_ENV_VAR = "REPRO_SDK_VMEM_BUDGET"

#: f32 VMEM tile (sublanes, lanes) of the TPU vector memory.
_SUBLANES, _LANES = 8, 128
#: Most scoped VMEM one sdk pallas_call may ask the compiler for: the
#: v5e TensorCore has 128 MiB, and the compiler keeps some for itself.
VMEM_LIMIT_CAP = 96 * 1024 * 1024


def default_vmem_budget() -> int:
    """The sdk executor's ``block="auto"`` VMEM budget in bytes when the
    caller passes ``vmem_budget=None``: the ``REPRO_SDK_VMEM_BUDGET``
    environment variable, else :data:`DEFAULT_VMEM_BUDGET` (8 MiB).  An
    explicit byte parameter threaded through `compile_plan` / `sdk_conv`
    — the autotuner sweeps it — with the env var as the deploy-time
    default.  Read per call (not cached at import) so tests and drivers
    can re-point it."""
    env = os.environ.get(_VMEM_ENV_VAR)
    if not env:
        return DEFAULT_VMEM_BUDGET
    try:
        budget = int(env)
    except ValueError:
        raise ValueError(
            f"{_VMEM_ENV_VAR}={env!r} is not an integer byte count "
            f"(suffixes like '8M' are not supported)") from None
    if budget <= 0:
        raise ValueError(f"{_VMEM_ENV_VAR}={env!r} must be > 0 "
                         f"(unset it for the {DEFAULT_VMEM_BUDGET}-byte "
                         f"default)")
    return budget


# ---------------------------------------------------------------------------
# Mapping-driven SDK kernel
# ---------------------------------------------------------------------------

def _tile_passes(mapping: LayerMapping, tile) -> Tuple[int, int, int, int]:
    """(ic_t, ar_c, oc_t, ac_c) of a tile's sequential array passes, per
    group — now shared executor logic on the mapping itself (the
    macro-parallel executor blocks the same passes over the grid)."""
    return mapping.tile_passes(tile)


def _tile_grid(layer, tile) -> Tuple[int, int, int, int, int, int]:
    """(step_y, step_x, ny, nx, lim_y, lim_x) of a tile's ceil-form window
    raster: `n = ny*nx` border-clamped loads of the regular window shape
    cover every output position (clamps stay on the stride grid)."""
    s = layer.stride
    w = tile.window
    step_y = ((w.pw_h - layer.k_h) // s + 1) * s
    step_x = ((w.pw_w - layer.k_w) // s + 1) * s
    ny = math.ceil(((layer.i_h - layer.k_h) // s + 1) / (step_y // s))
    nx = math.ceil(((layer.i_w - layer.k_w) // s + 1) / (step_x // s))
    lim_y = ((layer.i_h - w.pw_h) // s) * s
    lim_x = ((layer.i_w - w.pw_w) // s) * s
    return step_y, step_x, ny, nx, lim_y, lim_x


def _window_origin(wi, *, step_y, step_x, nx, lim_y, lim_x):
    """Border-clamped (y0, x0) of window `wi` in the ceil-form raster."""
    y0 = jnp.minimum((wi // nx) * step_y, lim_y)
    x0 = jnp.minimum((wi % nx) * step_x, lim_x)
    return y0, x0


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(*shape: int) -> int:
    """f32 bytes a VMEM buffer of ``shape`` occupies in the (8, 128)
    tiled layout: the last two dimensions round up to whole tiles."""
    *major, sub, lane = shape
    return (4 * math.prod(major) * _round_up(sub, _SUBLANES)
            * _round_up(lane, _LANES))


def _strided(v, start: int, n: int, s: int, axis: int):
    """``n`` entries of major axis ``axis`` from ``start`` at stride ``s``.
    A strided slice lowers to a gather the TPU compiler refuses, so
    stride > 1 concatenates unit slices instead."""
    if s == 1:
        return jax.lax.slice_in_dim(v, start, start + n, axis=axis)
    return jnp.concatenate(
        [jax.lax.slice_in_dim(v, start + i * s, start + i * s + 1, axis=axis)
         for i in range(n)], axis=axis)


def _window_matmuls(win, w_ref, *, s, k_h, k_w, py, px):
    """The window's k_h*k_w unrolled shift-matmuls (MXU passes): win
    (pw_h, pw_w, Bp, ic_t) x the (ic_t x oc_t) kernel block ->
    (py, px, Bp, oc_t) f32.  The shifts slice only the major (H, W)
    axes; merging them into the sublane axis is tile-aligned because Bp
    is a multiple of 8.  Shared by the whole-array and window-blocked
    kernels so the two tilings cannot drift."""
    bp, ic_t = win.shape[2], win.shape[3]
    oc_t = w_ref.shape[-1]
    acc = jnp.zeros((py * px * bp, oc_t), jnp.float32)
    for dy in range(k_h):
        for dx in range(k_w):
            patch = _strided(_strided(win, dy, py, s, 0), dx, px, s, 1)
            acc += jnp.dot(patch.reshape(py * px * bp, ic_t),
                           w_ref[0, 0, dy, dx],
                           preferred_element_type=jnp.float32)
    return acc.reshape(py, px, bp, oc_t)


def _sdk_kernel(x_ref, w_ref, o_ref, *, s, k_h, k_w, pw_h, pw_w, py, px,
                step_y, step_x, nx, lim_y, lim_x):
    """One grid step == one window load of one (ic_t x oc_t) array pass.
    The (ci, oi) output block stays resident across the window axis;
    every output position lies in some window, and clamped border
    windows rewrite identical values."""
    wi = pl.program_id(2)
    y0, x0 = _window_origin(wi, step_y=step_y, step_x=step_x, nx=nx,
                            lim_y=lim_y, lim_x=lim_x)
    win = x_ref[0, pl.ds(y0, pw_h), pl.ds(x0, pw_w)]
    vals = _window_matmuls(win, w_ref, s=s, k_h=k_h, k_w=k_w, py=py, px=px)
    o_ref[0, 0, pl.ds(y0 // s, py), pl.ds(x0 // s, px)] = \
        vals.astype(o_ref.dtype)


def _sdk_kernel_blocked(x_hbm, w_ref, o_hbm, xwin, ovals, in_sem, out_sem,
                        *, s, k_h, k_w, pw_h, pw_w, py, px, step_y, step_x,
                        ac_c, nw, nx, lim_y, lim_x):
    """Window-blocked variant of :func:`_sdk_kernel`: x and the output
    stay in HBM (``pl.ANY``); each grid step DMAs exactly one window
    patch (pw_h, pw_w, Bp, ic_t) into VMEM scratch and one output tile
    (py, px, Bp, oc_t) back out.  VMEM per step is the window working
    set — independent of the feature-map size, so big Inception /
    DenseNet layers fit where whole-array blocks would not.  Window
    origins are border-clamped to the stride grid, which BlockSpec index
    maps cannot express (blocks overlap); the DMA path is the general
    form.

    The DMAs are **double-buffered** (two scratch slots + paired
    semaphores, slot = flat step parity): step t prefetches window
    patch t+1 into the idle slot before waiting on its own patch, so the
    next load overlaps this step's MXU shift-matmuls; the output-tile
    store is likewise left in flight and only drained when its slot is
    about to be reused (t+2) or the grid ends.  The pipeline spans the
    flat step order, so every grid axis is ``"arbitrary"`` (sequential).
    The grid — and therefore the steps==cycles contract — is unchanged:
    pipelining shortens the step, it does not add or remove steps."""
    ci = pl.program_id(0)
    oi = pl.program_id(1)
    wi = pl.program_id(2)
    total = pl.num_programs(0) * ac_c * nw
    t = (ci * ac_c + oi) * nw + wi          # flat sequential step

    def origin(step):
        return _window_origin(step % nw, step_y=step_y, step_x=step_x,
                              nx=nx, lim_y=lim_y, lim_x=lim_x)

    def in_copy(step, slot):
        """Async copy of window patch `step` into scratch slot `slot`."""
        y0, x0 = origin(step)
        return pltpu.make_async_copy(
            x_hbm.at[step // (ac_c * nw), pl.ds(y0, pw_h), pl.ds(x0, pw_w)],
            xwin.at[slot], in_sem.at[slot])

    def out_copy(step, slot):
        """Async copy of output tile `step` out of scratch slot `slot`."""
        y0, x0 = origin(step)
        return pltpu.make_async_copy(
            ovals.at[slot],
            o_hbm.at[step // (ac_c * nw), step % (ac_c * nw) // nw,
                     pl.ds(y0 // s, py), pl.ds(x0 // s, px)],
            out_sem.at[slot])

    @pl.when(t == 0)
    def _warmup():                          # pipeline prologue
        in_copy(t, t % 2).start()

    @pl.when(t + 1 < total)
    def _prefetch():                        # overlap next load with compute
        in_copy(t + 1, (t + 1) % 2).start()

    in_copy(t, t % 2).wait()

    @pl.when(t >= 2)
    def _reclaim():                         # slot reused: drain store t-2
        out_copy(t - 2, t % 2).wait()

    ovals[t % 2] = _window_matmuls(xwin[t % 2], w_ref, s=s, k_h=k_h,
                                   k_w=k_w, py=py, px=px)
    out_copy(t, t % 2).start()

    if total >= 2:                          # static: grid has a t-1 step
        @pl.when(t == total - 1)
        def _drain_prev():
            out_copy(t - 1, (t - 1) % 2).wait()

    @pl.when(t == total - 1)
    def _drain_last():                      # pipeline epilogue
        out_copy(t, t % 2).wait()


def _tile_vmem_bytes(mode: str, layer, tile, ic_t: int, oc_t: int,
                     bp: int) -> int:
    """VMEM one grid step of ``mode`` ("whole" / "window") holds for a
    tile at padded batch ``bp``: pipelined blocks twice (Pallas double
    buffers them), DMA scratch slots, and the window's working values
    (patch + accumulator)."""
    w = tile.window
    s = layer.stride
    py = (w.pw_h - layer.k_h) // s + 1
    px = (w.pw_w - layer.k_w) // s + 1
    kernel = _vmem_bytes(layer.k_h * layer.k_w, ic_t, oc_t)
    win = _vmem_bytes(w.pw_h, w.pw_w, bp, ic_t)
    out = _vmem_bytes(py, px, bp, oc_t)
    working = win + _vmem_bytes(py * px * bp, ic_t) + out
    if mode == "whole":
        return (2 * (_vmem_bytes(layer.i_h, layer.i_w, bp, ic_t) + kernel
                     + _vmem_bytes(layer.o_h, layer.o_w, bp, oc_t))
                + working)
    return 2 * kernel + 2 * win + 2 * out + working


def _tile_mode(block: str, layer, tile, ic_t: int, oc_t: int, bp: int,
               vmem_budget: int) -> str:
    """The tiling a tile runs in: ``block``, or for "auto" the whole-array
    blocks when their working set fits ``vmem_budget``, else windows."""
    if block != "auto":
        return block
    whole = _tile_vmem_bytes("whole", layer, tile, ic_t, oc_t, bp)
    return "whole" if whole <= vmem_budget else "window"


def sdk_tilings(mapping: LayerMapping, batch: Optional[int], *,
                block: str = "auto",
                vmem_budget: Optional[int] = None) -> Tuple[str, ...]:
    """The tiling ("whole" / "window") each tile of ``mapping`` runs in
    at ``batch`` (``None`` — one example, padded to 8 rows)."""
    if vmem_budget is None:
        vmem_budget = default_vmem_budget()
    bp = _round_up(batch or 1, _SUBLANES)
    return tuple(_tile_mode(block, mapping.layer, tile,
                            *_tile_passes(mapping, tile)[::2], bp,
                            vmem_budget)
                 for tile in mapping.tiles)


def sdk_refusal(mapping: LayerMapping, batch: Optional[int], *,
                block: str = "auto",
                vmem_budget: Optional[int] = None) -> Optional[str]:
    """Why the compiled (Mosaic) sdk kernel cannot run ``mapping`` at
    ``batch`` — None when it can.  A static rule from the TPU tiling
    constraints, checked before anything is traced: a tile whose
    working set (padded to (8, 128) tiles, see :func:`_tile_vmem_bytes`)
    exceeds :data:`VMEM_LIMIT_CAP` has no tiling the chip accepts.
    ``batch=None`` prices one example (padded to 8 rows)."""
    bp = _round_up(batch or 1, _SUBLANES)
    modes = sdk_tilings(mapping, batch, block=block, vmem_budget=vmem_budget)
    for tile, mode in zip(mapping.tiles, modes):
        ic_t, _, oc_t, _ = _tile_passes(mapping, tile)
        need = _tile_vmem_bytes(mode, mapping.layer, tile, ic_t, oc_t, bp)
        if need > VMEM_LIMIT_CAP:
            return (f"{mode} tiling needs {need / 2**20:.1f} MiB VMEM at "
                    f"batch {bp} (cap {VMEM_LIMIT_CAP / 2**20:.0f} MiB)")
    return None


def sdk_conv_traced(mapping: LayerMapping, x: jnp.ndarray,
                    kernel: jnp.ndarray, *, interpret: bool = False,
                    block: str = "auto",
                    vmem_budget: Optional[int] = None) -> jnp.ndarray:
    """Trace-time body of :func:`sdk_conv` — see it for the contract.
    Public plan-consuming entry: `repro.exec.run` inlines it into the
    whole-network program.  Builds one pallas_call per (group, tile);
    stand-alone dispatch goes through :func:`sdk_conv_jit` so the
    closures are built once per static (mapping, shapes, flags)
    signature, not once per call."""
    if vmem_budget is None:     # trace-time resolution (static argument)
        vmem_budget = default_vmem_budget()
    _trace_counts[_trace_key(mapping, x, kernel, interpret=interpret,
                             block=block, vmem_budget=vmem_budget)] += 1
    layer = mapping.layer
    s = layer.stride
    b = x.shape[0]
    bp = _round_up(b, _SUBLANES)
    o_h, o_w = layer.o_h, layer.o_w
    g = mapping.group
    ic_g, oc_g = layer.ic // g, layer.oc // g
    if kernel.shape != (layer.k_h, layer.k_w, ic_g, layer.oc):
        raise ValueError(f"kernel shape {kernel.shape} != grouped layout "
                         f"{(layer.k_h, layer.k_w, ic_g, layer.oc)}")
    if block not in ("auto", "whole", "window"):
        raise ValueError(f"unknown block mode {block!r}")
    if not interpret:
        why = sdk_refusal(mapping, b, block=block, vmem_budget=vmem_budget)
        if why is not None:
            raise ValueError(f"{layer.name}: sdk kernel refused: {why}")

    outs = []
    for gi in range(g):
        with jax.named_scope(PLACE):
            xg = x[:, gi * ic_g:(gi + 1) * ic_g]
            kg = kernel[:, :, :, gi * oc_g:(gi + 1) * oc_g]
            acc = jnp.zeros((b, oc_g, o_h, o_w), jnp.float32)
        c_base = 0
        for tile in mapping.tiles:
            kept = tile.depth
            ic_t, ar_c, oc_t, ac_c = _tile_passes(mapping, tile)
            ic_pad, oc_pad = ar_c * ic_t, ac_c * oc_t
            mode = _tile_mode(block, layer, tile, ic_t, oc_t, bp,
                              vmem_budget)
            # The window DMAs slice HBM arrays, whose lane dimension must
            # then be whole 128-lane tiles: pad the channels with zeros.
            lanes = _LANES if mode == "window" else 1
            ic_l, oc_l = _round_up(ic_t, lanes), _round_up(oc_t, lanes)
            with jax.named_scope(PLACE):
                # channel passes lead, channels on lanes, batch (zero-
                # padded to whole sublane tiles) on sublanes:
                # (ar_c, H, W, Bp, ic_l)
                xt = jnp.pad(xg[:, c_base:c_base + kept],
                             ((0, bp - b), (0, ic_pad - kept), (0, 0),
                              (0, 0)))
                xt = xt.reshape(bp, ar_c, ic_t, layer.i_h, layer.i_w)
                xt = jnp.pad(xt.transpose(1, 3, 4, 0, 2),
                             ((0, 0),) * 4 + ((0, ic_l - ic_t),))
                # (ar_c, ac_c, k_h, k_w, ic_l, oc_l) kernel blocks
                kt = jnp.pad(kg[:, :, c_base:c_base + kept],
                             ((0, 0), (0, 0), (0, ic_pad - kept),
                              (0, oc_pad - oc_g)))
                kt = kt.reshape(layer.k_h, layer.k_w, ar_c, ic_t, ac_c,
                                oc_t)
                kt = jnp.pad(kt.transpose(2, 4, 0, 1, 3, 5),
                             ((0, 0),) * 4 + ((0, ic_l - ic_t),
                                              (0, oc_l - oc_t)))

            w = tile.window
            py = (w.pw_h - layer.k_h) // s + 1
            px = (w.pw_w - layer.k_w) // s + 1
            step_y, step_x, ny, nx, lim_y, lim_x = _tile_grid(layer, tile)
            geom = dict(s=s, k_h=layer.k_h, k_w=layer.k_w, pw_h=w.pw_h,
                        pw_w=w.pw_w, py=py, px=px, step_y=step_y,
                        step_x=step_x, nx=nx, lim_y=lim_y, lim_x=lim_x)
            need = _tile_vmem_bytes(mode, layer, tile, ic_t, oc_t, bp)
            params = pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=min(VMEM_LIMIT_CAP,
                                     max(32 * 2**20, 2 * need)))
            w_spec = pl.BlockSpec(
                (1, 1, layer.k_h, layer.k_w, ic_l, oc_l),
                lambda ci, oi, wi: (ci, oi, 0, 0, 0, 0))
            out_shape = jax.ShapeDtypeStruct(
                (ar_c, ac_c, o_h, o_w, bp, oc_l), jnp.float32)
            with jax.named_scope(MACRO):
                if mode == "window":
                    res = pl.pallas_call(
                        functools.partial(_sdk_kernel_blocked, ac_c=ac_c,
                                          nw=ny * nx, **geom),
                        grid=(ar_c, ac_c, ny * nx),
                        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM),
                                  w_spec],
                        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
                        out_shape=out_shape,
                        scratch_shapes=[       # two slots: double-buffered DMA
                            pltpu.VMEM((2, w.pw_h, w.pw_w, bp, ic_l),
                                       jnp.float32),
                            pltpu.VMEM((2, py, px, bp, oc_l), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,)),
                        ],
                        compiler_params=params,
                        interpret=interpret,
                        name="sdk_conv",
                    )(xt, kt)
                else:
                    res = pl.pallas_call(
                        functools.partial(_sdk_kernel, **geom),
                        grid=(ar_c, ac_c, ny * nx),
                        in_specs=[
                            pl.BlockSpec(
                                (1, layer.i_h, layer.i_w, bp, ic_t),
                                lambda ci, oi, wi: (ci, 0, 0, 0, 0)),
                            w_spec,
                        ],
                        out_specs=pl.BlockSpec(
                            (1, 1, o_h, o_w, bp, oc_t),
                            lambda ci, oi, wi: (ci, oi, 0, 0, 0, 0)),
                        out_shape=out_shape,
                        compiler_params=params,
                        interpret=interpret,
                        name="sdk_conv",
                    )(xt, kt)
            with jax.named_scope(PLACE):
                # shift-and-add over the channel passes, back to NCHW
                res = res[..., :oc_t].sum(axis=0).transpose(3, 0, 4, 1, 2)
                acc = acc + res.reshape(bp, oc_pad, o_h, o_w)[:b, :oc_g]
            # the tile's pruned trailing channels are skipped, not
            # shifted into the next tile's range
            c_base += kept + tile.pruned_channels
        outs.append(acc)
    with jax.named_scope(PLACE):
        return jnp.concatenate(outs, axis=1).astype(
            jnp.result_type(x, kernel))


#: Host-side trace counter keyed by the static signature — retracing
#: regressions are asserted in tests/test_sdk_conv.py.  Bounded like the
#: memo caches: oldest signatures drop first (jit itself keeps its own
#: cache, so the counter is diagnostics, not correctness).
_trace_counts: Dict[Tuple, int] = collections.defaultdict(int)
_TRACE_COUNT_LIMIT = 1024


def _trace_key(mapping, x, kernel, **flags) -> Tuple:
    while len(_trace_counts) >= _TRACE_COUNT_LIMIT:
        del _trace_counts[next(iter(_trace_counts))]
    return (mapping, x.shape, x.dtype, kernel.shape, kernel.dtype,
            tuple(sorted(flags.items())))


sdk_conv_jit = functools.partial(
    jax.jit, static_argnums=(0,),
    static_argnames=("interpret", "block", "vmem_budget"))(sdk_conv_traced)
sdk_conv_jit.__doc__ = (
    """jit entry mirroring ``cim_conv2d_jit``: mapping (frozen dataclass)
    and the tiling flags are static — the per-(group, tile) pallas_call
    closures are built once per distinct (mapping, shapes, flags)
    signature instead of on every call.""")


def sdk_conv(mapping: LayerMapping, x: jnp.ndarray, kernel: jnp.ndarray,
             *, interpret: bool = False, block: str = "auto",
             vmem_budget: Optional[int] = None) -> jnp.ndarray:
    """Execute a convolution exactly as `mapping` prescribes, on the MXU.

    Same contract as cnn.cim_conv2d: x (batch, ic, i_h, i_w) pre-padded,
    kernel (k_h, k_w, ic // G, oc) in lax grouped layout, output
    (batch, oc, o_h, o_w); pruned channels are skipped.  One pallas_call
    per (group, tile); within it the grid enumerates the mapping's
    (channel pass, oc pass, window) loads, so total grid steps ==
    the mapping's ceil-form cycle count (see sdk_conv_cycles).  Channel /
    oc passes are padded to whole ``ic_t`` / ``oc_t`` blocks with zero
    weights (zero partial products), and each channel pass writes its own
    slot of a leading accumulator axis that is summed on the host — the
    shift-and-add partial-sum accumulation of Fig 3.

    ``block`` picks the tiling: "whole" keeps the full feature map and
    OFM as VMEM blocks (fastest when they fit), "window" DMAs one
    window patch / output tile per grid step with the loads and stores
    double-buffered against the MXU (:func:`_sdk_kernel_blocked` — VMEM
    use independent of layer size), "auto" chooses "window" whenever the
    whole-array working set exceeds ``vmem_budget`` (``None`` —
    :func:`default_vmem_budget`, i.e. ``REPRO_SDK_VMEM_BUDGET`` or
    8 MiB).

    Dispatches through :func:`sdk_conv_jit` (mapping and flags static):
    repeat calls with the same shapes reuse the compiled program instead
    of rebuilding every pallas_call closure.
    """
    if vmem_budget is None:     # resolve before dispatch: None and the
        vmem_budget = default_vmem_budget()  # explicit default share a
    return sdk_conv_jit(mapping, x, kernel, interpret=interpret,  # cache
                        block=block, vmem_budget=vmem_budget)     # entry


def sdk_conv_cycles(mapping: LayerMapping) -> int:
    """Total grid steps sdk_conv executes == the mapping's cycle count in
    the ceil-form convention (tiles with marginal sets run their border
    loads as clamped regular windows, so floor+marginal counts map to the
    equivalent ceil raster), times the sequential group count."""
    total = 0
    for tile in mapping.tiles:
        _, _, ny, nx, _, _ = _tile_grid(mapping.layer, tile)
        _, ar_c, _, ac_c = _tile_passes(mapping, tile)
        total += ar_c * ac_c * ny * nx
    return total * mapping.group
