"""Mapping-driven Pallas executor (kernels.im2win_conv.sdk_conv) vs the
lax.conv oracle and the reference batched executor: both paths execute
the *same* LayerMapping (DESIGN.md equivalence contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArrayConfig, ConvLayerSpec, conv1d, map_layer
from repro.cnn import cim_conv2d, reference_conv2d
from repro.kernels import im2win_conv
from repro.kernels.im2win_conv import sdk_conv, sdk_conv_cycles

RNG = np.random.RandomState(7)


def _check(layer, alg, arr=ArrayConfig(512, 512), **kw):
    m = map_layer(layer, arr, alg, **kw)
    g = m.group
    ic_g = layer.ic // g
    x = jnp.asarray(RNG.randn(2, layer.ic, layer.i_h, layer.i_w),
                    jnp.float32)
    k = jnp.asarray(RNG.randn(layer.k_h, layer.k_w, ic_g, layer.oc),
                    jnp.float32)
    pruned = sum(t.pruned_channels for t in m.tiles)
    if pruned:
        k = k.at[:, :, ic_g - pruned:, :].set(0.0)
    y = sdk_conv(m, x, k, interpret=True)
    ref = reference_conv2d(layer, x, k, groups=g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)
    # the Pallas path and the reference batched path execute the same
    # mapping => identical results up to float summation order
    yr = cim_conv2d(m, x, k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-3, rtol=1e-3)
    return m


@pytest.mark.parametrize("alg", ["img2col", "VW-SDK", "Tetris-SDK",
                                 "TetrisG-SDK"])
def test_sdk_conv_equivalence(alg):
    _check(ConvLayerSpec("t", 18, 18, 3, 3, 24, 32), alg)


def test_sdk_conv_marginal_windows():
    m = _check(ConvLayerSpec("t", 18, 18, 3, 3, 32, 32), "Tetris-SDK")
    assert any(t.marginals for t in m.tiles)      # border loads exercised
    assert any(t.pruned_channels for t in m.tiles)


def test_sdk_conv_strided():
    _check(ConvLayerSpec("t", 10, 10, 3, 3, 8, 8, stride=2), "Tetris-SDK",
           ArrayConfig(128, 128))


@pytest.mark.slow
def test_sdk_conv_grouped_and_multi_tile():
    m = _check(ConvLayerSpec("t", 7, 7, 3, 3, 64, 64), "Tetris-SDK")
    assert len(m.tiles) > 1
    _check(ConvLayerSpec("t", 10, 10, 3, 3, 16, 16, groups=16),
           "Tetris-SDK", ArrayConfig(128, 128))


def test_sdk_conv_conv1d():
    _check(conv1d("t", 32, 4, 8, 8), "Tetris-SDK", ArrayConfig(128, 128))


def test_sdk_conv_window_blocked():
    """The DMA window-blocked path (BlockSpecs smaller than whole-array:
    one window patch + one output tile in VMEM per grid step) matches the
    whole-array path and the oracle, marginals and stride included."""
    for layer, arr in (
            (ConvLayerSpec("t", 18, 18, 3, 3, 32, 32), ArrayConfig(512, 512)),
            (ConvLayerSpec("s", 10, 10, 3, 3, 8, 8, stride=2),
             ArrayConfig(128, 128))):
        m = map_layer(layer, arr, "Tetris-SDK")
        ic_g = layer.ic // m.group
        x = jnp.asarray(RNG.randn(2, layer.ic, layer.i_h, layer.i_w),
                        jnp.float32)
        k = jnp.asarray(RNG.randn(layer.k_h, layer.k_w, ic_g, layer.oc),
                        jnp.float32)
        pruned = sum(t.pruned_channels for t in m.tiles)
        if pruned:
            k = k.at[:, :, ic_g - pruned:, :].set(0.0)
        yw = sdk_conv(m, x, k, interpret=True, block="window")
        y0 = sdk_conv(m, x, k, interpret=True, block="whole")
        ref = reference_conv2d(layer, x, k, groups=m.group)
        np.testing.assert_allclose(np.asarray(yw), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(yw), np.asarray(y0),
                                   atol=1e-3, rtol=1e-3)


def test_sdk_conv_auto_block_big_layer():
    """auto mode drops to window blocks when the whole-array working set
    exceeds the VMEM budget (big Inception-style layer)."""
    layer = ConvLayerSpec("big", 30, 30, 5, 5, 16, 32)
    m = map_layer(layer, ArrayConfig(64, 64), "VW-SDK")
    x = jnp.asarray(RNG.randn(1, layer.ic, 30, 30), jnp.float32)
    k = jnp.asarray(RNG.randn(5, 5, 16, 32), jnp.float32)
    y = sdk_conv(m, x, k, interpret=True, block="auto",
                 vmem_budget=64 * 1024)     # force the window path
    ref = reference_conv2d(layer, x, k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


def _double_buffer_case():
    """Stride-2 mapping exercising every blocked-kernel hazard at once:
    multiple channel passes (slot reuse across ci), marginal windows
    (border-clamped prefetch origins) and pruned channels."""
    layer = ConvLayerSpec("db", 11, 11, 3, 3, 16, 16, stride=2)
    m = map_layer(layer, ArrayConfig(128, 128), "Tetris-SDK")
    assert any(t.marginals for t in m.tiles)
    assert any(t.pruned_channels for t in m.tiles)
    assert any(t.ar_c > 1 for t in m.tiles)
    ic_g = layer.ic // m.group
    x = jnp.asarray(RNG.randn(2, layer.ic, layer.i_h, layer.i_w),
                    jnp.float32)
    k = jnp.asarray(RNG.randn(layer.k_h, layer.k_w, ic_g, layer.oc),
                    jnp.float32)
    pruned = sum(t.pruned_channels for t in m.tiles)
    k = k.at[:, :, ic_g - pruned:, :].set(0.0)
    return layer, m, x, k


def test_double_buffered_window_blocked():
    """The double-buffered DMA pipeline (prefetch window t+1 during the
    MXU step t, stores drained on slot reuse) matches block="whole" and
    both reference executors on the stride>1 + marginal + pruned case,
    and the steps==cycles contract is untouched."""
    layer, m, x, k = _double_buffer_case()
    yw = sdk_conv(m, x, k, interpret=True, block="window")
    y0 = sdk_conv(m, x, k, interpret=True, block="whole")
    ref = reference_conv2d(layer, x, k, groups=m.group)
    yr = cim_conv2d(m, x, k)
    np.testing.assert_allclose(np.asarray(yw), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(yw), np.asarray(y0),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(yw), np.asarray(yr),
                               atol=1e-3, rtol=1e-3)
    # steps==cycles contract, unchanged by double-buffering: exact on a
    # ceil-form (marginal-free) mapping of the same strided layer
    mv = map_layer(layer, ArrayConfig(512, 512), "VW-SDK")
    assert not any(t.marginals for t in mv.tiles)
    assert sdk_conv_cycles(mv) == mv.cycles
    yv = sdk_conv(mv, x, k, interpret=True, block="window")
    np.testing.assert_allclose(
        np.asarray(yv),
        np.asarray(reference_conv2d(layer, x, k, groups=mv.group)),
        atol=1e-3, rtol=1e-3)


def test_sdk_conv_no_retrace():
    """sdk_conv dispatches through a static-shape-keyed jit entry: repeat
    calls with identical (mapping, shapes, flags) must not rebuild the
    pallas_call closures; new shapes/flags trace exactly once each."""
    layer = ConvLayerSpec("t", 12, 12, 3, 3, 8, 8)
    m = map_layer(layer, ArrayConfig(256, 256), "VW-SDK")
    x = jnp.asarray(RNG.randn(2, 8, 12, 12), jnp.float32)
    k = jnp.asarray(RNG.randn(3, 3, 8, 8), jnp.float32)
    im2win_conv._trace_counts.clear()
    for _ in range(3):
        sdk_conv(m, x, k, interpret=True)
    assert list(im2win_conv._trace_counts.values()) == [1]
    sdk_conv(m, x[:1], k, interpret=True)         # new batch: one retrace
    sdk_conv(m, x, k, interpret=True, block="window")  # new flag: one more
    assert sorted(im2win_conv._trace_counts.values()) == [1, 1, 1]


def test_grid_steps_match_ceil_cycles():
    """The pallas grid enumerates the mapping's loads: for a ceil-form
    (marginal-free, single-macro) mapping the step count equals the
    mapping's cycle count exactly."""
    layer = ConvLayerSpec("t", 18, 18, 3, 3, 24, 32)
    m = map_layer(layer, ArrayConfig(512, 512), "VW-SDK")
    assert not any(t.marginals for t in m.tiles)
    assert sdk_conv_cycles(m) == m.cycles
    # SDK tiles multiplex rows over ar_c passes; the grid must account
    # (and execute) those passes too
    ms = map_layer(layer, ArrayConfig(512, 512), "SDK")
    assert sdk_conv_cycles(ms) == ms.cycles
    _check(layer, "SDK")
