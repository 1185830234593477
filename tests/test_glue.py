"""Direct unit tests of the inter-layer glue (repro/exec/glue.py):
fit_spatial / center_crop geometry (odd sizes, identity no-op,
pool-then-pad) and the chain-classification errors — previously only
exercised indirectly through whole-net runs."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec.glue import (center_crop, fit_spatial, resolve_chain)


def _x(h, w, b=2, c=3, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(b, c, h, w), jnp.float32)


def test_fit_spatial_identity_noop():
    x = _x(18, 18)
    assert fit_spatial(x, 18, 18) is x


def test_fit_spatial_center_pad_even_and_odd():
    x = _x(5, 4)
    y = fit_spatial(x, 8, 7)
    assert y.shape[-2:] == (8, 7)
    # centred: floor(pad/2) before, remainder after
    np.testing.assert_array_equal(np.asarray(y[..., 1:6, 1:5]),
                                  np.asarray(x))
    assert float(jnp.abs(y).sum()) == pytest.approx(
        float(jnp.abs(x).sum()), rel=1e-6)      # zero padding only


def test_fit_spatial_center_crop_odd_sizes():
    x = _x(9, 7)
    y = fit_spatial(x, 6, 4)
    assert y.shape[-2:] == (6, 4)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(x[..., 1:7, 1:5]))


def test_fit_spatial_pools_exact():
    """>= 2x on both axes pools (2x2 max) down to the exact target —
    the DenseNet transition shape."""
    x = _x(16, 16)
    y = fit_spatial(x, 8, 8)
    pooled = jnp.max(x.reshape(2, 3, 8, 2, 8, 2), axis=(3, 5))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(pooled))


def test_fit_spatial_pools_then_crops_odd_target():
    """Pooling stops below 2x the target; the odd remainder is cropped
    (a leading slice when the surplus is a single row/column)."""
    x = _x(16, 16)
    y = fit_spatial(x, 7, 7)
    assert y.shape[-2:] == (7, 7)
    pooled = jnp.max(x.reshape(2, 3, 8, 2, 8, 2), axis=(3, 5))
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(pooled[..., :7, :7]))


def test_fit_spatial_pools_only_when_both_axes_large():
    x = _x(16, 6)                 # width below 2x target: no pooling
    y = fit_spatial(x, 8, 6)
    assert y.shape[-2:] == (8, 6)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(x[..., 4:12, :]))


def test_center_crop_odd_and_identity():
    x = _x(7, 9)
    np.testing.assert_array_equal(np.asarray(center_crop(x, 7, 9)),
                                  np.asarray(x))
    y = center_crop(x, 4, 5)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(x[..., 1:5, 2:7]))


def test_resolve_chain_kinds_and_error():
    assert resolve_chain("a", 32, 16, "b", 32) == "chain"
    assert resolve_chain("a", 32, 16, "b", 48) == "concat"
    with pytest.raises(ValueError, match=r"cannot chain a \(oc=32, "
                                         r"carry=16\) into b \(ic=40\)"):
        resolve_chain("a", 32, 16, "b", 40)


def test_concat_carry_mismatch_raises_at_compile():
    """A DenseNet-style stack whose concat arithmetic breaks raises the
    clear chaining error from compile_plan (not mid-forward)."""
    from repro.core import ArrayConfig, ConvLayerSpec, MacroGrid, map_net
    from repro.exec import compile_plan
    layers = [
        ConvLayerSpec("a", 10, 10, 3, 3, 8, 12),
        ConvLayerSpec("b", 8, 8, 3, 3, 20, 12),    # 8 + 12: concat, ok
        ConvLayerSpec("c", 6, 6, 3, 3, 13, 8),     # neither 12 nor 32
    ]
    net = map_net("bad", layers, ArrayConfig(64, 64), "Tetris-SDK",
                  MacroGrid(1, 1))
    with pytest.raises(ValueError, match="cannot chain b"):
        compile_plan(net, executor_policy="reference")


# ---------------------------------------------------------------------------
# Inception module glue: the pool stages and the static write count
# ---------------------------------------------------------------------------

def _np_max_pool(x, stride, lo, hi):
    """3x3 max-pool in numpy over x padded with -inf by ``lo`` before and
    ``hi`` after each spatial axis."""
    xp = np.pad(np.asarray(x), ((0, 0), (0, 0), (lo, hi), (lo, hi)),
                constant_values=-np.inf)
    n = (xp.shape[-1] - 3) // stride + 1
    return np.stack([np.stack([xp[..., i * stride:i * stride + 3,
                                  j * stride:j * stride + 3].max((-2, -1))
                               for j in range(n)], -1)
                     for i in range(n)], -2)


@pytest.mark.parametrize("size", [4, 7, 8])
def test_stage_pool_max3s1_keeps_size_with_neg_inf_padding(size):
    from repro.exec.glue import stage_pool
    x = _x(size, size) - 10.0          # all negative: a zero pad would win
    y = stage_pool(x, "max3s1")
    assert y.shape == x.shape
    np.testing.assert_array_equal(np.asarray(y), _np_max_pool(x, 1, 1, 1))


@pytest.mark.parametrize("size,out", [(28, 14), (14, 7), (8, 4), (7, 3)])
def test_stage_pool_max3s2_is_ceil_mode(size, out):
    """GoogLeNet's stage pools: 28 -> 14 -> 7, the last window hanging
    past the far edge where ceil((n - 3) / 2) rounds up."""
    from repro.exec.glue import pool_size, stage_pool
    assert pool_size(size, "max3s2") == out
    assert pool_size(size, "max3s1") == size == pool_size(size, "none")
    x = _x(size, size) - 10.0
    y = stage_pool(x, "max3s2")
    assert y.shape[-2:] == (out, out)
    hi = 2 * (out - 1) + 3 - size
    np.testing.assert_array_equal(np.asarray(y),
                                  _np_max_pool(x, 2, 0, hi))


def test_stage_pool_none_is_identity():
    from repro.exec.glue import stage_pool
    x = _x(5, 5)
    assert stage_pool(x, "none") is x


def test_glue_spec_refuses_unknown_pool_and_kind():
    from repro.core import GlueSpec
    with pytest.raises(ValueError, match="unknown glue pool"):
        GlueSpec(pool="avg3s1")
    with pytest.raises(ValueError, match="unknown glue kind"):
        GlueSpec(kind="fork")
