"""Macro-parallel mapped-network executor (cnn/mapped_net.py): forward
equivalence against the lax.conv composition, executed grid steps ==
analytical cycle counts, exact gradients, and the shard_map device path.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ArrayConfig, ConvLayerSpec, MacroGrid, map_layer,
                        map_net, networks)
from repro.cnn.cim_conv import reference_conv2d
from repro.cnn.mapped_net import (assert_steps_match, executed_steps,
                                  layer_schedule, mapped_conv2d,
                                  mapped_net_apply, network_schedule,
                                  reference_net_apply, zero_pruned_kernels)

RNG = np.random.RandomState(11)


def _layer_data(m, batch=2):
    lay = m.layer
    x = jnp.asarray(RNG.randn(batch, lay.ic, lay.i_h, lay.i_w), jnp.float32)
    k = jnp.asarray(RNG.randn(lay.k_h, lay.k_w, lay.ic // m.group, lay.oc),
                    jnp.float32)
    pruned = sum(t.pruned_channels for t in m.tiles)
    if pruned:
        k = k.at[:, :, lay.ic // m.group - pruned:, :].set(0.0)
    return x, k


def _check_layer(layer, alg, arr, grid, **kw):
    m = map_layer(layer, arr, alg, grid, **kw)
    x, k = _layer_data(m)
    y = mapped_conv2d(m, x, k)
    ref = reference_conv2d(layer, x, k, groups=m.group)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)
    assert executed_steps(m) == m.cycles
    return m


@pytest.mark.parametrize("grid", [MacroGrid(1, 1), MacroGrid(2, 2),
                                  MacroGrid(4, 2), MacroGrid(1, 16)])
def test_mapped_conv2d_grids(grid):
    """The executor realizes every grid shape layer_cycles accounts for:
    rows parallelize channel passes, columns oc passes."""
    _check_layer(ConvLayerSpec("t", 18, 18, 3, 3, 32, 32), "Tetris-SDK",
                 ArrayConfig(64, 64), grid)


@pytest.mark.parametrize("alg", ["img2col", "SDK", "VW-SDK", "Tetris-SDK",
                                 "TetrisG-SDK"])
def test_mapped_conv2d_algorithms(alg):
    _check_layer(ConvLayerSpec("t", 18, 18, 3, 3, 24, 32), alg,
                 ArrayConfig(64, 64), MacroGrid(2, 2))


def test_mapped_conv2d_strided_and_grouped():
    _check_layer(ConvLayerSpec("s", 10, 10, 3, 3, 8, 8, stride=2),
                 "Tetris-SDK", ArrayConfig(128, 128), MacroGrid(2, 2))
    m = _check_layer(ConvLayerSpec("g", 18, 18, 3, 3, 32, 32),
                     "TetrisG-SDK", ArrayConfig(64, 64), MacroGrid(2, 4))
    assert m.group > 1
    _check_layer(ConvLayerSpec("dw", 10, 10, 3, 3, 16, 16, groups=16),
                 "Tetris-SDK", ArrayConfig(128, 128), MacroGrid(2, 2))


def test_group_rounds_time_multiplex():
    """More groups than the grid's group-parallel slots: rounds > 1 and
    the step count reflects the time multiplexing."""
    m = map_layer(ConvLayerSpec("dw", 10, 10, 3, 3, 16, 16, groups=16),
                  ArrayConfig(128, 128), "Tetris-SDK", MacroGrid(2, 2))
    s = layer_schedule(m)
    assert m.group == 16 and s.group_rounds > 1
    assert s.steps == m.cycles


def test_mapped_net_cnn8():
    """Whole-network forward through the mapped path == lax.conv
    composition; total executed steps == NetworkMapping.total_cycles."""
    net = map_net("cnn8", networks.cnn8(), ArrayConfig(64, 64),
                  "TetrisG-SDK", MacroGrid(2, 2), groups=(1, 2, 4))
    ks = zero_pruned_kernels(net, [
        _layer_data(m)[1] * 0.1 for m in net.layers])
    x = jnp.asarray(RNG.randn(2, 24, 18, 18), jnp.float32)
    y = mapped_net_apply(net, ks, x)
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(r))))
    assert any(m.group > 1 for m in net.layers)          # grouped layers ran
    assert sum(s.steps for s in network_schedule(net)) == net.total_cycles


def test_mapped_net_densenet_slice():
    """DenseNet40 slice across a transition: dense-concat chaining,
    marginal-window layers, 1x1 transition + spatial pooling."""
    layers = networks.densenet40()[10:15]    # b1l11, b1l12, t1, b2l1, b2l2
    net = map_net("dn40", layers, ArrayConfig(64, 64), "TetrisG-SDK",
                  MacroGrid(4, 1), groups=(1, 2))
    assert any(t.marginals for m in net.layers for t in m.tiles)
    assert any(m.group > 1 for m in net.layers)
    ks = zero_pruned_kernels(net, [
        _layer_data(m)[1] * 0.1 for m in net.layers])
    x = jnp.asarray(RNG.randn(1, layers[0].ic, layers[0].i_h,
                              layers[0].i_w), jnp.float32)
    y = mapped_net_apply(net, ks, x)
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(r))))
    assert_steps_match(net)


def test_mapped_net_strided_chain():
    """A strided layer inside a chained stack."""
    layers = [
        ConvLayerSpec("a", 18, 18, 3, 3, 8, 16),
        ConvLayerSpec("b", 16, 16, 3, 3, 16, 16, stride=2),
        ConvLayerSpec("c", 9, 9, 3, 3, 16, 32),
    ]
    net = map_net("strided", layers, ArrayConfig(64, 64), "Tetris-SDK",
                  MacroGrid(2, 2))
    ks = zero_pruned_kernels(net, [
        _layer_data(m)[1] * 0.1 for m in net.layers])
    x = jnp.asarray(RNG.randn(2, 8, 18, 18), jnp.float32)
    y = mapped_net_apply(net, ks, x)
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(r))))


def test_steps_equal_cycles_all_bench_networks():
    """Executed schedule == analytical cycles for every bench network —
    host-side only, no compute (the Fig 20 contract)."""
    for name, fn in networks.NETWORKS.items():
        net = map_net(name, fn(), ArrayConfig(64, 64), "TetrisG-SDK",
                      MacroGrid(4, 4), groups=(1, 2))
        assert_steps_match(net)


def test_mapped_gradients_match_reference():
    """Training-path contract: gradients through the macro-parallel
    executor equal the lax.conv gradients (overlapping border windows
    recompute identical values; the scatter transpose must not
    double-count)."""
    layer = ConvLayerSpec("CNN8-2", 18, 18, 3, 3, 24, 32)
    m = map_layer(layer, ArrayConfig(64, 64), "TetrisG-SDK")
    x, k = _layer_data(m, batch=1)
    ic_g = layer.ic // m.group
    pruned = sum(t.pruned_channels for t in m.tiles)

    def zap(t):
        return t.at[:, :, ic_g - pruned:, :].set(0.0) if pruned else t

    gm = jax.grad(lambda kk: jnp.sum(mapped_conv2d(m, x, kk) ** 2))(k)
    gr = jax.grad(lambda kk: jnp.sum(
        reference_conv2d(layer, x, kk, groups=m.group) ** 2))(k)
    np.testing.assert_allclose(np.asarray(zap(gm)), np.asarray(zap(gr)),
                               rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(gr))))


@pytest.mark.slow
def test_train_through_mapped_executor():
    """train_cnn(executor="mapped") optimizes and tracks the reference
    path (identical init, data, and schedule)."""
    from repro.cnn.models import cnn8_config
    from repro.cnn.train import train_cnn
    kw = dict(steps=20, batch=32, n_train=256, n_test=64)
    rm = train_cnn(cnn8_config(group=2), executor="mapped",
                   grid=MacroGrid(2, 2), **kw)
    rr = train_cnn(cnn8_config(group=2), **kw)
    assert np.isfinite(rm.final_loss)
    assert abs(rm.final_loss - rr.final_loss) < 1e-2
    assert rm.executor == "mapped"


class _FakeMesh:
    def __init__(self, **shape):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)


def test_macro_pass_specs_data_axis():
    """Spec selection: a "data" axis shards the batch axis of patches and
    output; weights replicate across it; psum stays confined to "row"."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import macro_mesh_fits, macro_pass_specs
    plain = _FakeMesh(row=2, col=2)
    assert macro_pass_specs(plain) == (P("row"), P("row", "col"), P("col"))
    assert macro_pass_specs(None) == (P("row"), P("row", "col"), P("col"))
    data = _FakeMesh(data=2, row=2, col=2)
    p, w, o = macro_pass_specs(data)
    assert p == P("row", "data") and o == P("col", "data")
    assert w == P("row", "col")                  # replicated over "data"
    # fits: data meshes additionally require batch % data == 0
    assert macro_mesh_fits(plain, 2, 2)
    assert macro_mesh_fits(plain, 2, 2, batch=3)  # no data axis: any batch
    assert macro_mesh_fits(data, 2, 2, batch=4)
    assert not macro_mesh_fits(data, 2, 2, batch=3)
    assert not macro_mesh_fits(data, 2, 2)        # unknown batch
    assert not macro_mesh_fits(data, 3, 2, batch=4)


def test_make_macro_mesh_single_device_degenerate():
    """On one device every composition degenerates to the vmap path."""
    from repro.launch.mesh import make_macro_mesh, make_serving_mesh
    dev = jax.devices()[:1]
    assert make_macro_mesh(2, 2, dev) is None
    assert make_macro_mesh(2, 2, dev, data=1) is None
    assert make_macro_mesh(2, 2, dev, data=2) is None   # not enough devices
    assert make_serving_mesh(2, 2, 4, dev) is None
    with pytest.raises(ValueError):
        make_macro_mesh(2, 2, dev, data=0)


def test_data_axis_shard_map():
    """Tentpole contract: a (data=2, row=2, col=2) mesh on 8 forced host
    devices composes batch sharding with the macro grid — forward output
    agrees with the single-device vmap path to the reassociation rule
    (DESIGN.md §7) on a CNN8 slice, the psum stays confined to "row",
    and gradients agree to float-reassoc tolerance (vs the lax reference
    at the usual 1e-3)."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ArrayConfig, MacroGrid, map_net, networks
from repro.cnn.mapped_net import (mapped_conv2d, mapped_net_apply,
                                  reference_net_apply, zero_pruned_kernels)
from repro.exec import REASSOC_RTOL, reassoc_error
from repro.launch.mesh import make_macro_mesh, make_serving_mesh
assert len(jax.devices()) == 8
net = map_net("cnn8", networks.cnn8()[:3], ArrayConfig(64, 64),
              "Tetris-SDK", MacroGrid(2, 2))
assert all(m.sub_grid == MacroGrid(2, 2) for m in net.layers)
mesh = make_macro_mesh(2, 2, data=2)
assert dict(mesh.shape) == {"data": 2, "row": 2, "col": 2}
assert dict(make_serving_mesh(2, 2, 4).shape) == \\
    {"data": 2, "row": 2, "col": 2}
rng = np.random.RandomState(0)
ks = zero_pruned_kernels(net, [
    jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                          m.layer.ic // m.group, m.layer.oc) * 0.2,
                jnp.float32) for m in net.layers])
first = net.layers[0].layer
x = jnp.asarray(rng.randn(4, first.ic, first.i_h, first.i_w), jnp.float32)
y_sharded = mapped_net_apply(net, ks, x, mesh=mesh)
y_vmap = mapped_net_apply(net, ks, x)
assert reassoc_error(y_sharded, y_vmap) <= REASSOC_RTOL, \
    f"forward diverged: {reassoc_error(y_sharded, y_vmap)}"
ref = reference_net_apply(net, ks, x)
assert float(jnp.max(jnp.abs(y_sharded - ref))) < 1e-3

m0, k0 = net.layers[0], ks[0]
gs = jax.grad(lambda k: jnp.sum(mapped_conv2d(m0, x, k, mesh=mesh)**2))(k0)
gv = jax.grad(lambda k: jnp.sum(mapped_conv2d(m0, x, k)**2))(k0)
scale = float(jnp.max(jnp.abs(gv)))
assert float(jnp.max(jnp.abs(gs - gv))) < 1e-6 * scale, "grad diverged"
print("DATA-SHARDED-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "DATA-SHARDED-OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_shard_map_macro_path():
    """The shard_map realization on a real multi-device ("row", "col")
    mesh (forced host devices in a subprocess) matches lax.conv."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ArrayConfig, ConvLayerSpec, MacroGrid, map_layer
from repro.cnn.cim_conv import reference_conv2d
from repro.cnn.mapped_net import mapped_conv2d
from repro.launch.mesh import make_macro_mesh
assert len(jax.devices()) == 4
layer = ConvLayerSpec("t", 18, 18, 3, 3, 32, 32)
m = map_layer(layer, ArrayConfig(64, 64), "Tetris-SDK", MacroGrid(2, 2))
mesh = make_macro_mesh(m.sub_grid.r, m.sub_grid.c)
assert mesh is not None and dict(mesh.shape) == {"row": 2, "col": 2}
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(2, 32, 18, 18), jnp.float32)
k = jnp.asarray(rng.randn(3, 3, 32, 32), jnp.float32)
pruned = sum(t.pruned_channels for t in m.tiles)
if pruned: k = k.at[:, :, 32 - pruned:, :].set(0.0)
y = mapped_conv2d(m, x, k, mesh=mesh)
ref = reference_conv2d(layer, x, k)
assert float(jnp.max(jnp.abs(y - ref))) < 1e-3
print("SHARDED-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "SHARDED-OK" in out.stdout, out.stderr[-2000:]
