"""Macro-parallel mapped-network executor (cnn/mapped_net.py): forward
equivalence against the lax.conv composition, executed grid steps ==
analytical cycle counts, exact gradients, and the shard_map device path.
"""
import functools
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ArrayConfig, ConvLayerSpec, MacroGrid, map_layer,
                        map_net, networks)
from repro.cnn.cim_conv import (placement_groups, reference_conv2d,
                                scatter_indices)
from repro.cnn.mapped_net import (_macro_step, _tile_dims, _tile_operands,
                                  assert_steps_match, executed_steps,
                                  layer_schedule, mapped_conv2d,
                                  mapped_conv2d_jit, mapped_net_apply,
                                  network_schedule, reference_net_apply,
                                  zero_pruned_kernels)
from repro.exec import REASSOC_RTOL, compile_plan, reassoc_error

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


# ---------------------------------------------------------------------------
# Placement: channel super-steps summed, then each tile placed once
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _bench_mapping(name):
    """The benchmark's mapping of a network (bench/configs): TetrisG-SDK
    on one 512x512 array, grouping factors 1, 2 or 4."""
    return map_net(name, networks.NETWORKS[name](), ArrayConfig(512, 512),
                   "TetrisG-SDK", MacroGrid(), groups=(1, 2, 4))


def _bench_layer(name):
    net = _bench_mapping("cnn8" if name.startswith("CNN8") else "densenet40")
    return next(m for m in net.layers if m.layer.name == name)


def _tile_blocks(m):
    """(R, C, window shapes) of every tile: the executor's loop bounds."""
    return [_tile_dims(m, t)[:2] + (len(placement_groups(m.layer, t)),)
            for t in m.tiles]


@functools.partial(jax.jit, static_argnums=0)
def _scatter_placement(m, x, k):
    """The former placement, kept as an oracle: each channel super-step
    writes every shape's results into a zero buffer by a set-semantics
    scatter, and the buffers are summed across super-steps."""
    lay, g, b = m.layer, m.group, x.shape[0]
    ic_g, oc_g = lay.ic // g, lay.oc // g
    kr = k.reshape(lay.k_h, lay.k_w, ic_g, g, oc_g)
    xr = x.reshape(b, g, ic_g, lay.i_h, lay.i_w)
    out = jnp.zeros((b, g, oc_g, lay.o_h, lay.o_w), x.dtype)
    sub, c_base = m.sub_grid, 0
    for tile in m.tiles:
        kept, oc_t = tile.depth, m.tile_passes(tile)[2]
        R, C, ic_pad, oc_pad = _tile_dims(m, tile)
        xc = jnp.pad(xr[:, :, c_base:c_base + kept],
                     ((0, 0), (0, 0), (0, ic_pad - kept), (0, 0), (0, 0)))
        ks = jnp.pad(kr[:, :, c_base:c_base + kept],
                     ((0, 0), (0, 0), (0, ic_pad - kept), (0, 0),
                      (0, oc_pad - oc_g)))
        shapes = []
        for (p_all, w_all), ((ph, pw), o) in zip(
                _tile_operands(m, tile, xc, ks, R, C),
                placement_groups(lay, tile).items()):
            py = (ph - lay.k_h) // lay.stride + 1
            px = (pw - lay.k_w) // lay.stride + 1
            shapes.append((p_all, w_all, py, px,
                           scatter_indices(o, py, px, lay.stride)))
        acc = jnp.zeros((b, g, oc_pad, lay.o_h, lay.o_w), x.dtype)
        soc = sub.c * oc_t
        for ri in range(R):
            buf = jnp.zeros_like(acc)
            for ci in range(C):
                for p_all, w_all, py, px, (OY, OX) in shapes:
                    res = _macro_step(p_all[ri], w_all[ri, ci])
                    n = res.shape[3]
                    vals = res.reshape(sub.c, b, g, n, py, px, oc_t)
                    vals = vals.transpose(1, 2, 0, 6, 3, 4, 5).reshape(
                        b, g, soc, n, py, px)
                    buf = buf.at[:, :, ci * soc:(ci + 1) * soc,
                                 OY, OX].set(vals)
            acc = acc + buf
        out = out + acc[:, :, :oc_g]
        c_base += kept + tile.pruned_channels
    return out.reshape(b, lay.oc, lay.o_h, lay.o_w)


@pytest.mark.parametrize("name,blocks", [
    ("DN40-b3l12", [(44, 1, 1)]),           # 44 channel super-steps
    ("DN40-b2l3", [(18, 1, 1), (1, 3, 1)]),  # a tile of 3 oc super-steps
    ("DN40-t1", [(1, 1, 2)]),               # 1x1 kernel, 171 windows
    ("DN40-b1l3", [(1, 1, 3), (1, 1, 2)]),  # overlapping marginal windows
    ("CNN8-2", [(1, 1, 3)]),                # g=4, three shapes
    ("CNN8-7", [(4, 1, 1)]),                # 1x1 output
])
def test_placement_extreme_schedules(name, blocks):
    """Summing the super-steps and placing each tile once computes the
    convolution, and the former per-super-step scatter placement to the
    reassociation rule, on the benchmark's most extreme schedules."""
    m = _bench_layer(name)
    assert _tile_blocks(m) == blocks
    if name == "DN40-t1":
        assert layer_schedule(m).tiles[0].window_loads == 171
    if name == "CNN8-2":
        assert m.group == 4
    lay = m.layer
    x = jnp.asarray(RNG.randn(2, lay.ic, lay.i_h, lay.i_w), jnp.float32)
    k = jnp.asarray(RNG.randn(lay.k_h, lay.k_w, lay.ic // m.group, lay.oc),
                    jnp.float32)
    k = zero_pruned_kernels(SimpleNamespace(layers=[m]), [k])[0]
    with jax.default_matmul_precision("highest"):
        y = mapped_conv2d(m, x, k)
        old = _scatter_placement(m, x, k)
        ref = reference_conv2d(lay, x, k, groups=m.group)
    assert reassoc_error(y, old) <= REASSOC_RTOL, reassoc_error(y, old)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-4 * float(jnp.max(jnp.abs(ref))))


def test_mapped_gradients_summed_super_steps():
    """Gradients through the summed, once-placed tile on a layer with two
    channel super-steps and overlapping windows: the transpose of the
    one-writer-per-pixel gather neither drops nor double-counts."""
    m = _bench_layer("DN40-b1l1")
    lay = m.layer
    (R, _, _), = _tile_blocks(m)
    loads = sum(len(o) * ((ph - lay.k_h) // lay.stride + 1)
                * ((pw - lay.k_w) // lay.stride + 1)
                for (ph, pw), o in placement_groups(lay, m.tiles[0]).items())
    assert R > 1 and loads > lay.o_h * lay.o_w         # windows overlap
    x = jnp.asarray(RNG.randn(1, lay.ic, lay.i_h, lay.i_w), jnp.float32)
    k = jnp.asarray(RNG.randn(lay.k_h, lay.k_w, lay.ic // m.group, lay.oc),
                    jnp.float32)

    def loss(conv):
        return lambda xx, kk: jnp.sum(conv(xx, kk) ** 2)

    with jax.default_matmul_precision("highest"):
        gm = jax.grad(loss(lambda xx, kk: mapped_conv2d(m, xx, kk)),
                      argnums=(0, 1))(x, k)
        gr = jax.grad(loss(lambda xx, kk: reference_conv2d(
            lay, xx, kk, groups=m.group)), argnums=(0, 1))(x, k)
    for a, r in zip(gm, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(r))))


def test_place_phase_has_no_scatter():
    """A layer with 18 channel super-steps and a tile of 3 oc super-steps:
    every array pass still runs (one dot_general each), the only scatter
    left is the in-forward weight build, and `place` is one gather per
    tile."""
    m = _bench_layer("DN40-b2l3")
    lay = m.layer
    x = jax.ShapeDtypeStruct((2, lay.ic, lay.i_h, lay.i_w), jnp.float32)
    k = jax.ShapeDtypeStruct((lay.k_h, lay.k_w, lay.ic // m.group, lay.oc),
                             jnp.float32)
    low = mapped_conv2d_jit.lower(m, x, k)
    passes = sum(R * C * n for R, C, n in _tile_blocks(m))
    assert low.as_text().count("stablehlo.dot_general") == passes == 21
    ops = re.findall(r" (scatter|gather)\(.*?op_name=\"([^\"]*)\"",
                     low.compile().as_text())
    scatters = [name for op, name in ops if op == "scatter"]
    assert scatters and all("/weights/" in n for n in scatters), scatters
    placed = [name for op, name in ops if "/place/" in name]
    assert len(placed) == layer_schedule(m).placements == len(m.tiles)
    assert all(op == "gather" for op, name in ops if "/place/" in name)


@pytest.mark.parametrize("name,tiles,former", [("cnn8", 6, 14),
                                               ("densenet40", 58, 818)])
def test_placement_totals_bench_mappings(name, tiles, former):
    """One placement per tile on the benchmark's mappings, where the
    former executor scattered once per channel super-step, oc super-step
    and window shape; describe() reports the total."""
    net = _bench_mapping(name)
    assert sum(R * C * n for m in net.layers
               for R, C, n in _tile_blocks(m)) == former
    plan = compile_plan(net, executor_policy="mapped")
    assert plan.placements == tiles == sum(len(m.tiles) for m in net.layers)
    assert f" placements={tiles} " in plan.describe()
    per_layer = re.findall(r"([^\s(,]+):mapped(?:\[[^]]*\])?\(p=(\d+)\)",
                           plan.describe())
    assert per_layer == [(m.layer.name, str(len(m.tiles)))
                         for m in net.layers]


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
