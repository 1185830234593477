"""Compile rehearsals for a described TPU v5e: the chip's own compiler
(Mosaic for the Pallas kernels, XLA:TPU for the whole plan program)
accepts the kernels of the main path at real widths, with no chip
attached.  Nothing runs, so these say nothing about results or times:
interpret-mode tests check results, chip runs give times.

The topology is described only inside the module fixture (one process
may load the TPU compiler library at a time; under several test workers
only the worker given this file loads it).  The persistent compilation
cache is off around these compiles: their entries cannot be read back
without a chip.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ArrayConfig, MacroGrid, map_net, networks

BATCH = 64          # the serving ladder's top tier


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:      # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described "
                            f"here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@functools.lru_cache(maxsize=None)
def _mapped(net: str):
    return map_net(net, networks.NETWORKS[net](), ArrayConfig(512, 512),
                   "TetrisG-SDK", MacroGrid(), groups=(1, 2, 4))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _layer_names(net: str):
    return [lay.name for lay in networks.NETWORKS[net]()]


#: Every conv layer of the nets whose serving plans may run sdk.
SDK_NETS = ("cnn8", "densenet40", "inception")


@pytest.mark.parametrize("block", ["whole", "window"])
@pytest.mark.parametrize("batch", [8, BATCH])
@pytest.mark.parametrize("net,layer", [(n, name) for n in SDK_NETS
                                       for name in _layer_names(n)])
def test_sdk_conv_compiles_for_v5e(one_chip, net, layer, batch, block):
    """Every layer the sdk executor accepts on a TPU (`_why_not_sdk`)
    compiles for the chip in both tilings at the smallest padded batch
    and the serving ladder's top tier; every layer it refuses is one
    ``auto`` keeps off sdk and an explicit sdk policy raises on."""
    from repro.exec import compile_plan
    from repro.exec.plan import _auto_executor, _why_not_sdk
    from repro.kernels.im2win_conv import sdk_conv_traced
    nm = _mapped(net)
    i, m = next((i, m) for i, m in enumerate(nm.layers)
                if m.layer.name == layer)
    if _why_not_sdk(m, batch, block=block) is not None:
        assert _auto_executor(m, backend="tpu", batch=batch) != "sdk"
        pinned = ["reference"] * len(nm.layers)
        pinned[i] = "sdk"
        with pytest.raises(ValueError, match="executor 'sdk' refused"):
            compile_plan(nm, executor_policy=pinned, batch=batch,
                         block=block, interpret=False)
        return
    lay = m.layer
    x = _spec((batch, lay.ic, lay.i_h, lay.i_w), one_chip)
    k = _spec((lay.k_h, lay.k_w, lay.ic // m.group, lay.oc), one_chip)
    text = _compiled_text(
        functools.partial(sdk_conv_traced, m, block=block), x, k)
    assert "tpu_custom_call" in text


def test_auto_plan_forward_compiles_for_v5e(one_chip):
    """The whole cnn8 serving program with the executors ``auto`` picks
    on a TPU (sdk where realizable, the Pallas kernels compiled, not
    interpreted) compiles for the chip at the top ladder tier."""
    from repro.exec import compile_plan, execute_plan
    from repro.exec.plan import _auto_executor
    net = _mapped("cnn8")
    plan = compile_plan(
        net, batch=BATCH, interpret=False,
        executor_policy=lambda m: _auto_executor(m, backend="tpu",
                                                 batch=BATCH))
    assert "sdk" in plan.executors
    first = net.layers[0].layer
    ks = [_spec((m.layer.k_h, m.layer.k_w, m.layer.ic // m.group,
                 m.layer.oc), one_chip) for m in net.layers]
    x = _spec((BATCH, first.ic, first.i_h, first.i_w), one_chip)
    text = _compiled_text(lambda ks, x: execute_plan(plan, ks, x), ks, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tetris_matmul_compiles_for_v5e(one_chip, dtype):
    """stablelm_1_6b's fused QKV projection: 4096 tokens x d_model 2048
    -> 3 x 2048."""
    from repro.kernels.tetris_matmul import tetris_matmul
    x = _spec((4096, 2048), one_chip, dtype)
    w = _spec((2048, 6144), one_chip, dtype)
    assert "tpu_custom_call" in _compiled_text(tetris_matmul, x, w)


def test_grouped_matmul_compiles_for_v5e(one_chip):
    """stablelm_1_6b's FFN up-projection (2048 -> 5632) as two groups."""
    from repro.kernels.grouped_matmul import grouped_matmul
    x = _spec((2, 4096, 1024), one_chip)
    w = _spec((2, 1024, 2816), one_chip)
    assert "tpu_custom_call" in _compiled_text(grouped_matmul, x, w)


def test_mha_flash_compiles_for_v5e(one_chip):
    """stablelm_1_6b attention: 32 heads of 64, sequence 1024."""
    from repro.kernels.flash_attention import mha_flash
    q = _spec((2, 1024, 32, 64), one_chip)
    kv = _spec((2, 1024, 32, 64), one_chip)
    assert "tpu_custom_call" in _compiled_text(
        functools.partial(mha_flash, causal=True), q, kv, kv)
