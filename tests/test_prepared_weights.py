"""Prepared shifted-weight blocks (exec/constants.py): with fixed kernels
``execute_plan`` feeds the mapped layers blocks built once per kernel set;
traced or numpy kernels, and blocks too large for the device, keep the
in-trace build."""
import dataclasses
import functools
import gc
import re
import warnings
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArrayConfig, MacroGrid, map_net, memo, networks
from repro.exec import (REASSOC_RTOL, compile_plan, constant_counts,
                        execute_looped, execute_plan, prepared_stats,
                        reassoc_error)
from repro.exec import constants as plan_constants
from repro.launch.batching import PlanLadder
from repro.launch.tracing import SPAN_PREFIX, WEIGHTS


def _net(name, layers, glue=None):
    nm = map_net(name, layers, ArrayConfig(64, 64), "TetrisG-SDK",
                 MacroGrid(2, 2), groups=(1, 2))
    return nm if glue is None else dataclasses.replace(nm, glue=glue)


def _inception_toy():
    """Two Inception modules with their branch and join glue."""
    return _net("inception_toy", *networks.inception_modules(
        (("a", 8, 8, 4, 4, 8, 4, 4, 4), ("b", 20, 4, 8, 4, 8, 4, 8, 4)),
        ("b",)))


NETS = {"cnn8": lambda: _net("cnn8", networks.cnn8()[:3]),
        "densenet40": lambda: _net("densenet40_s",
                                   networks.densenet40()[10:15]),
        "inception": _inception_toy}


def _data(net, batch, seed=0):
    rng = np.random.RandomState(seed)
    ks = [jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                                m.layer.ic // m.group, m.layer.oc) * 0.2,
                      jnp.float32) for m in net.layers]
    lay0 = net.layers[0].layer
    x = jnp.asarray(rng.randn(batch, lay0.ic, lay0.i_h, lay0.i_w),
                    jnp.float32)
    return ks, x


def _in_trace(plan, ks, x):
    """The in-trace build: through jit the kernels are tracers."""
    return jax.jit(functools.partial(execute_plan, plan))(ks, x)


@pytest.mark.parametrize("name", sorted(NETS))
def test_fixed_kernels_match_the_in_trace_build(name):
    """A chain, a concat slice and a branch-and-join toy: prepared
    blocks give the in-trace build's outputs bit for bit; numpy kernels
    (mutable) keep the in-trace build."""
    memo.clear()
    net = NETS[name]()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    ks, x = _data(net, 2)
    y = execute_plan(plan, ks, x)
    assert prepared_stats() == {"prepared_calls": 1, "in_trace_calls": 0,
                                "materializations": 1,
                                "memory_fallbacks": 0}
    assert bool(jnp.all(y == _in_trace(plan, ks, x)))
    y_np = execute_plan(plan, [np.asarray(k) for k in ks], x)
    assert bool(jnp.all(y == y_np))
    s = prepared_stats()
    assert s["prepared_calls"] == 1 and s["materializations"] == 1
    assert s["in_trace_calls"] == 2


def test_ladder_tiers_share_one_materialization():
    """Every tier of a ladder reads the SAME handle: one
    materialization for the network and its kernel set."""
    memo.clear()
    net = NETS["cnn8"]()
    ladder = PlanLadder(net, (1, 2, 4))
    ks, x = _data(net, 4)
    for t in ladder.tiers:
        execute_plan(ladder.plans[t], ks, x[:t])
    assert list(constant_counts(net=net).values()) == [1]
    assert prepared_stats() == {"prepared_calls": 3, "in_trace_calls": 0,
                                "materializations": 1,
                                "memory_fallbacks": 0}
    handles = {id(plan_constants.fixed_constants(ladder.plans[t], ks))
               for t in ladder.tiers}
    assert len(handles) == 1


def test_new_kernels_materialize_and_dead_ones_let_go():
    """New kernel objects (same values) get a handle of their own; the
    old entry, and its blocks, go when its kernels are collected."""
    memo.clear()
    net = NETS["densenet40"]()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    ks, x = _data(net, 2)
    y = execute_plan(plan, ks, x)
    old = weakref.ref(plan_constants.fixed_constants(plan, ks))
    ks2 = [k + 0 for k in ks]
    assert bool(jnp.all(execute_plan(plan, ks2, x) == y))
    assert prepared_stats()["materializations"] == 2
    assert len(constant_counts(net=net)) == 2
    del ks
    gc.collect()
    assert old() is None
    assert list(constant_counts(net=net).values()) == [1]
    new = plan_constants.fixed_constants(plan, ks2)
    assert new is plan_constants.fixed_constants(plan, ks2)
    assert prepared_stats()["materializations"] == 2


def test_traced_kernels_keep_the_in_trace_build():
    """``jax.grad`` of a training-style loss and a user's ``jit`` that
    closes over fixed kernels trace the in-program build: nothing is
    materialized, the gradient is the per-layer path's, and the compiled
    gradient program still builds its weights (the ``weights`` scope)."""
    memo.clear()
    net = NETS["densenet40"]()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    ks, x = _data(net, 2)
    last = plan.layers[-1]
    out_c = last.mapping.layer.oc + (last.carry_c
                                     if last.glue.kind == "concat" else 0)
    head = jnp.asarray(np.random.RandomState(1).randn(out_c, 3),
                       jnp.float32)

    def loss(run, ks, x):
        feats = run(plan, ks, x, activation=jax.nn.relu).mean(axis=(2, 3))
        return jax.nn.log_softmax(feats @ head)[:, 0].sum()

    g = jax.grad(functools.partial(loss, execute_plan))(ks, x)
    g_ref = jax.grad(functools.partial(loss, execute_looped))(ks, x)
    for a, b in zip(g, g_ref):
        assert reassoc_error(a, b) <= REASSOC_RTOL
    y = jax.jit(lambda x: execute_plan(plan, ks, x))(x)
    assert bool(jnp.all(y == execute_plan(plan, ks, x)))
    s = prepared_stats()
    assert s["in_trace_calls"] == 2 and s["prepared_calls"] == 1
    assert s["materializations"] == 1          # the last, untraced call
    text = jax.jit(jax.grad(functools.partial(loss, execute_plan))).lower(
        ks, x).compile().as_text()
    assert re.search(rf'op_name="[^"]*/{WEIGHTS}/', text)


def test_blocks_over_half_the_device_keep_the_in_trace_build(monkeypatch):
    """Blocks of more than half the device's ``bytes_limit`` are never
    materialized: the call keeps the in-trace build, once decided per
    kernel set; at exactly half they are prepared."""
    memo.clear()
    net = NETS["cnn8"]()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    ks, x = _data(net, 2)
    y = _in_trace(plan, ks, x)                  # in-trace call 1
    mappings = tuple(lp.mapping for lp in plan.layers)
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(
        plan_constants._prepare_blocks.eval_shape(mappings, tuple(ks))))
    monkeypatch.setattr(plan_constants, "_bytes_limit",
                        lambda kernels: 2 * nbytes - 2)
    for _ in range(2):
        assert bool(jnp.all(execute_plan(plan, ks, x) == y))
    assert prepared_stats() == {"prepared_calls": 0, "in_trace_calls": 3,
                                "materializations": 0,
                                "memory_fallbacks": 1}
    monkeypatch.setattr(plan_constants, "_bytes_limit",
                        lambda kernels: 2 * nbytes)
    ks2 = [k + 0 for k in ks]
    assert bool(jnp.all(execute_plan(plan, ks2, x) == y))
    assert prepared_stats()["materializations"] == 1


def test_execute_plan_span_says_whether_it_was_prepared(tmp_path):
    """The ``repro.execute_plan`` span carries ``prepared``: 1 with fixed
    kernels, 0 with numpy ones."""
    from jax.profiler import ProfileData
    memo.clear()
    net = NETS["cnn8"]()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    ks, x = _data(net, 2)
    ks_np = [np.asarray(k) for k in ks]
    for k in (ks, ks_np):                       # compile both
        execute_plan(plan, k, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in (ks, ks_np):
            execute_plan(plan, k, x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    with warnings.catch_warnings():
        # the stats' type warns (no __module__) when first built
        warnings.simplefilter("ignore", DeprecationWarning)
        spans = sorted((e.start_ns, dict(e.stats))
                       for plane in ProfileData.from_file(str(path)).planes
                       if plane.name.startswith("/host:")
                       for line in plane.lines for e in line.events
                       if e.name == SPAN_PREFIX + "execute_plan")
    assert [s["prepared"] for _, s in spans] == [1, 0]
    assert all(s["batch"] == 2 for _, s in spans)


def test_held_handles_count_against_the_memory_limit(monkeypatch):
    """The fallback counts the handles the cache already holds: with
    room for one handle, a second kernel set keeps the in-trace build
    until the first set's kernels, and so its handle, are gone."""
    memo.clear()
    net = NETS["cnn8"]()
    plan = compile_plan(net, executor_policy="mapped", batch=2)
    ks, x = _data(net, 2)
    mappings = tuple(lp.mapping for lp in plan.layers)
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(
        plan_constants._prepare_blocks.eval_shape(mappings, tuple(ks))))
    monkeypatch.setattr(plan_constants, "_bytes_limit",
                        lambda kernels: 4 * nbytes - 2)
    y = execute_plan(plan, ks, x)
    assert plan_constants.held_bytes() == nbytes
    ks2 = [k + 0 for k in ks]
    assert plan_constants.fixed_constants(plan, ks2) is None
    assert bool(jnp.all(execute_plan(plan, ks2, x) == y))
    assert prepared_stats()["memory_fallbacks"] == 1
    del ks
    gc.collect()
    assert plan_constants.held_bytes() == 0
    ks3 = [k + 0 for k in ks2]
    assert plan_constants.fixed_constants(plan, ks3) is not None
    assert prepared_stats()["materializations"] == 2


def test_one_device_mesh_is_prepared_and_larger_ones_are_not():
    """A mesh of one device reads the prepared blocks; a plan on a mesh
    of several devices keeps the in-trace build (the multi-device case
    runs in tests/test_fleet.py)."""
    from jax.sharding import Mesh
    memo.clear()
    net = NETS["cnn8"]()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("row", "col"))
    plan = compile_plan(net, executor_policy="mapped", mesh=mesh, batch=2)
    ks, x = _data(net, 2)
    y = execute_plan(plan, ks, x, mesh=mesh)
    assert prepared_stats()["prepared_calls"] == 1
    assert bool(jnp.all(y == jax.jit(lambda ks, x: execute_plan(
        plan, ks, x, mesh=mesh))(ks, x)))

    class _Wide:
        size = 4
    assert plan_constants.fixed_constants(plan, ks, _Wide()) is None
