"""Compiled-plan execution vs the per-layer dispatch loop.

For each bench network: lower the mapping once (`repro.exec.compile_plan`)
and measure the SAME plan through both dispatch shapes —

* ``loop``  — one jit launch per layer, eager glue between
  (`execute_looped`, the pre-plan behavior);
* ``fused`` — the whole forward as one jitted program with bounded
  one-layer-lookahead pipelining (`execute_plan`).

The fused rows must show the per-forward host dispatch count dropping to
1 and wall-clock no worse than the loop (DESIGN.md §8).  CNN8 and
DenseNet40 execute as real chains; Inception's spec list is a
representative layer *set*, so it runs layerwise (`execute_layerwise`
vs an `apply_layer` loop — same dispatch comparison).  The default run
uses a DenseNet40 prefix to keep CI compile time sane; ``--full``
compiles the whole 38-layer chain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ArrayConfig, MacroGrid, map_net, networks
from repro.cnn.mapped_net import zero_pruned_kernels
from repro.exec import (apply_layer, compile_plan, execute_layerwise,
                        execute_looped, execute_plan)

from .common import Row, interleaved_medians

BATCH = 4
GRID = MacroGrid(2, 2)


def _kernels(net, rng):
    return zero_pruned_kernels(net, [
        jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                              m.layer.ic // m.group, m.layer.oc) * 0.1,
                    jnp.float32) for m in net.layers])


def _time_pair(fn_a, fn_b, rounds: int = 5):
    """Median us of two warm paths via the shared interleaved-rounds
    primitive (`repro.tune.measure`), so machine noise (2-core CI
    boxes) hits both equally."""
    a, b = interleaved_medians([fn_a, fn_b], rounds=rounds, warmup=1)
    return a * 1e6, b * 1e6


def _rows(label: str, plan, us_loop: float, us_fused: float):
    n = len(plan.layers)
    # memory-pass estimate (exec/memory.py) of training through this
    # plan unremat'd, scaled to the bench batch — the frontier's x-axis,
    # inspectable without running the trainer (train_bench measures it)
    mem_mb = plan.unremat_peak_bytes * BATCH / 1e6
    return [
        Row(f"plan/{label}/loop", us_loop,
            f"dispatches={n};batch={BATCH};mem_mb={mem_mb:.1f}"),
        Row(f"plan/{label}/fused", us_fused,
            f"dispatches=1;speedup={us_loop / us_fused:.2f};"
            f"steps={plan.total_steps};batch={BATCH};"
            f"mem_mb={mem_mb:.1f}"),
    ]


def run(full: bool = False):
    arr = ArrayConfig(64, 64)
    rng = np.random.RandomState(0)
    rows = []

    chained = [("cnn8", networks.cnn8()),
               ("densenet40" if full else "densenet40[:12]",
                networks.densenet40() if full else
                networks.densenet40()[:12])]
    for label, layers in chained:
        net = map_net(label, layers, arr, "TetrisG-SDK", GRID,
                      groups=(1, 2))
        plan = compile_plan(net, executor_policy="mapped")
        ks = _kernels(net, rng)
        first = net.layers[0].layer
        x = jnp.asarray(rng.randn(BATCH, first.ic, first.i_h, first.i_w),
                        jnp.float32)
        us_loop, us_fused = _time_pair(
            lambda: jax.block_until_ready(execute_looped(plan, ks, x)),
            lambda: jax.block_until_ready(execute_plan(plan, ks, x)))
        rows += _rows(label, plan, us_loop, us_fused)

    # inception: representative layer set, not a chain -> layerwise plan
    net = map_net("inception", networks.inception(), arr, "TetrisG-SDK",
                  GRID, groups=(1, 2))
    plan = compile_plan(net, executor_policy="mapped", chained=False)
    ks = _kernels(net, rng)
    xs = [jnp.asarray(rng.randn(BATCH, m.layer.ic, m.layer.i_h,
                                m.layer.i_w), jnp.float32)
          for m in net.layers]
    n = len(net.layers)
    us_loop, us_fused = _time_pair(
        lambda: jax.block_until_ready(
            [apply_layer(plan, i, xs[i], ks[i]) for i in range(n)]),
        lambda: jax.block_until_ready(execute_layerwise(plan, ks, xs)))
    rows += _rows("inception", plan, us_loop, us_fused)
    return rows
