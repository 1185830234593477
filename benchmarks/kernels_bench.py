"""Pallas kernel micro-bench (interpret mode on CPU — numbers are for
plumbing sanity, not TPU perf; TPU perf is the roofline analysis)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

from .common import Row, timed


def run(full: bool = False):
    rng = np.random.RandomState(0)
    rows = []
    x = jnp.asarray(rng.randn(256, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256, 256), jnp.float32)
    y, _ = timed(lambda: ops.matmul(x, w).block_until_ready())
    _, us = timed(lambda: ops.matmul(x, w).block_until_ready(), repeats=3)
    rows.append(Row("kernel/tetris_matmul/256", us, "interpret=cpu"))

    xg = jnp.asarray(rng.randn(4, 128, 64), jnp.float32)
    wg = jnp.asarray(rng.randn(4, 64, 128), jnp.float32)
    timed(lambda: ops.gmm(xg, wg).block_until_ready())
    _, us = timed(lambda: ops.gmm(xg, wg).block_until_ready(), repeats=3)
    rows.append(Row("kernel/grouped_matmul/4x128", us, "interpret=cpu"))
    return rows
