"""Training through the mapping IR: the Table II proxy and the plan
trainer.

MNIST/CIFAR/TinyImageNet are not available offline; the claim under test —
"grouped convolutions are near-lossless (and sometimes better)" — is
checked on a seeded synthetic image-classification task
(:mod:`repro.data.synthetic`).  We train the CNN8-shaped stack with
G in {1, 2, 4} under identical budgets and report accuracy deltas next to
the mapping cycle counts (benchmarks/table2_grouped.py).

``executor="mapped"`` (or "cim" / "sdk") trains through the
mapping-driven executors instead of lax.conv: the executor name resolves
to a compiled execution-plan policy (``repro.exec.compile_plan`` via
``apply_cnn`` — DESIGN.md §8), so every conv of every training step runs
exactly as its ``LayerMapping`` prescribes and the accuracy the study
reports is measured on the same execution path whose cycles the tables
count.  Gradients flow through the executors' gather/matmul/scatter
(exact; asserted against the lax.conv path in tests/test_mapped_net.py).

Both trainers share the step machinery (DESIGN.md §13):

* the **optimizer** is `repro.optim.adamw` with :data:`ADAM` (plain
  Adam: no decay, no clipping) — the update is bit-identical to the
  hand-rolled closure it replaced (tests/test_train_plan.py);
* **gradient accumulation**: ``accum`` microbatches per optimizer step,
  `lax.scan` over the reshaped batch, per-example losses summed and
  divided by the *valid* example count once — so accumulation and
  padding never change the gradient;
* **pad-and-mask**: a ragged tail batch is padded to the compiled
  ``(accum, microbatch)`` shape (`launch.mesh.pad_to_data_axis` when a
  mesh fixes the data axis) with zero-weight masks, so raggedness never
  recompiles the fused program — one compile per *distinct* shape,
  asserted via `exec.plan.compile_counts`;
* **donation**: the step donates the params/optimizer buffers when the
  platform supports it (`exec.run.donation_supported`), halving
  steady-state optimizer-state residency.

`train_plan` is the scale path: it trains the kernels of a **chained**
NetworkMapping through `execute_plan` — the whole forward as one fused
program — with ``remat`` segments from the plan's memory model
(exec/memory.py, exec/remat.py).  When ``REPRO_TRAIN_MEM_BUDGET`` is
set, a plan whose peak estimate exceeds it refuses to train (the
CPU-deterministic stand-in for an accelerator OOM); ``remat="auto"``
segments under that budget and trains.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.grouped import tetrisg_layer
from repro.core.types import (ArrayConfig, LayerMapping, MacroGrid,
                              NetworkMapping)
from repro.data.synthetic import image_task
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from .models import CNNConfig, apply_cnn, ensure_head, init_cnn

#: Plain Adam via the shared AdamW module: the b1/b2/eps the hand-rolled
#: closure used, decay and clipping off.  With these settings
#: `adamw_update` is bit-identical to the classic
#: ``p - lr*mh/(sqrt(vh)+eps)`` update (regression-tested).
ADAM = AdamWConfig(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                   grad_clip=float("inf"))


@dataclass
class TrainResult:
    config: str
    group: int
    steps: int
    final_loss: float
    train_acc: float
    test_acc: float
    executor: str = "reference"


@dataclass
class PlanTrainResult:
    """`train_plan` outcome + the memory-model facts the frontier
    (benchmarks/train_bench.py) reports next to measured steps/s."""
    name: str
    steps: int
    batch: int
    accum: int
    final_loss: float
    first_loss: float
    peak_mb: float              # estimate of the plan as segmented
    unremat_peak_mb: float      # estimate with remat off
    segments: int
    donated: bool


def train_mappings(cfg: CNNConfig, array: ArrayConfig,
                   grid: MacroGrid = MacroGrid()
                   ) -> Tuple[LayerMapping, ...]:
    """Per-conv TetrisG mappings pinned to the config's grouping factor,
    so each mapping's group matches the trained kernels' grouped layout
    ``(k, k, ic/G, oc)``."""
    return tuple(tetrisg_layer(c, array, grid, groups=(cfg.group,))
                 for c in cfg.convs)


def loss_fn(params, cfg: CNNConfig, x, y, mappings=None, executor=None):
    logits = apply_cnn(params, cfg, x, mappings=mappings, executor=executor)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


def _pad_and_mask(x, y, batch: int):
    """Pad a (possibly ragged) tail batch to ``batch`` examples with a
    0/1 validity mask — the compiled step sees ONE shape."""
    k = x.shape[0]
    mask = jnp.ones((k,), jnp.float32)
    if k < batch:
        pad = batch - k
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((pad,), jnp.float32)])
    return x, y, mask


def _accum_grads(loss_sum_fn, params, xb, yb, mask):
    """Scan ``accum`` microbatches, summing per-example loss and grads;
    divide by the valid count once at the end — gradients are exactly
    those of the unpadded whole-batch mean (DESIGN.md §13).

    ``loss_sum_fn(params, x, y, mask) -> masked per-example SUM``;
    ``xb``/``yb``/``mask`` are (accum, microbatch, ...)."""
    zeros = jax.tree.map(jnp.zeros_like, params)

    def body(acc, mb):
        x, y, mk = mb
        lv, g = jax.value_and_grad(loss_sum_fn)(params, x, y, mk)
        lsum, gsum = acc
        return (lsum + lv,
                jax.tree.map(jnp.add, gsum, g)), None

    (lsum, gsum), _ = lax.scan(body, (jnp.zeros(()), zeros),
                               (xb, yb, mask))
    count = mask.sum()
    return lsum / count, jax.tree.map(lambda g: g / count, gsum)


def _make_step(loss_sum_fn, lr: float, *, donate: bool):
    """The shared jitted optimizer step: accumulate → adamw.  Donates
    the params/opt buffers when the platform implements donation."""

    def step(params, opt, xb, yb, mask):
        loss, grads = _accum_grads(loss_sum_fn, params, xb, yb, mask)
        params, opt, _ = adamw_update(params, grads, opt, lr, ADAM)
        return params, opt, loss

    if donate:
        return jax.jit(step, donate_argnums=(0, 1))
    return jax.jit(step)


def _microbatched(x, y, mask, accum: int):
    mb = x.shape[0] // accum
    return (x.reshape((accum, mb) + x.shape[1:]),
            y.reshape((accum, mb)),
            mask.reshape((accum, mb)))


def train_cnn(cfg: CNNConfig, *, steps: int = 300, batch: int = 64,
              lr: float = 3e-3, seed: int = 0,
              n_train: int = 2048, n_test: int = 512,
              executor: str = "reference",
              array: Optional[ArrayConfig] = None,
              grid: MacroGrid = MacroGrid(),
              accum: int = 1, remat=None, mesh=None,
              donate: Optional[bool] = None) -> TrainResult:
    """The Table II accuracy study trainer (module docstring).

    ``accum`` splits each ``batch`` into that many scanned microbatches
    per optimizer step (``batch % accum == 0``); ``remat`` forwards to
    the execution plan's segment pass (mapping-driven executors only —
    the lax.conv fast path has no plan to segment).  ``donate=None``
    resolves via `donation_supported`.
    """
    if accum < 1 or batch % accum:
        raise ValueError(f"accum={accum} must divide batch={batch}")
    from repro.exec.run import donation_supported
    from repro.launch.mesh import pad_to_data_axis
    if donate is None:
        donate = donation_supported(mesh)
    # the compiled step shape: microbatches pad up to the mesh data axis
    # when one is bound (plans refuse ragged data-axis batches)
    micro = batch // accum
    micro = pad_to_data_axis(micro, mesh) if mesh is not None else micro
    batch = micro * accum

    rng = jax.random.PRNGKey(seed)
    k_init, k_data = jax.random.split(rng)
    xs, ys, xt, yt = image_task(k_data, n_train=n_train, n_test=n_test,
                                size=cfg.convs[0].i_w - 2,
                                channels=cfg.convs[0].ic,
                                num_classes=cfg.num_classes)
    params = ensure_head(init_cnn(k_init, cfg), cfg)

    mappings = None
    if executor != "reference":
        mappings = train_mappings(cfg, array or ArrayConfig(512, 512), grid)

    def loss_sum(params, x, y, mask):
        logits = apply_cnn(params, cfg, x, mappings=mappings,
                           executor=executor, mesh=mesh, remat=remat)
        logp = jax.nn.log_softmax(logits)
        per = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return (per * mask).sum()

    step = _make_step(loss_sum, lr, donate=donate)
    opt = adamw_init(params)
    n = xs.shape[0]
    loss = float("nan")
    for i in range(steps):
        lo = (i * batch) % max(1, n - batch)
        xb, yb, mask = _pad_and_mask(xs[lo:lo + batch], ys[lo:lo + batch],
                                     batch)
        params, opt, loss = step(params, opt,
                                 *_microbatched(xb, yb, mask, accum))

    @jax.jit
    def acc(params, x, y):
        logits = apply_cnn(params, cfg, x, mappings=mappings,
                           executor=executor)
        return (logits.argmax(-1) == y).mean()

    return TrainResult(
        config=cfg.name, group=cfg.group, steps=steps,
        final_loss=float(loss),
        train_acc=float(acc(params, xs[:n_test], ys[:n_test])),
        test_acc=float(acc(params, xt, yt)),
        executor=executor)


def init_plan_kernels(net: NetworkMapping, key) -> list:
    """He-init kernels in the executor layout ``(k_h, k_w, ic/G, oc)``,
    pruned channels zeroed to match the mapping."""
    from repro.cnn.mapped_net import zero_pruned_kernels
    ks = []
    for i, m in enumerate(net.layers):
        c = m.layer
        fan_in = c.k_h * c.k_w * c.ic // m.group
        ks.append(jax.random.normal(
            jax.random.fold_in(key, i),
            (c.k_h, c.k_w, c.ic // m.group, c.oc), jnp.float32)
            * (2.0 / fan_in) ** 0.5)
    return zero_pruned_kernels(net, ks)


def train_plan(net: NetworkMapping, *, steps: int = 10, batch: int = 8,
               lr: float = 1e-3, seed: int = 0, accum: int = 1,
               remat=None, executor_policy="reference", mesh=None,
               num_classes: int = 10, n_train: int = 256,
               donate: Optional[bool] = None,
               losses: Optional[list] = None,
               step_times: Optional[list] = None) -> PlanTrainResult:
    """Train a chained NetworkMapping's kernels (+ a linear head on the
    GAP features) through `execute_plan` — the whole fused forward, with
    ``remat`` segments applied per `jax.checkpoint` (module docstring).

    When ``REPRO_TRAIN_MEM_BUDGET`` is set (bytes), a plan whose peak
    live-byte *estimate* exceeds it raises MemoryError before touching
    the device — the deterministic CPU stand-in for an accelerator OOM;
    compile with ``remat="auto"`` to segment under the budget.  Pass a
    list as ``losses`` to collect the per-step loss trajectory, and/or
    one as ``step_times`` for per-step wall seconds (the first entry
    includes the jit compile — benchmarks drop it).
    """
    import time as _time
    from repro.exec import compile_plan, execute_plan
    from repro.exec.remat import ENV_BUDGET
    from repro.exec.run import donation_supported
    from repro.launch.mesh import pad_to_data_axis
    if accum < 1 or batch % accum:
        raise ValueError(f"accum={accum} must divide batch={batch}")
    if net.glue is not None and any(g.kind in ("branch", "join")
                                    for g in net.glue):
        raise ValueError(
            f"{net.name}: train_plan trains chained CNNs only; this "
            f"mapping has branch glue (Inception modules), which it does "
            f"not train through")
    micro = batch // accum
    micro = pad_to_data_axis(micro, mesh) if mesh is not None else micro
    batch = micro * accum
    if donate is None:
        donate = donation_supported(mesh)

    plan = compile_plan(net, executor_policy=executor_policy, mesh=mesh,
                        batch=micro, remat=remat)
    budget = os.environ.get(ENV_BUDGET)
    if budget and plan.peak_bytes > int(budget):
        raise MemoryError(
            f"{net.name}: plan peak estimate {plan.peak_bytes / 1e6:.1f}MB "
            f"exceeds {ENV_BUDGET}={int(budget) / 1e6:.1f}MB "
            f"(remat={remat!r}, {len(plan.spans)} segment(s)) — compile "
            f"with remat='auto' or a byte budget to segment under it")

    first = net.layers[0].layer
    rng = jax.random.PRNGKey(seed)
    k_init, k_head, k_data = jax.random.split(rng, 3)
    xs, ys, _, _ = image_task(k_data, n_train=n_train, n_test=1,
                              size=max(4, first.i_w - 2),
                              channels=first.ic, num_classes=num_classes)
    last = plan.layers[-1]
    out_c = last.mapping.layer.oc
    if last.glue.kind == "concat":      # DenseNet: carry + final output
        out_c += last.carry_c
    params = {
        "kernels": init_plan_kernels(net, k_init),
        "head": jax.random.normal(k_head, (out_c, num_classes),
                                  jnp.float32) * (1.0 / out_c) ** 0.5,
    }

    def loss_sum(params, x, y, mask):
        feats = execute_plan(plan, params["kernels"], x, mesh=mesh,
                             activation=jax.nn.relu).mean(axis=(2, 3))
        logp = jax.nn.log_softmax(feats @ params["head"])
        per = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return (per * mask).sum()

    step = _make_step(loss_sum, lr, donate=donate)
    opt = adamw_init(params)
    n = xs.shape[0]
    loss = first_loss = float("nan")
    for i in range(steps):
        lo = (i * batch) % max(1, n - batch)
        xb, yb, mask = _pad_and_mask(xs[lo:lo + batch], ys[lo:lo + batch],
                                     batch)
        t0 = _time.perf_counter()
        params, opt, lval = step(params, opt,
                                 *_microbatched(xb, yb, mask, accum))
        loss = float(lval)               # sync: the step really finished
        if step_times is not None:
            step_times.append(_time.perf_counter() - t0)
        if i == 0:
            first_loss = loss
        if losses is not None:
            losses.append(loss)

    return PlanTrainResult(
        name=net.name, steps=steps, batch=batch, accum=accum,
        final_loss=loss, first_loss=first_loss,
        peak_mb=plan.peak_bytes / 1e6,
        unremat_peak_mb=plan.unremat_peak_bytes / 1e6,
        segments=len(plan.spans), donated=donate)
