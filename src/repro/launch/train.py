"""Training driver: end-to-end supervised loop on a real mesh.

On this CPU container it drives the smoke-scale configs (examples/
train_lm.py); on hardware the same entry point takes --arch <id> with
the production mesh.  Wires together: config -> sharded init ->
TokenStream pipeline -> train_step -> CheckpointStore + TrainSupervisor
(heartbeats, straggler log, restart-exact resume).

``--plan-net <network>`` switches to the CNN plan trainer instead: the
named bench network (core/networks.py) is mapped, compiled to a chained
NetworkPlan, and its kernels train through `execute_plan` with
rematerialization (`--remat off|auto|<bytes>`) and gradient accumulation
(`--accum K`) — `repro.cnn.train.train_plan`, DESIGN.md §13.

Usage:
    python -m repro.launch.train --arch stablelm_1_6b --smoke \
        --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
    python -m repro.launch.train --plan-net densenet40 --remat auto \
        --steps 10 --batch 8 --accum 2
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointStore
from repro.configs import get_config
from repro.data import ShardedDataPipeline
from repro.data.synthetic import TokenStream
from repro.launch.steps import (TrainConfig, init_train_state,
                                make_train_step)
from repro.runtime import HeartbeatMonitor, TrainSupervisor


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_1_6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-net", default=None,
                    help="train this bench network through the plan "
                         "trainer (cnn/train.train_plan) instead of the "
                         "transformer loop")
    ap.add_argument("--remat", default="off",
                    help="plan trainer: off | auto | <peak budget bytes>")
    ap.add_argument("--accum", type=int, default=1,
                    help="plan trainer: microbatches per optimizer step")
    args = ap.parse_args(argv)

    from repro.launch.device import describe_devices, enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(describe_devices())
    if args.plan_net is not None:
        return _plan_main(args)

    cfg = get_config(args.arch, smoke=args.smoke)
    tc = TrainConfig(microbatches=args.microbatches, peak_lr=args.lr,
                     warmup_steps=max(2, args.steps // 20),
                     total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, tc))

    ts = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                     global_batch=args.batch, seed=args.seed)
    pipe = ShardedDataPipeline(ts)
    store = CheckpointStore(Path(args.ckpt_dir) / cfg.name, keep=2,
                            async_save=True)
    sup = TrainSupervisor(store=store, pipeline=pipe,
                          monitor=HeartbeatMonitor(1),
                          save_every=args.save_every)

    def wrapped(state, tokens):
        state, metrics = step_fn(state, {"tokens": jnp.asarray(tokens)})
        return state, metrics

    t0 = time.time()
    if args.resume:
        like = jax.eval_shape(partial(init_train_state, cfg),
                              jax.random.PRNGKey(args.seed))
        state, last = sup.resume(like, _metric_logger(wrapped, t0),
                                 steps=args.steps)
    else:
        state = init_train_state(cfg, jax.random.PRNGKey(args.seed))
        state, last = sup.run(state, _metric_logger(wrapped, t0),
                              steps=args.steps)
    store.wait()
    print(f"done: {last} steps in {time.time()-t0:.1f}s; "
          f"events={sup.events[-3:]}")


def _plan_main(args) -> None:
    """The --plan-net path: map the named network and train its kernels
    through the compiled plan (module docstring)."""
    from repro.cnn.train import train_plan
    from repro.core import ArrayConfig, MacroGrid, map_net, networks
    if args.plan_net not in networks.NETWORKS:
        raise SystemExit(f"unknown network {args.plan_net!r} "
                         f"(have: {sorted(networks.NETWORKS)})")
    remat = None if args.remat == "off" else (
        args.remat if args.remat == "auto" else int(args.remat))
    net = networks.with_glue(args.plan_net, map_net(
        args.plan_net, networks.NETWORKS[args.plan_net](),
        ArrayConfig(64, 64), "TetrisG-SDK", MacroGrid(2, 2)))
    t0 = time.time()
    losses: list = []
    r = train_plan(net, steps=args.steps, batch=args.batch, lr=args.lr,
                   seed=args.seed, accum=args.accum, remat=remat,
                   losses=losses)
    for i, lv in enumerate(losses):
        if i % 10 == 0 or i == len(losses) - 1:
            print(f"step {i + 1:>5d}  loss {lv:.4f}", flush=True)
    print(f"done: {r.steps} steps in {time.time() - t0:.1f}s; "
          f"loss {r.first_loss:.4f} -> {r.final_loss:.4f}; "
          f"peak~{r.peak_mb:.0f}MB (unremat {r.unremat_peak_mb:.0f}MB, "
          f"{r.segments} segment(s), accum={r.accum}, "
          f"donated={r.donated})")


def _metric_logger(step_fn, t0, every: int = 10):
    counter = {"n": 0}

    def fn(state, batch):
        state, metrics = step_fn(state, batch)
        counter["n"] += 1
        if counter["n"] % every == 0 or counter["n"] == 1:
            print(f"step {counter['n']:>5d}  "
                  f"loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"({time.time()-t0:.0f}s)", flush=True)
        return state, metrics
    return fn


if __name__ == "__main__":
    main()
