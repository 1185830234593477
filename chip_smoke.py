"""Smoke run of the serving and training path on a TPU.

    python chip_smoke.py                # one chip: serve + train
    python chip_smoke.py --four-chips   # four chips: sharded mesh + replicas

One chip (the default) drives the system through the entry points a user
calls, at the published widths of the paper's CNN8:

* **serve** — map cnn8 with TetrisG-SDK on the 512x512 array
  (`serve_cnn.map_for_serving`) and serve a Poisson trace through
  `serve_cnn.serve_dynamic` (plan-ladder tiers up to batch 64), once
  under the default ``policy="mapped"`` and once under ``"auto"``, which
  must put at least one layer on the compiled Pallas ``sdk`` kernel.
  Each plan is compared with `execute_oracle` (lax.conv over the same
  chain) in float32 under ``default_matmul_precision("highest")`` at the
  smallest padded batch and the top tier; ``auto`` also with each sdk
  tiling ("whole", "window") forced, and both tilings must have run.
* **train** — `cnn.train.train_plan` takes 3 steps on cnn8; the loss
  must stay finite.

Four chips (``--four-chips``) runs only what exists across chips:

* **mesh** — in a child process that owns all four chips: the sharded
  serving mesh (`serving_mesh_for`, data x row x col) against the
  one-chip ``mapped`` plan of the same mapping and batch, held to the
  reassociation rule of DESIGN.md §7;
* **replicas** — from this process, which never starts a JAX backend:
  `serve_replicas` with four one-chip workers on a short trace, each
  bound to its own chip; every request served exactly once.

Rates printed here are smoke readings, not benchmark numbers.  Any
failed phase, a wrong result, or a platform other than ``tpu`` exits
non-zero; the last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import subprocess
import sys
import time
from pathlib import Path

NET = "cnn8"
ARRAY = (512, 512)
ALG = "TetrisG-SDK"
MAX_BATCH = 64
MAX_DELAY_MS = 2.0
REQUESTS = 48
ARRIVAL_RATE = 2000.0       # requests per second
MAX_REQUEST = 16            # rows per request, uniform in [1, 16]
CHECK_BATCHES = (8, MAX_BATCH)    # smallest padded batch, top tier
#: sdk tiling per check: the plan's own choice, then each one forced.
CHECK_BLOCKS = {"mapped": ("auto",), "auto": ("auto", "whole", "window")}
#: The served plans against `execute_oracle`, both in f32 at the highest
#: matmul precision: max|y - ref| <= ORACLE_RTOL * max|ref|.
ORACLE_RTOL = 1e-4
TRAIN_STEPS = 3
TRAIN_BATCH = 32
MESH_GRID = (2, 2)          # macro grid whose serving mesh spans 4 chips
MESH_BATCH = 8
REPLICAS = 4
REPLICA_REQUESTS = 32
REPLICA_MAX_BATCH = 8


class SmokeFailure(Exception):
    pass


def fail(msg: str) -> None:
    raise SmokeFailure(msg)


def _src_on_path() -> None:
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"no repro package under {src}: run chip_smoke.py from a "
             f"checkout of the repository")
    sys.path.insert(0, str(src))


def _tpu_devices(expect: int) -> dict:
    from repro.launch.device import device_summary
    dev = device_summary()
    if dev["platform"] != "tpu":
        fail(f"no TPU found: JAX runs on {dev['platform']!r}")
    if dev["count"] != expect:
        fail(f"expected {expect} TPU chip(s), JAX reports {dev['count']}")
    return dev


def _check_against_oracle(mapping, policy: str, seed: int, batch: int,
                          block: str) -> set:
    """One batch of the policy's plan vs the lax.conv oracle; returns
    the sdk tilings the plan ran."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.exec import compile_plan, execute_oracle, execute_plan
    from repro.kernels.im2win_conv import sdk_tilings
    from repro.launch.serve_cnn import _serving_kernels
    rng, ks = _serving_kernels(mapping, seed)
    first = mapping.layers[0].layer
    x = jnp.asarray(rng.randn(batch, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    plan = compile_plan(mapping, executor_policy=policy, batch=batch,
                        block=block)
    with jax.default_matmul_precision("highest"):
        y = np.asarray(execute_plan(plan, ks, x))
        ref = np.asarray(execute_oracle(plan, ks, x))
    last = mapping.layers[-1].layer
    want = (batch, last.oc, last.o_h, last.o_w)
    if y.shape != want or not np.isfinite(y).all():
        fail(f"{policy}: output shape {y.shape} (want {want}) or "
             f"non-finite values")
    tilings = {lp.mapping.layer.name: sdk_tilings(lp.mapping, batch,
                                                  block=block)
               for lp in plan.layers if lp.executor == "sdk"}
    ran = "".join(f" {n}={'+'.join(t)}" for n, t in tilings.items())
    err = float(np.max(np.abs(y - ref)))
    rel = err / float(np.max(np.abs(ref)))
    line = (f"[check] policy={policy} block={block} batch={batch} vs "
            f"execute_oracle (f32, highest precision): max_abs_err={err!r} "
            f"rel_err={rel!r} tolerance rel<={ORACLE_RTOL}"
            + (f" sdk tilings:{ran}" if ran else ""))
    print(line)
    if not rel <= ORACLE_RTOL:
        fail(f"{line} — outside tolerance")
    return {m for t in tilings.values() for m in t}


def phase_serve(seed: int) -> None:
    from repro.core import ArrayConfig, memo
    from repro.exec import compile_plan
    from repro.launch import serve_cnn
    mapping, search_s = serve_cnn.map_for_serving(NET, ArrayConfig(*ARRAY),
                                                  ALG)
    print(f"[serve] {NET} [{ALG}] array={ARRAY[0]}x{ARRAY[1]} "
          f"layers={len(mapping.layers)} search_s={search_s!r} "
          f"table_builds={memo.snapshot()['table_misses']}")
    reqs = serve_cnn.poisson_arrivals(REQUESTS, ARRIVAL_RATE, MAX_REQUEST,
                                      seed=seed)
    rows = sum(r for _, r in reqs)
    for policy in ("mapped", "auto"):
        t0 = time.perf_counter()
        s = serve_cnn.serve_dynamic(mapping, reqs, max_batch=MAX_BATCH,
                                    max_delay_ms=MAX_DELAY_MS,
                                    policy=policy, seed=seed)
        setup_s = time.perf_counter() - t0 - s.wall_s
        if s.request_images != rows:
            fail(f"{policy}: served {s.request_images} of {rows} rows")
        top = compile_plan(mapping, executor_policy=policy, batch=MAX_BATCH)
        print(f"[serve] policy={policy} {top.describe()}")
        sdk = [lp for lp in top.layers if lp.executor == "sdk"]
        if policy == "auto":
            if not sdk:
                fail("auto put no layer on the sdk kernel")
            if any(lp.interpret for lp in sdk):
                fail("sdk layers run in interpret mode on the chip")
        print(f"[serve] policy={policy} sdk_layers={len(sdk)} "
              f"interpret={any(lp.interpret for lp in top.layers)} "
              f"requests={len(reqs)} images={s.request_images} "
              f"tiers={'/'.join(str(t) for t in sorted(s.tiers))} "
              f"setup_s={setup_s!r} (search, plan and XLA compiles, "
              f"warmup)")
        print(f"[serve] policy={policy} smoke reading, not a benchmark: "
              f"images_per_s={s.images_per_s!r} "
              f"p50_ms={s.delay_ms(50)!r} p99_ms={s.delay_ms(99)!r}")
        ran = set()
        for batch in CHECK_BATCHES:
            for block in CHECK_BLOCKS[policy]:
                ran |= _check_against_oracle(mapping, policy, seed, batch,
                                             block)
        if policy == "auto" and ran != {"whole", "window"}:
            fail(f"auto checked sdk tilings {sorted(ran)}, want both")


def phase_train(seed: int) -> None:
    from repro.cnn.train import train_plan
    from repro.core import ArrayConfig
    from repro.launch import serve_cnn
    mapping, _ = serve_cnn.map_for_serving(NET, ArrayConfig(*ARRAY), ALG)
    losses: list = []
    t0 = time.perf_counter()
    r = train_plan(mapping, steps=TRAIN_STEPS, batch=TRAIN_BATCH, seed=seed,
                   losses=losses)
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        fail(f"train_plan losses {losses}: want {TRAIN_STEPS} finite")
    print(f"[train] train_plan {NET} steps={r.steps} batch={TRAIN_BATCH} "
          f"losses={losses} seconds={time.perf_counter() - t0!r}")


def one_chip(seed: int) -> dict:
    t0 = time.perf_counter()
    from repro.launch.device import (compile_stats, enable_compile_cache,
                                     held_device_nodes)
    cache = enable_compile_cache()
    dev = _tpu_devices(1)
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} nodes={','.join(held_device_nodes())}")
    print(f"compile cache: {cache}")
    phase_serve(seed)
    phase_train(seed)
    print(f"compile cache: {cache} {compile_stats()} "
          f"total_s={time.perf_counter() - t0!r}")
    return dev


def phase_mesh(seed: int) -> dict:
    """Child process of --four-chips: sharded mesh vs the one-chip plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ArrayConfig, MacroGrid
    from repro.exec import (REASSOC_RTOL, compile_plan, execute_plan,
                            reassoc_error)
    from repro.launch import serve_cnn
    from repro.launch.device import enable_compile_cache
    from repro.launch.mesh import mesh_tag
    enable_compile_cache()
    dev = _tpu_devices(REPLICAS)
    mapping, _ = serve_cnn.map_for_serving(NET, ArrayConfig(*ARRAY), ALG,
                                           grid=MacroGrid(*MESH_GRID))
    mesh = serve_cnn.serving_mesh_for(mapping, MESH_BATCH)
    if mesh is None:
        fail("no serving mesh over four chips")
    ids = sorted({d.id for d in np.asarray(mesh.devices).ravel()})
    if len(ids) != REPLICAS:
        fail(f"mesh spans devices {ids}, want {REPLICAS} distinct chips")
    sharded = compile_plan(mapping, executor_policy="mapped", mesh=mesh,
                           batch=MESH_BATCH)
    if not any(lp.use_mesh for lp in sharded.layers):
        fail("no layer of the plan runs under the mesh")
    single = compile_plan(mapping, executor_policy="mapped",
                          batch=MESH_BATCH)
    rng, ks = serve_cnn._serving_kernels(mapping, seed)
    first = mapping.layers[0].layer
    x = jnp.asarray(rng.randn(MESH_BATCH, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_mesh = np.asarray(execute_plan(sharded, ks, x, mesh=mesh))
        y_one = np.asarray(execute_plan(single, ks, x))
    if not np.isfinite(y_mesh).all():
        fail("sharded output has non-finite values")
    rel = reassoc_error(y_mesh, y_one)
    print(f"[mesh] mesh={dict(mesh.shape)} ({mesh_tag(mesh)}) devices={ids} "
          f"layers_on_mesh={sum(lp.use_mesh for lp in sharded.layers)}/"
          f"{len(sharded.layers)}")
    print(f"[mesh] sharded vs one-chip mapped plan (f32, highest precision,"
          f" batch {MESH_BATCH}): rel_err={rel!r} rule rel<={REASSOC_RTOL} "
          f"(DESIGN.md §7)")
    if not rel <= REASSOC_RTOL:
        fail(f"sharded output off the one-chip plan: rel_err={rel!r}")
    return dev


def phase_replicas(seed: int) -> None:
    """Four one-chip workers behind the router; this process stays off
    the backend."""
    from repro.launch.device import host_tpu_chips
    from repro.launch.replica import WorkerConfig, serve_replicas
    from repro.launch.serve_cnn import poisson_arrivals
    if host_tpu_chips() < REPLICAS:
        fail(f"host has {host_tpu_chips()} TPU chips, need {REPLICAS}")
    trace = poisson_arrivals(REPLICA_REQUESTS, ARRIVAL_RATE, MAX_REQUEST // 2,
                             seed=seed)
    cfg = WorkerConfig(net=NET, array=ARRAY, alg=ALG,
                       max_batch=REPLICA_MAX_BATCH, max_delay_ms=MAX_DELAY_MS,
                       seed=seed, platform="tpu")
    rs = serve_replicas(trace, cfg, REPLICAS)
    print("[replicas] " + rs.describe().replace("\n", "\n[replicas] "))
    served = sum(v.served_requests for v in rs.workers.values())
    tags = sorted(v.device for v in rs.workers.values())
    # the device nodes each worker holds open ("tpu:kind#0@vfio/2 chip2"):
    # one each, and no two workers on the same node
    held = [t.partition("@")[2].split(" ")[0].split(",") for t in tags]
    nodes = {n for h in held for n in h if n}
    if rs.deaths or rs.duplicate_serves or served != len(trace):
        fail(f"replicas served {served}/{len(trace)} requests, "
             f"duplicate_serves={rs.duplicate_serves}, deaths={rs.deaths}")
    if (len(nodes) != REPLICAS or any(len(h) != 1 for h in held)
            or not all(t.startswith("tpu:") for t in tags)):
        fail(f"workers did not each hold their own chip: {tags}")
    print(f"[replicas] {REPLICAS} workers on {tags}: every request "
          f"served exactly once ({served}/{len(trace)}, duplicate_serves="
          f"{rs.duplicate_serves}); smoke reading, not a benchmark: "
          f"images_per_s={rs.images_per_s!r}")


def four_chips(seed: int) -> dict:
    """The mesh phase runs in a child that owns the four chips and exits
    before the replica workers need them."""
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--mesh-phase",
         "--seed", str(seed)], capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line)
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        fail(f"mesh phase exited {proc.returncode}")
    dev = json.loads(lines[-1])["device"]
    phase_replicas(seed)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the sharded-mesh and replica phases on four "
                         "chips instead of the one-chip serve and train")
    ap.add_argument("--mesh-phase", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        _src_on_path()
        if args.mesh_phase:
            dev = phase_mesh(args.seed)
        elif args.four_chips:
            dev = four_chips(args.seed)
        else:
            dev = one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
