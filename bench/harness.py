"""Set-up, serving and the output check shared by ``run.py``,
``sweep.py`` and ``control.py``.

Everything the benchmark takes from the program is here and in
``window.py``: the mapping search (``core.map_net``) with the
configuration's layer graph (``core.GlueSpec``), the plan ladder
(``launch.batching.PlanLadder`` over ``batch_tiers``), the coalescer and
``exec.execute_plan``.  The weights, the images, the traffic and the
reference (the configuration's own module, ``cells.reference_module``)
are the benchmark's own.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import cells

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"

#: Requests whose outputs a run keeps for the check, drawn from the
#: seed; each tier served adds its first ``KEEP_PER_TIER``.
CHECK_REQUESTS = 128
KEEP_PER_TIER = 2


class NoChip(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def setup_paths() -> None:
    """The program's sources, and JAX's compile cache at a fixed path in
    the checkout (the path is part of the cache key).  Call before JAX
    is imported."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise NoChip(f"no program under {src}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def check_device(chips: int) -> dict:
    """``{"platform", "kind", "count"}`` of the TPU this process drives;
    :class:`NoChip` without one, or with fewer than ``chips``."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


class CompileCounter:
    """Traces and backend compiles JAX reports while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


def layer_glue(config: dict):
    """The configuration's layer graph as one ``GlueSpec`` a layer, its
    JSON lists as tuples; None where no layer carries ``"glue"``."""
    from repro.core import GlueSpec
    layers = config["layers"]
    lacking = [l["name"] for l in layers if "glue" not in l]
    if len(lacking) == len(layers):
        return None
    if lacking:
        raise ValueError(f"{config['name']}: {lacking} carry no \"glue\"; "
                         f"give every layer its glue, or none")
    return tuple(GlueSpec(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in l["glue"].items()})
                 for l in layers)


def build_mapping(config: dict):
    """The TetrisG-SDK mapping of the configuration's layers, checked
    against the grouping and pruning the configuration states, with the
    layer graph its ``"glue"`` gives (else ``glue=None``: the program
    infers chain and concat)."""
    from repro.core import ArrayConfig, ConvLayerSpec, MacroGrid, map_net
    glue = layer_glue(config)
    m = config["mapping"]
    specs = [ConvLayerSpec(name=l["name"], i_w=l["size"], i_h=l["size"],
                           k_w=l["kernel"], k_h=l["kernel"], ic=l["ic"],
                           oc=l["oc"], stride=l["stride"])
             for l in config["layers"]]
    net = map_net(config["name"], specs, ArrayConfig(*m["array"]),
                  m["algorithm"], MacroGrid(*m["macro_grid"]),
                  groups=tuple(m["groups"]))
    for lm, l in zip(net.layers, config["layers"]):
        pruned, c = [], 0
        for t in lm.tiles:
            c += t.depth
            if t.pruned_channels:
                pruned.append([c, t.pruned_channels])
            c += t.pruned_channels
        if lm.group != l["groups"] or pruned != l["pruned"]:
            raise ValueError(
                f"{l['name']}: the mapping groups by {lm.group} and prunes "
                f"{pruned}; the configuration states groups={l['groups']} "
                f"pruned={l['pruned']}")
    return net if glue is None else dataclasses.replace(net, glue=glue)


class Server:
    """One configuration set up to serve: weights and image pool from the
    seed, the mapping, and the plan ladder (the mix's ``tiers``, else
    ``batch_tiers(max_batch)``) with every tier compiled and run twice.
    ``setup`` holds the seconds of each phase.  ``reference`` is the
    configuration's own reference module."""

    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.core import memo
        from repro.exec import donation_supported
        from repro.launch.batching import batch_tiers
        from repro.launch.device import enable_compile_cache
        enable_compile_cache()
        memo.set_disk_cache(CACHE / "mapping")
        self.config, self.mix = config, mix
        self.reference = cells.reference_module(config)
        self.counter = CompileCounter()
        self.setup = {}
        t0 = time.perf_counter()
        self.reseed(seed)
        t1 = time.perf_counter()
        self.setup["data_s"] = t1 - t0
        self.net = build_mapping(config)
        t2 = time.perf_counter()
        self.setup["search_s"] = t2 - t1
        self.donate = donation_supported()
        self.max_batch = int(mix["max_batch"])
        self.max_delay_s = float(mix["max_delay_ms"]) / 1e3
        self.ladder = self._warm(mix.get("tiers")
                                 or batch_tiers(self.max_batch))
        self.setup["ladder_warm_s"] = time.perf_counter() - t2
        self.flops_per_image = self.reference.useful_flops_per_image(
            config)

    def reseed(self, seed: int) -> None:
        import numpy as np
        self.kernels, pool = self.reference.make_data(
            self.config, int(self.mix["pool_images"]), seed)
        self.pool = np.asarray(pool)

    def _warm(self, tiers):
        import jax
        import numpy as np
        from repro.exec import execute_plan
        from repro.launch.batching import PlanLadder
        ladder = PlanLadder(self.net, tiers)
        self.tier_ms = {}
        for t in ladder.tiers:
            x = np.zeros((t, *self.config["input"]), np.float32)
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(execute_plan(
                    ladder.plans[t], self.kernels, jax.device_put(x),
                    donate=self.donate))
            # the second run: upload, forward and wait, compiled
            self.tier_ms[t] = (time.perf_counter() - t0) * 1e3
        return ladder

    @property
    def executors(self):
        return self.ladder.plans[self.ladder.max_batch].executors

    def serve(self, sched, seconds: float, *, keep, trace=None,
              execute=None):
        """One window over ``sched``; counts compiles inside it.
        ``trace=(from_s, start)`` calls ``start()`` once the window has
        run ``from_s`` seconds and records spans from then on."""
        import window as winlib
        self.counter.count = 0
        self.counter.on = True
        try:
            win = winlib.serve_window(
                self.ladder, self.kernels, self.pool, sched, seconds,
                max_batch=self.max_batch, max_delay_s=self.max_delay_s,
                donate=self.donate, keep=keep, keep_per_tier=KEEP_PER_TIER,
                trace=trace, execute=execute)
        finally:
            self.counter.on = False
        win.compiles = self.counter.count
        return win

    def reference_outputs(self, sched, ids, *, operands=None):
        """The reference over the kept requests' images, concatenated in
        the order of ``ids``."""
        import numpy as np
        rows = np.concatenate([self.pool[sched.starts[j]:sched.starts[j]
                                         + sched.sizes[j]] for j in ids])
        return self.reference.forward_blocks(
            self.config, self.kernels, rows, block=self.max_batch,
            operands=operands)


def per_request_errors(sched, ids, outputs, ref):
    """``max|y - ref| / max|ref|`` of each request in ``ids``;
    ``outputs[j]`` are request ``j``'s rows, ``ref`` the reference rows
    of all ``ids`` concatenated."""
    import reference
    errs, off = [], 0
    for j in ids:
        n = int(sched.sizes[j])
        errs.append(reference.rel_err(outputs[j], ref[off:off + n]))
        off += n
    return errs


def check_outputs(server: Server, sched, win, limit: float) -> dict:
    """Every kept request against the reference: the worst request's
    relative error, the tiers the window served that no kept request
    covers, and the sampled requests that were never answered."""
    ids = sorted(win.kept)
    if not ids:
        return {"worst_rel_err": float("inf"), "tiers_unchecked": -1,
                "unanswered": win.unanswered, "requests": 0, "images": 0,
                "over_limit": 0}
    errs = per_request_errors(sched, ids, win.kept,
                              server.reference_outputs(sched, ids))
    checked = {int(win.tier_of[j]) for j in ids}
    return {"worst_rel_err": max(errs),
            "tiers_unchecked": len(set(win.tiers_served) - checked),
            "unanswered": win.unanswered,
            "requests": len(ids),
            "images": int(sum(int(sched.sizes[j]) for j in ids)),
            "over_limit": int(sum(e > limit for e in errs))}
