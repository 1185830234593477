"""Run one benchmark cell on this machine's chips and print its result.

    python3 bench/run.py --workload cnn8.poisson --seed 7 --seconds 20 \
        --trace 0

Set-up (``setup_s``, from process start to the window): imports and
backend, the weights and the image pool made on the device from the
seed, the TetrisG-SDK mapping (through a disk cache under
``bench/.cache``), the plan ladder compiled and every tier run twice.
Then the window serves the cell's traffic for ``--seconds``
(``bench/window.py``).  After it the device's peak memory is read and
the kept outputs are compared with the plain float32 reference (the
module the configuration names, else ``bench/reference.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window's last ``TRACE_SECONDS`` and
reports the cell's per-layer
metrics, each read by its own ``bench/metrics/<name>.py``.  The last
line of standard output is the result as one JSON object; the numbers
the check compared, each with its limit, are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from harness import log  # noqa: E402

#: A traced run records the profiler over the window's last seconds.
TRACE_SECONDS = 3.0


class Run:
    """What one run measured; ``bench/metrics/*.py`` read from it."""

    def __init__(self, cell, window, setup, trace, peaks, flops_per_image):
        self.cell = cell
        self.window = window            # window.WindowResult
        self.setup = setup              # phase -> seconds
        self.trace = trace              # trace_reduce.Summary or None
        self.peaks = peaks              # this device's bench/peaks.json row
        self.flops_per_image = flops_per_image


def end_to_end(name: str, run: Run):
    if name == "images_per_s":
        return run.window.images_per_s
    if name == "latency_p95_ms":
        return run.window.latency_ms(95)
    if name == "setup_s":
        return run.setup["setup_s"]
    raise KeyError(f"no end-to-end metric {name!r}")


def _start_trace(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, execute=None) -> dict:
    """Set up, serve the window, check; the result object.  ``execute``
    stands in for ``exec.execute_plan`` (the fault tests)."""
    import jax
    import numpy as np
    import window as winlib

    peaks = harness.peaks_for(device["kind"])
    imports_s = time.perf_counter() - t_start
    server = harness.Server(cell.config, cell.traffic, seed)
    setup = dict(imports_s=imports_s, **server.setup)
    sched = traffic.schedule(cell.traffic, cell.rate_per_s, seconds, seed)
    keep = winlib.sample_mask(len(sched), harness.CHECK_REQUESTS, seed)
    log(f"[setup] {cell.config['name']} layers={len(server.net.layers)} "
        f"executors={','.join(sorted(set(server.executors)))} "
        f"tiers={server.ladder.tiers} requests={len(sched)} "
        f"rate={cell.rate_per_s}/s images={int(sched.sizes.sum())} "
        f"donate={server.donate} tier_ms={server.tier_ms}")
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup["setup_s"] = time.perf_counter() - t_start
    win = server.serve(
        sched, seconds, keep=keep, execute=execute,
        trace=(max(0.0, seconds - TRACE_SECONDS),
               lambda: _start_trace(trace_dir)) if trace else None)
    summary = None
    if trace:
        import trace_reduce
        jax.profiler.stop_trace()
        summary = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device,
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    server.ladder = None            # the program's state, before the check
    limit = float(cell.config["check"]["worst_rel_err_limit"])
    chk = harness.check_outputs(server, sched, win, limit)

    lat = win.lateness_s[np.isfinite(win.lateness_s)]
    log(f"[window] due={win.due} completed={win.completed} "
        f"queued_at_close={win.queued_at_close} "
        f"images={win.images_completed} batches={len(win.batches)} "
        f"tiers={win.tiers_served} compiles_in_window={win.compiles}")
    if len(lat):
        log(f"[window] generator lateness (due -> queued) ms: "
            f"p50={float(traffic.percentile(lat, 50)) * 1e3!r} "
            f"p99={float(traffic.percentile(lat, 99)) * 1e3!r} "
            f"max={float(lat.max()) * 1e3!r}")
    log("[setup] " + " ".join(f"{k}={v!r}" for k, v in setup.items()))
    st = win.steps
    log(f"[steps] slow (> {st.SLOW_S} s) {st.slow} taking {st.slow_s!r} s; "
        f"gc {st.gc_runs} runs {st.gc_s!r} s (longest {st.gc_max_s!r} s); "
        "longest " + " ".join(f"{k}={v!r}" for k, v in st.longest.items()))
    log(f"[check] kept {chk['requests']} requests ({chk['images']} images)"
        f" against the float32 reference")

    run = Run(cell, win, setup, summary, peaks, server.flops_per_image)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = (chk["worst_rel_err"] <= limit and chk["tiers_unchecked"] == 0
               and chk["unanswered"] == 0 and win.completed > 0)
    result = {"correct": bool(correct), "attempted": win.due,
              "failed": chk["over_limit"] + chk["unanswered"],
              "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {
        "worst_rel_err": {"value": chk["worst_rel_err"], "limit": limit},
        "tiers_unchecked": {"value": chk["tiers_unchecked"], "limit": 0},
        "unanswered": {"value": chk["unanswered"], "limit": 0}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        harness.setup_paths()
        cell = cells.load_cell(args.workload)
        device = harness.check_device(cell.chips)
    except (harness.NoChip, KeyError, OSError) as e:
        log(f"bench: cannot run {args.workload}: {e}")
        return 3
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START, device=device)
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
