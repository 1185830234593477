"""Find a configuration's knee: the highest Poisson rate it sustains.

    python3 bench/sweep.py --config cnn8 --start 1000 --seconds 5 \
        --seeds 11 12 --out <file>

One process sets the configuration up once (``harness.Server``) and, for
each seed, serves windows of the ``poisson`` mix at rising rates:
doubling from ``--start`` until a rate fails, then ``--steps``
bisections between the last rate that held and the first that failed.
A rate holds when the queue does not grow (``WindowResult.queue_grew``:
no more requests wait at the close than waited at any launch in the
window's first half) and, where the configuration states a latency bound
(``knee.p99_ms``), the 99th percentile of latency (due arrival ->
outputs on the host) is within it.  The knee is the lowest of the
seeds' knees.  A cell's rate is then set from it by hand, in
``bench/cells/<cell>.json`` beside the knee, with the sweep's table in
PERF.md.  Runs on the chip only.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cells  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from harness import log  # noqa: E402


def point(server, mix, rate: float, seconds: float, seed: int,
          p99_ms) -> dict:
    import numpy as np
    sched = traffic.schedule(mix, rate, seconds, seed)
    win = server.serve(sched, seconds, keep=np.zeros(len(sched), bool))
    p99 = win.latency_ms(99)
    first = [d for t, d in win.depth if t < seconds / 2]
    within = p99 is not None and (p99_ms is None or p99 <= p99_ms)
    return {"rate_per_s": rate, "seed": seed, "due": win.due,
            "completed": win.completed,
            "queued_at_close": win.queued_at_close,
            "most_waiting_first_half": max(first, default=0),
            "grew": win.queue_grew, "images_per_s": win.images_per_s,
            "p50_ms": win.latency_ms(50), "p95_ms": win.latency_ms(95),
            "p99_ms": p99, "batch_fill": win.batch_fill,
            "compiles": win.compiles,
            "holds": bool(within and not win.queue_grew)}


def search(run, start: float, steps: int):
    """Double while rates hold, halve while they fail, until both are
    seen; then bisect.  The highest rate that held, or None."""
    good, bad, rate = None, None, start
    while start / 64 <= rate <= start * 4096:
        if run(rate):
            good, rate = rate, rate * 2
        else:
            bad, rate = rate, rate / 2
        if good is not None and bad is not None:
            break
    for _ in range(steps if good is not None and bad else 0):
        mid = (good + bad) / 2
        if run(mid):
            good = mid
        else:
            bad = mid
    return good


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--start", type=float, required=True,
                    help="first rate, requests per second")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        harness.setup_paths()
        config = cells.load_config(args.config)
        with open(harness.BENCH / "traffic" / "poisson.json") as f:
            mix = json.load(f)
        device = harness.check_device(1)
    except (harness.NoChip, KeyError, OSError) as e:
        log(f"sweep: cannot run: {e}")
        return 3
    p99_ms = config["knee"].get("p99_ms")
    server = harness.Server(config, mix, args.seeds[0])
    log(f"[sweep] {args.config} {device} setup_s="
        f"{time.perf_counter() - T_START!r} {server.setup} "
        f"tier_ms={server.tier_ms}")
    rows, knees = [], {}
    for seed in args.seeds:
        server.reseed(seed)

        def run(rate):
            r = point(server, mix, rate, args.seconds, seed, p99_ms)
            rows.append(r)
            log("[sweep] " + json.dumps(r))
            return r["holds"]

        knees[seed] = search(run, args.start, args.steps)
    found = [k for k in knees.values() if k is not None]
    knee = min(found) if len(found) == len(knees) else None
    out = {"config": args.config, "device": device,
           "seconds": args.seconds, "seeds": args.seeds,
           "p99_bound_ms": p99_ms, "knee_per_s": knee,
           "knee_per_seed": knees, "points": rows}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"config": args.config, "knee_per_s": knee,
                      "knee_per_seed": knees}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
