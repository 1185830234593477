"""Readings for a cell's output check: the program's and the control's.

    python3 bench/control.py --workload densenet40.poisson \
        --seeds 1,2,3 --seconds 4 --out control.json

One process sets the cell up once and, for each seed, makes that seed's
weights and images, serves a short window at the cell's own rate and
tiers, and compares the kept requests with the float32 reference twice:

* ``program`` — what the timed path produced (the number ``run.py``
  compares with the configuration's ``worst_rel_err_limit``);
* ``control`` — the reference put in the program's place, its operands
  rounded to the precision below the one the configuration states
  (``precision.control_operands``), over the same requests.

The largest ``program`` reading over the seeds is the lower end for the
limit, the smallest ``control`` reading the upper end (PERF.md gives
both).  The benchmark's own runs never run this.  Runs on the chip only.
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


def readings(server, cell, seed: int, seconds: float,
             operands: str) -> dict:
    """One seed's program and control readings at the cell's load."""
    import window as winlib
    server.reseed(seed)
    sched = traffic.schedule(cell.traffic, cell.rate_per_s, seconds, seed)
    keep = winlib.sample_mask(len(sched), harness.CHECK_REQUESTS, seed)
    win = server.serve(sched, seconds, keep=keep)
    ids = sorted(win.kept)
    ref = server.reference_outputs(sched, ids)
    low = server.reference_outputs(sched, ids, operands=operands)
    off, ctrl = 0, {}
    for j in ids:
        n = int(sched.sizes[j])
        ctrl[j] = low[off:off + n]
        off += n
    prog = harness.per_request_errors(sched, ids, win.kept, ref)
    cont = harness.per_request_errors(sched, ids, ctrl, ref)
    return {"seed": seed, "requests": len(ids), "tiers": win.tiers_served,
            "completed": win.completed, "program": max(prog),
            "control": max(cont), "control_median": sorted(cont)[
                len(cont) // 2]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        harness.setup_paths()
        cell = cells.load_cell(args.workload)
        device = harness.check_device(cell.chips)
    except (harness.NoChip, KeyError, OSError) as e:
        log(f"control: cannot run: {e}")
        return 3
    operands = cell.config["precision"]["control_operands"]
    server = harness.Server(cell.config, cell.traffic, seeds[0])
    rows = []
    for seed in seeds:
        r = readings(server, cell, seed, args.seconds, operands)
        rows.append(r)
        log("[control] " + json.dumps(r))
    out = {"workload": args.workload, "device": device,
           "operands": operands, "seconds": args.seconds,
           "lower": max(r["program"] for r in rows),
           "upper": min(r["control"] for r in rows), "seeds": rows}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in
                      ("workload", "operands", "lower", "upper")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
