"""Multi-replica scaling bench: N worker processes vs one serve loop.

The ISSUE 9 acceptance quantity: on the SAME backlogged Poisson trace,
``serve_replicas`` with N=2 workers must reach aggregate effective
images/s >= the single-process ``serve_dynamic`` path (PR 5) — the
paper's inter-macro replication argument applied at process level.
Also measures what the shared disk cache buys a cold worker: the same
fleet is brought up twice against one cache directory, cold (empty
cache — every worker builds its search tables) then warm (pure disk
hits), and per-worker start-up seconds are reported for both.

This process never starts a JAX backend: the workers and the
single-process baseline each run in a child process of their own, so on
a TPU host every chip is free for whichever of them needs it.

    python -m benchmarks.replica_bench --smoke            # CI: 2 workers
    python -m benchmarks.replica_bench --full --replicas 4
    python -m benchmarks.replica_bench --smoke --ledger BENCH_serve.json \
        --pr "PR 9"

Prints the harness CSV (``name,usec,extras``) to stdout — CI tees it
into ``bench-out/replica_bench.csv``.  Exposes ``run(full)`` returning
`benchmarks.common.Row`s like every bench module, though it is not in
run.py's default MODULES: spawning worker fleets is minutes, not the
seconds budget ``python -m benchmarks.run`` holds to.
"""
from __future__ import annotations

import argparse
import multiprocessing
import statistics
import tempfile
from concurrent.futures import ProcessPoolExecutor

from repro.core import memo
from repro.launch.device import expected_platform
from repro.launch.replica import WorkerConfig, serve_replicas
from repro.launch.serve_cnn import poisson_arrivals, serve_dynamic

from .common import Row

NET = "cnn8"
LAYERS = 4            # cnn8 prefix: keeps per-worker CPU compiles sane
ARRAY = (64, 64)
GRID = (2, 2)
GROUPS = (1, 2)
MAX_BATCH = 4
MAX_DELAY_MS = 2.0


def bench_config(cache_dir: str) -> WorkerConfig:
    """The worker profile both sides of the comparison serve."""
    return WorkerConfig(net=NET, array=ARRAY, grid=GRID, layers=LAYERS,
                        groups=GROUPS, max_batch=MAX_BATCH,
                        max_delay_ms=MAX_DELAY_MS, warmup=1,
                        cache_dir=cache_dir, platform=expected_platform())


def bench_trace(full: bool):
    """One backlogged Poisson trace (rate 0) shared by every leg."""
    n = 96 if full else 32
    return poisson_arrivals(n, 0.0, MAX_BATCH, seed=0)


def single_process_baseline(trace, cache_dir: str):
    """The PR 5 path: one process, one mesh, one plan ladder."""
    from repro.launch.replica import _build_mapping
    memo.set_disk_cache(cache_dir)
    mapping = _build_mapping(bench_config(cache_dir))
    return serve_dynamic(mapping, trace, max_batch=MAX_BATCH,
                         max_delay_ms=MAX_DELAY_MS, warmup=1)


def baseline_in_child(trace, cache_dir: str):
    """:func:`single_process_baseline` in a spawned child process, which
    owns the device while it runs and releases it when it exits."""
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=1, mp_context=ctx) as pool:
        return pool.submit(single_process_baseline, trace, cache_dir
                           ).result()


def replica_run(trace, cache_dir: str, n_replicas: int):
    return serve_replicas(trace, bench_config(cache_dir), n_replicas)


def _startup(rs) -> float:
    return statistics.mean(v.startup_s for v in rs.workers.values())


def run(full: bool = False, n_replicas: int = 2):
    """Harness-shaped entry: cold fleet, warm fleet, single baseline,
    and the scaling row comparing warm aggregate rate to the single
    process on the same trace."""
    trace = bench_trace(full)
    n_req = len(trace)
    rows = []
    with tempfile.TemporaryDirectory(prefix="replica-bench-") as cache:
        cold = replica_run(trace, cache, n_replicas)
        warm = replica_run(trace, cache, n_replicas)
        single = baseline_in_child(trace, cache)
        scaling = warm.images_per_s / max(single.images_per_s, 1e-12)
        rows.append(Row(
            f"replica/{NET}/single",
            single.wall_s / max(single.request_images, 1) * 1e6,
            f"images_per_s={single.images_per_s:.1f};"
            f"padded_images_per_s={single.padded_images_per_s:.1f};"
            f"requests={n_req};p50_ms={single.delay_ms(50):.2f};"
            f"p95_ms={single.delay_ms(95):.2f}"))
        rows.append(Row(
            f"replica/{NET}/n{n_replicas}-cold",
            cold.wall_s / max(cold.request_images, 1) * 1e6,
            f"images_per_s={cold.images_per_s:.1f};"
            f"startup_s={_startup(cold):.2f};"
            f"table_builds="
            f"{sum(v.table_misses for v in cold.workers.values())};"
            f"disk_hits={sum(v.disk_hits for v in cold.workers.values())}"))
        rows.append(Row(
            f"replica/{NET}/n{n_replicas}",
            warm.wall_s / max(warm.request_images, 1) * 1e6,
            f"images_per_s={warm.images_per_s:.1f};"
            f"padded_images_per_s={warm.padded_images_per_s:.1f};"
            f"scaling={scaling:.2f};requests={n_req};"
            f"startup_s={_startup(warm):.2f};"
            f"table_builds="
            f"{sum(v.table_misses for v in warm.workers.values())};"
            f"disk_hits={sum(v.disk_hits for v in warm.workers.values())};"
            f"p50_ms={warm.delay_ms(50):.2f};"
            f"p95_ms={warm.delay_ms(95):.2f};"
            f"requeued={warm.requeued};"
            f"duplicate_serves={warm.duplicate_serves}"))
    return rows


def ledger_entry(rows, *, pr: str, note: str) -> dict:
    """BENCH_serve.json entry: the single- vs multi-replica rates (and
    the cold/warm start-up the disk cache buys) as plain numbers."""
    def kv(row):
        return dict(p.split("=", 1) for p in row.derived.split(";"))
    single = next(r for r in rows if r.name.endswith("/single"))
    cold = next(r for r in rows if r.name.endswith("-cold"))
    multi = next(r for r in rows if not r.name.endswith("/single")
                 and not r.name.endswith("-cold"))
    return {
        "pr": pr,
        "note": note,
        "net": NET,
        "replicas": int(multi.name.rsplit("/n", 1)[1]),
        "requests": int(kv(multi)["requests"]),
        "single_images_per_s": float(kv(single)["images_per_s"]),
        "multi_images_per_s": float(kv(multi)["images_per_s"]),
        "scaling": float(kv(multi)["scaling"]),
        "cold_startup_s": float(kv(cold)["startup_s"]),
        "warm_startup_s": float(kv(multi)["startup_s"]),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--smoke", action="store_true",
                      help="32-request trace (the CI run)")
    mode.add_argument("--full", action="store_true",
                      help="96-request trace")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--csv", default=None,
                    help="also write the CSV to this path")
    ap.add_argument("--ledger", default=None,
                    help="append a BENCH_serve.json ledger entry here")
    ap.add_argument("--pr", default="",
                    help="ledger entry tag for --ledger")
    args = ap.parse_args(argv)

    rows = run(full=args.full, n_replicas=args.replicas)
    text = "\n".join(r.csv() for r in rows) + "\n"
    print(text, end="")
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(text)
    if args.ledger:
        from repro.tune.report import append_trajectory
        append_trajectory(args.ledger, ledger_entry(
            rows, pr=args.pr, note="smoke" if args.smoke else "full"))


if __name__ == "__main__":
    main()
