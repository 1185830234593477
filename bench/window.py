"""The measured window: an open-loop client and the serve loop.

The loop mirrors the program's ``launch/serve_cnn.serve_dynamic`` step
for step, built from the same public pieces (``batching.Coalescer``,
``batching.PlanLadder``, ``exec.execute_plan``): push the arrivals that
are due into the coalescer, pop the ready FIFO prefix, pad it to its
ladder tier, upload it and run the tier's plan, wait for the device.
Unlike ``serve_dynamic`` every request carries its own images, and the
outputs come back to the host, where each request takes its own rows:
a request is timed from its due arrival to that moment.

When a trace is asked for, the loop starts the profiler part way
through the window and from then on puts each call into the program in a
span (``bench.pop``, ``bench.prepare``, ``bench.dispatch``,
``bench.wait``, ``bench.fetch``) inside one ``bench.window`` span that
ends with the window: the trace covers the window's last seconds, where
the load is steady, and stays small.

The loop also watches its own steps (:class:`StepWatch`): the longest
one, with what the thread and the process did meanwhile, tells a stall
of the host (the garbage collector, page faults, preemption) from a wait
on the runtime.
"""
from __future__ import annotations

import contextlib
import gc
import resource
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from traffic import Schedule, percentile, rng_for

_NO_SPAN = contextlib.nullcontext()

#: How long after the close the loop serves on for sampled answers.
DRAIN_LIMIT_S = 60.0

_RUSAGE = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)


def no_span(name: str):
    return _NO_SPAN


def trace_span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclass
class WindowResult:
    seconds: float
    sizes: np.ndarray
    latency_s: np.ndarray           # due arrival -> outputs on the host
    wait_s: np.ndarray              # due arrival -> batch launch
    lateness_s: np.ndarray          # due arrival -> pushed to the queue
    tier_of: np.ndarray             # ladder tier that served it (0: none)
    batches: List[Tuple[int, int, float]]  # (tier, request images, launch)
    kept: Dict[int, np.ndarray]     # request -> its outputs, for the check
    due: int                        # requests due inside the window
    queued_at_close: int            # due but not served at the close
    depth: List[Tuple[float, int]] = None  # (launch, requests waiting)
    traced_from: Optional[float] = None    # when the trace began
    steps: Optional["StepWatch"] = None
    unanswered: int = 0             # sampled requests never answered
    compiles: int = 0               # compilations inside the window

    @property
    def done(self) -> np.ndarray:
        return np.isfinite(self.latency_s)

    @property
    def completed(self) -> int:
        return int(self.done.sum())

    @property
    def images_completed(self) -> int:
        return int(self.sizes[self.done].sum())

    @property
    def images_per_s(self) -> float:
        return self.images_completed / self.seconds

    def latency_ms(self, q: float) -> Optional[float]:
        lat = self.latency_s[self.done]
        return percentile(lat, q) * 1e3 if len(lat) else None

    def wait_ms(self, q: float) -> Optional[float]:
        w = self.wait_s[np.isfinite(self.wait_s) & self.done]
        return percentile(w, q) * 1e3 if len(w) else None

    @property
    def batch_fill(self) -> Optional[float]:
        padded = sum(t for t, _, _ in self.batches)
        return sum(r for _, r, _ in self.batches) / padded if padded else None

    @property
    def tiers_served(self) -> Tuple[int, ...]:
        return tuple(sorted({t for t, _, _ in self.batches}))

    @property
    def traced_images(self) -> int:
        """Request images of the batches launched after the trace began:
        the loop is serial, so exactly their device work lies in it."""
        if self.traced_from is None:
            return 0
        return sum(r for _, r, at in self.batches if at >= self.traced_from)

    @property
    def queue_grew(self) -> bool:
        """More requests wait at the close than waited at any launch in
        the window's first half: the queue grew all through it."""
        first = [d for t, d in self.depth or () if t < self.seconds / 2]
        return self.queued_at_close > max(first, default=0)


class StepWatch:
    """The loop's longest step (one batch: push and pop, prepare,
    dispatch, wait, fetch, hand-out) with the seconds of each phase and
    what happened meanwhile: the thread's and the process's CPU seconds,
    the thread's page faults and context switches, and the seconds
    Python's garbage collector ran.  Steps over :attr:`SLOW_S` are
    counted."""

    SLOW_S = 0.1
    PHASES = ("pop_prepare", "dispatch", "wait", "fetch", "hand_out")

    def __init__(self):
        self.longest: dict = {}
        self.slow = 0
        self.slow_s = 0.0
        self.gc_s = 0.0
        self.gc_max_s = 0.0
        self.gc_runs = 0
        self._gc_t = None

    def gc_event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            d = time.perf_counter() - self._gc_t
            self.gc_s += d
            self.gc_max_s = max(self.gc_max_s, d)
            self.gc_runs += 1
            self._gc_t = None

    def begin(self) -> None:
        self._cpu = time.thread_time()
        self._proc = time.process_time()
        self._ru = resource.getrusage(_RUSAGE)
        self._gc0 = self.gc_s

    def end(self, at: float, marks: Tuple[float, ...]) -> None:
        """``marks``: the clock at the step's start, after each phase."""
        wall = marks[-1] - marks[0]
        if wall > self.SLOW_S:
            self.slow += 1
            self.slow_s += wall
        if wall <= self.longest.get("wall_s", 0.0):
            return
        ru = resource.getrusage(_RUSAGE)
        self.longest = {
            "at_s": at, "wall_s": wall,
            **{p + "_s": b - a for p, a, b in zip(self.PHASES, marks,
                                                   marks[1:])},
            "thread_cpu_s": time.thread_time() - self._cpu,
            "process_cpu_s": time.process_time() - self._proc,
            "gc_s": self.gc_s - self._gc0,
            "minor_faults": ru.ru_minflt - self._ru.ru_minflt,
            "major_faults": ru.ru_majflt - self._ru.ru_majflt,
            "voluntary_switches": ru.ru_nvcsw - self._ru.ru_nvcsw,
            "involuntary_switches": ru.ru_nivcsw - self._ru.ru_nivcsw}


def sample_mask(n: int, want: int, seed: int) -> np.ndarray:
    """Which of ``n`` requests the check keeps (about ``want``), drawn
    from the seed."""
    p = min(1.0, want / max(n, 1))
    return rng_for(seed, 4).random(n) < p


def serve_window(ladder, kernels, pool: np.ndarray, sched: Schedule,
                 seconds: float, *, max_batch: int, max_delay_s: float,
                 donate: bool, keep: np.ndarray, keep_per_tier: int,
                 trace: Optional[Tuple[float, Callable]] = None,
                 execute: Callable = None) -> WindowResult:
    """Serve ``sched`` for ``seconds`` through ``ladder``.

    Outputs are kept for the requests ``keep`` marks, for the first
    ``keep_per_tier`` requests each tier serves, and for the first
    request of the largest size, so the check sees every tier and the
    longest request.  Requests still queued at the close are neither
    completed nor failed; after the close the loop serves on until each
    marked request has its answer (``_drain``)."""
    import jax
    from repro.exec import execute_plan
    from repro.launch.batching import Coalescer
    execute = execute or execute_plan
    co = Coalescer(max_batch, max_delay_s)
    times = sched.times
    sizes = sched.sizes
    starts = sched.starts
    n = len(times)
    latency = np.full(n, np.inf)
    wait = np.full(n, np.inf)
    lateness = np.full(n, np.inf)
    tier_of = np.zeros(n, np.int64)
    batches: List[Tuple[int, int, float]] = []
    depth: List[Tuple[float, int]] = []
    steps = StepWatch()
    served = 0
    traced_from = None
    kept: Dict[int, np.ndarray] = {}
    per_tier: Dict[int, int] = {}
    biggest = int(sizes.max())
    biggest_kept = False
    inf = float("inf")
    span = no_span
    window_span = contextlib.ExitStack()
    i = 0
    clock = time.perf_counter
    gc.callbacks.append(steps.gc_event)
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if trace is not None and span is no_span and now >= trace[0]:
            trace[1]()
            window_span.enter_context(trace_span("bench.window"))
            span = trace_span
            now = traced_from = clock() - t0
        steps.begin()
        while i < n and times[i] <= now:
            co.push(int(sizes[i]), float(times[i]), payload=i)
            lateness[i] = now - times[i]
            i += 1
        with span("bench.pop"):
            batch = co.pop(now)
        if not batch:
            deadline = co.next_deadline()
            horizon = min(times[i] if i < n else inf,
                          inf if deadline is None else deadline, seconds)
            if horizon > now:
                time.sleep(horizon - now)
            continue
        with span("bench.prepare"):
            rows = sum(r.rows for r in batch)
            tier, plan = ladder.plan_for(rows)
            xd = jax.device_put(_gather(batch, tier, pool, starts))
        launch = clock() - t0
        depth.append((launch, i - served))
        with span("bench.dispatch"):
            y = execute(plan, kernels, xd, donate=donate)
        dispatched = clock() - t0
        with span("bench.wait"):
            y.block_until_ready()
        waited = clock() - t0
        with span("bench.fetch"):
            y = np.asarray(y)
            done = clock() - t0
            off = 0
            for r in batch:
                j = r.payload
                out = y[off:off + r.rows]
                off += r.rows
                latency[j] = done - times[j]
                wait[j] = launch - times[j]
                tier_of[j] = tier
                if (keep[j] or per_tier.get(tier, 0) < keep_per_tier
                        or (not biggest_kept and r.rows == biggest)):
                    kept[j] = out.copy()
                    per_tier[tier] = per_tier.get(tier, 0) + 1
                    biggest_kept |= r.rows == biggest
        served += len(batch)
        batches.append((tier, rows, launch))
        steps.end(now, (now, launch, dispatched, waited, done,
                        clock() - t0))
    window_span.close()
    gc.callbacks.remove(steps.gc_event)
    # a request that finished after the close did not finish in the window
    late = latency + times > seconds
    latency[late] = np.inf
    due = int(np.searchsorted(times, seconds, side="left"))
    while i < due:
        co.push(int(sizes[i]), float(times[i]), payload=i)
        i += 1
    owed = {j for j in np.flatnonzero(keep[:due]).tolist() if j not in kept}
    _drain(co, ladder, kernels, pool, starts, owed, kept, tier_of, execute,
           donate)
    return WindowResult(
        seconds=seconds, sizes=sizes, latency_s=latency, wait_s=wait,
        lateness_s=lateness, tier_of=tier_of, batches=batches, kept=kept,
        due=due, queued_at_close=due - int(np.isfinite(latency).sum()),
        depth=depth, traced_from=traced_from, steps=steps,
        unanswered=len(owed))


def _gather(batch, tier: int, pool: np.ndarray,
            starts: np.ndarray) -> np.ndarray:
    """The batch's requests' own pool rows, zero-padded to ``tier``."""
    x = np.zeros((tier,) + pool.shape[1:], np.float32)
    off = 0
    for r in batch:
        s = starts[r.payload]
        x[off:off + r.rows] = pool[s:s + r.rows]
        off += r.rows
    return x


def _drain(co, ladder, kernels, pool, starts, owed, kept, tier_of, execute,
           donate) -> None:
    """After the close: serve the queue on in FIFO order, running only
    the batches that hold a request in ``owed`` (sampled, not answered),
    until each has its answer, the queue is empty or
    :data:`DRAIN_LIMIT_S` has passed.  These answers are late and
    untimed; one that never comes counts against ``correct``."""
    import jax
    t_end = time.perf_counter() + DRAIN_LIMIT_S
    while owed and time.perf_counter() < t_end:
        batch = co.pop(float("inf"), force=True)
        if not batch:
            return
        if not any(r.payload in owed for r in batch):
            continue
        tier, plan = ladder.plan_for(sum(r.rows for r in batch))
        y = np.asarray(execute(plan, kernels,
                               jax.device_put(_gather(batch, tier, pool,
                                                      starts)),
                               donate=donate))
        off = 0
        for r in batch:
            j = r.payload
            if j in owed:
                kept[j] = y[off:off + r.rows].copy()
                tier_of[j] = tier
                owed.discard(j)
            off += r.rows
