"""Multi-replica serving (launch/replica.py): router dispatch and
exactly-once accounting as pure unit tests, the full serve loop driven
deterministically through the in-memory fake transport + fake clock
(worker death, re-queue, heartbeat-timeout hang detection), and slow
real-multiprocess runs (scaling vs the single-process path, lossless
kill-a-worker recovery)."""
import os

import numpy as np
import pytest

from repro.launch import batching
from repro.launch.batching import (CTRL_DIE, CTRL_GO, CTRL_STOP, MSG_DONE,
                                   MSG_DYING, MSG_HEARTBEAT, MSG_READY,
                                   MSG_STATS, Coalescer, InMemoryTransport,
                                   WorkItem)
from repro.launch.replica import (NoSurvivorsError, ReplicaRouter,
                                  ReplicaStats, WorkerConfig, WorkerView,
                                  serve_replicas)
from repro.runtime.recovery import HeartbeatMonitor


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# Router unit tests (no transport, no clock)
# ---------------------------------------------------------------------------

def test_router_least_loaded_dispatch():
    """Items go to the replica with the fewest outstanding rows; ties
    break to fewer outstanding requests, then lowest wid."""
    r = ReplicaRouter(3)
    assert r.dispatch(WorkItem(0, 4, 0.0)) == 0      # all empty -> wid 0
    assert r.dispatch(WorkItem(1, 1, 0.0)) == 1
    assert r.dispatch(WorkItem(2, 1, 0.0)) == 2
    assert r.dispatch(WorkItem(3, 2, 0.0)) == 1      # 1 row < 2 rows < 4
    assert r.dispatch(WorkItem(4, 1, 0.0)) == 2      # now 2: ties to wid 2
    assert r.load(0) == 4 and r.load(1) == 3 and r.load(2) == 2
    assert r.dispatched == 5 and r.incomplete() == 5


def test_router_completion_accounting_and_dedup():
    """First completion wins; a second completion for the same seq is
    counted as duplicate_serves and changes nothing else."""
    r = ReplicaRouter(2)
    r.dispatch(WorkItem(0, 2, 0.0))
    r.dispatch(WorkItem(1, 1, 0.0))
    new = r.on_batch_done(0, 2, [(0, 2, 0.010)], exec_s=0.005)
    assert new == 1 and r.incomplete() == 1
    assert r.views[0].served_requests == 1 and r.views[0].served_rows == 2
    assert r.views[0].delays_s == [0.010]
    assert r.load(0) == 0                    # outstanding retired
    new = r.on_batch_done(1, 1, [(0, 2, 0.020), (1, 1, 0.001)])
    assert new == 1                          # seq 0 was a duplicate
    assert r.duplicate_serves == 1 and r.incomplete() == 0
    assert r.served == {0: 0, 1: 1}


def test_router_mark_dead_requeues_once():
    """mark_dead hands back the dead worker's outstanding items in seq
    order exactly once (idempotent), and re-dispatching them does not
    inflate the distinct-request count."""
    r = ReplicaRouter(2)
    for seq in range(4):
        r.dispatch(WorkItem(seq, 1, 0.0))
    r.on_batch_done(0, 1, [(0, 1, 0.0)])
    items = r.mark_dead(0)
    assert [i.seq for i in items] == [2]     # seq 0 served, 1/3 on wid 1
    assert r.mark_dead(0) == []              # idempotent
    assert r.deaths == 1 and r.requeued == 1
    assert r.dispatch(items[0]) == 1         # only survivor
    assert r.dispatched == 4                 # re-queue is not a new request
    assert not r.views[0].alive and r.alive_ids() == [1]


def test_router_no_survivors_raises():
    r = ReplicaRouter(1)
    r.dispatch(WorkItem(0, 1, 0.0))
    r.mark_dead(0)
    with pytest.raises(NoSurvivorsError, match="no live replica"):
        r.dispatch(WorkItem(1, 1, 0.0))
    with pytest.raises(ValueError, match=">= 1 replica"):
        ReplicaRouter(0)


def test_router_heartbeat_and_deadline_dead():
    """Heartbeats feed the monitor for live workers only; a silent
    worker crosses the deadline and shows up in deadline_dead until it
    is marked dead (then the monitor forgets it)."""
    clk = FakeClock()
    mon = HeartbeatMonitor(2, dead_after_s=1.0, clock=clk)
    r = ReplicaRouter(2, monitor=mon)
    clk.advance(0.9)
    r.on_heartbeat(0)
    clk.advance(0.2)                         # wid 1 now silent for 1.1s
    assert r.deadline_dead() == [1]
    r.mark_dead(1)
    assert r.deadline_dead() == []           # forgotten, not re-reported
    r.on_heartbeat(1)                        # late beat from a dead wid
    assert 1 not in mon.last_seen            # ignored: not alive


def test_replica_stats_pooled_percentiles_match_numpy():
    """Aggregate queue-delay percentiles pool ALL per-worker samples —
    cross-checked against numpy on the pooled vector, and distinct from
    the average of per-worker percentiles."""
    w0 = WorkerView(0, delays_s=[0.001, 0.002, 0.003, 0.100])
    w1 = WorkerView(1, delays_s=[0.004, 0.200, 0.300, 0.400, 0.500])
    rs = ReplicaStats(workers={0: w0, 1: w1}, wall_s=1.0, requeued=0,
                      duplicate_serves=0, deaths=0)
    pooled = w0.delays_s + w1.delays_s
    for q in (50, 95, 99):
        expect = float(np.percentile(pooled, q,
                                     method="inverted_cdf")) * 1e3
        assert rs.delay_ms(q) == pytest.approx(expect)
        avg = (batching.percentile(w0.delays_s, q)
               + batching.percentile(w1.delays_s, q)) / 2 * 1e3
        assert rs.delay_ms(q) != pytest.approx(avg)
    assert "pooled" in rs.describe()


# ---------------------------------------------------------------------------
# Deterministic end-to-end: fake transport + fake clock
# ---------------------------------------------------------------------------

SERVED_LOG: list = []      # every (wid, seq) any fake worker ever served


class FakeWorker:
    """Synchronous stand-in for `_worker_main`: same protocol, one
    coalescer pop per step, virtual clock, rate-limited heartbeats."""

    def __init__(self, wid, cfg, inbox, emit, clock, *, startup_s=0.1,
                 exec_s=0.001, table_misses=1, disk_hits=0):
        self.wid, self.cfg = wid, cfg
        self.inbox, self.emit, self.clock = inbox, emit, clock
        self.epoch = None
        self.co = Coalescer(cfg.max_batch, cfg.max_delay_ms / 1e3)
        self.stopping = False
        self.exec_s = exec_s
        self.last_hb = None
        self.served_rows = self.padded_rows = self.batches = 0
        emit((MSG_READY, wid, startup_s, table_misses, disk_hits))

    def on_batch(self, entries):
        SERVED_LOG.extend((self.wid, seq) for seq, _, _ in entries)

    def step(self):
        while self.inbox:
            msg = self.inbox.popleft()
            if isinstance(msg, WorkItem):
                self.co.push(msg.rows, msg.arrival_s, payload=msg)
            elif msg[0] == CTRL_GO:
                self.epoch = float(msg[1])
            elif msg[0] == CTRL_STOP:
                self.stopping = True
            elif msg[0] == CTRL_DIE:
                self.emit((MSG_DYING, self.wid, "killed"))
                return False
        if self.epoch is None:
            return True
        now = self.clock() - self.epoch
        if self.last_hb is None or now - self.last_hb >= self.cfg.heartbeat_s:
            self.last_hb = now
            self.emit((MSG_HEARTBEAT, self.wid, now))
        batch = self.co.pop(now, force=self.stopping)
        if batch:
            rows = sum(r.rows for r in batch)
            tier = batching.tier_for(
                rows, batching.batch_tiers(self.cfg.max_batch))
            entries = tuple((r.payload.seq, r.rows, now - r.arrival_s)
                            for r in batch)
            self.on_batch(entries)
            self.served_rows += rows
            self.padded_rows += tier
            self.batches += 1
            self.emit((MSG_DONE, self.wid, tier, entries, self.exec_s))
        elif self.stopping and not len(self.co):
            self.emit((MSG_STATS, self.wid, self.served_rows,
                       self.padded_rows, self.batches))
            return False
        return True


def _fake_serve(trace, n, *, worker_cls=FakeWorker, cfg=None, **kw):
    SERVED_LOG.clear()
    clk = FakeClock()
    cfg = cfg or WorkerConfig(max_batch=4, max_delay_ms=2.0,
                              heartbeat_s=0.05)
    transport = InMemoryTransport(
        lambda wid, c, inbox, emit: worker_cls(wid, c, inbox, emit, clk))
    rs = serve_replicas(trace, cfg, n, transport=transport,
                        clock=clk, sleep=clk.advance, **kw)
    return rs


def test_fake_transport_serves_everything_balanced():
    """A backlogged trace of singles drains across both workers, every
    request exactly once, with the router's least-loaded dispatch
    splitting the load evenly."""
    trace = [(0.0, 1)] * 12
    rs = _fake_serve(trace, 2)
    assert rs.request_images == 12 and rs.deaths == 0
    assert rs.duplicate_serves == 0 and rs.requeued == 0
    served = sorted(seq for _, seq in SERVED_LOG)
    assert served == list(range(12))         # exactly once, all of them
    per_worker = [rs.workers[w].served_requests for w in (0, 1)]
    assert per_worker == [6, 6]
    assert rs.workers[0].startup_s == pytest.approx(0.1)
    assert len(rs.delays_s) == 12


def test_fake_transport_timed_arrivals_advance_clock():
    """A timed trace forces the serve loop through its idle path: the
    fake clock must advance (injected sleep) until each arrival is due,
    and queue delays reflect the coalescer's max-delay wait."""
    trace = [(0.0, 1), (0.5, 2), (1.0, 1)]
    rs = _fake_serve(trace, 2)
    assert rs.request_images == 4
    assert rs.wall_s >= 1.0                  # virtual time really passed
    assert rs.duplicate_serves == 0
    # lone singles wait out the 2ms coalescing delay before launching
    assert all(0.0 <= d <= 0.1 for d in rs.delays_s)


def test_fake_transport_kill_worker_lossless():
    """THE recovery contract: a worker killed mid-backlog loses nothing
    — its outstanding requests are re-queued to the survivor and every
    request is served exactly once."""
    trace = [(0.0, 1)] * 12
    rs = _fake_serve(trace, 2, kill_worker=1, kill_after_batches=1)
    assert rs.deaths == 1 and not rs.workers[1].alive
    assert rs.requeued > 0
    assert rs.duplicate_serves == 0
    served = sorted(seq for _, seq in SERVED_LOG)
    assert served == list(range(12))         # exactly once, all of them
    assert rs.workers[1].batches >= 1        # it did work before dying
    assert rs.request_images == 12


class HangingWorker(FakeWorker):
    """wid 1 goes silent after its first batch: alive per the
    transport, but no heartbeats, no completions — the deadline must
    catch it (process-death detection alone never would)."""

    def step(self):
        if self.wid == 1 and self.batches >= 1:
            return True                      # hung: holds work forever
        return super().step()


def test_fake_transport_heartbeat_timeout_recovers_hung_worker():
    """A hung worker (process alive, no heartbeats) is declared dead at
    the monitor's deadline and its queued work re-served — the recovery
    path that process-death detection alone cannot catch."""
    trace = [(0.0, 1)] * 12
    rs = _fake_serve(trace, 2, kill_worker=None, dead_after_s=0.5)
    assert rs.deaths == 0                    # healthy baseline first
    SERVED_LOG.clear()
    rs = _fake_serve(trace, 2, worker_cls=HangingWorker, dead_after_s=0.5)
    assert rs.deaths == 1 and rs.requeued > 0
    assert rs.duplicate_serves == 0
    served = sorted(seq for _, seq in SERVED_LOG)
    assert served == list(range(12))
    hung = [w for w, v in rs.workers.items() if not v.alive]
    assert len(hung) == 1


class StillbornWorker(FakeWorker):
    """Dies during startup instead of reporting ready."""

    def __init__(self, wid, cfg, inbox, emit, clock, **kw):
        self.wid = wid
        emit((MSG_DYING, wid, "startup: boom"))

    def step(self):
        return False


def test_startup_death_raises():
    with pytest.raises(RuntimeError, match="died during startup"):
        _fake_serve([(0.0, 1)], 1, worker_cls=StillbornWorker)


class SilentWorker(FakeWorker):
    """Never reports ready at all (hung startup)."""

    def __init__(self, wid, cfg, inbox, emit, clock, **kw):
        self.wid = wid

    def step(self):
        return True


def test_ready_timeout_raises():
    with pytest.raises(RuntimeError, match="became\\s+ready|ready within"):
        _fake_serve([(0.0, 1)], 1, worker_cls=SilentWorker,
                    ready_timeout_s=1.0)


class CrashedWorker(SilentWorker):
    """Exits during startup without a word (a hard crash)."""

    def step(self):
        return False


def test_startup_crash_raises_without_waiting_out_the_timeout():
    with pytest.raises(RuntimeError, match="exited during startup"):
        _fake_serve([(0.0, 1)], 2, worker_cls=CrashedWorker,
                    ready_timeout_s=1e9)


def test_kill_only_worker_raises_no_survivors():
    # 10 singles: two full batches drain instantly, the 2-row leftover
    # keeps the worker loaded so the kill injection actually fires.
    trace = [(0.0, 1)] * 10
    with pytest.raises(NoSurvivorsError):
        _fake_serve(trace, 1, kill_worker=0, kill_after_batches=1)


def test_serve_replicas_validates_inputs():
    cfg = WorkerConfig(max_batch=4)
    with pytest.raises(ValueError, match="never split"):
        serve_replicas([(0.0, 8)], cfg, 2,
                       transport=InMemoryTransport(lambda *a: None))
    with pytest.raises(ValueError, match="kill_worker"):
        serve_replicas([(0.0, 1)], cfg, 2, kill_worker=5,
                       transport=InMemoryTransport(lambda *a: None))
    with pytest.raises(ValueError, match=">= 1 replica"):
        serve_replicas([(0.0, 1)], cfg, 0,
                       transport=InMemoryTransport(lambda *a: None))


# ---------------------------------------------------------------------------
# Real multiprocess paths (slow)
# ---------------------------------------------------------------------------

def _mp_config(cache_dir):
    return WorkerConfig(net="cnn8", array=(64, 64), grid=(2, 2), layers=4,
                        groups=(1, 2), max_batch=4, max_delay_ms=2.0,
                        warmup=1, cache_dir=str(cache_dir))


@pytest.mark.slow
def test_mp_kill_worker_lossless(tmp_path):
    """Spawned-process recovery: kill one of two real workers while it
    holds a backlog — the run still serves every request exactly once
    (zero lost, zero duplicated), survivors pick up the re-queued
    work."""
    from repro.launch.serve_cnn import poisson_arrivals
    trace = poisson_arrivals(24, 0.0, 4, seed=1)
    rs = serve_replicas(trace, _mp_config(tmp_path / "cache"), 2,
                        kill_worker=1, kill_after_batches=0)
    assert rs.deaths == 1 and not rs.workers[1].alive
    assert rs.requeued > 0
    assert rs.duplicate_serves == 0
    assert sum(v.served_requests for v in rs.workers.values()) == 24
    assert rs.request_images == sum(r for _, r in trace)
    assert rs.workers[0].served_requests + rs.workers[1].served_requests \
        == 24


@pytest.mark.slow
@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="process scale-out cannot beat one process on "
                           "a single core (workers just timeshare it)")
def test_mp_two_replicas_scale_vs_single_process():
    """ISSUE 9 acceptance: on the same backlogged trace, 2 replicas'
    aggregate effective images/s >= the single-process serve_dynamic
    baseline — measured through benchmarks/replica_bench so the test
    and the CI artifact share one code path."""
    from benchmarks import replica_bench
    rows = replica_bench.run(full=False, n_replicas=2)
    multi = next(r for r in rows if r.name.endswith("/n2"))
    kv = dict(p.split("=", 1) for p in multi.derived.split(";"))
    assert float(kv["scaling"]) >= 1.0, multi.derived
    assert int(kv["requeued"]) == 0 and int(kv["duplicate_serves"]) == 0
