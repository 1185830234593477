"""Dynamic batching (launch/batching.py): coalescer semantics under a
fake clock, the tier ladder, per-tier stats, the donation input ring,
and the arrival-driven serve loop with deterministic virtual time."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ArrayConfig, MacroGrid, map_net, memo, networks
from repro.launch.batching import (Coalescer, DynamicServeStats, InputRing,
                                   PlanLadder, TierStats, batch_tiers,
                                   percentile, tier_for)


class _FakeMesh:
    """Just enough mesh for pad_to_data_axis/data_axis_size."""

    def __init__(self, **shape):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)


def _small_net(n_layers=2, grid=MacroGrid(2, 2)):
    return map_net("cnn8", networks.cnn8()[:n_layers], ArrayConfig(64, 64),
                   "Tetris-SDK", grid)


# ---------------------------------------------------------------------------
# Coalescer (fake clock: explicit `now` everywhere)
# ---------------------------------------------------------------------------

def test_coalescer_max_batch_trigger():
    """Reaching max_batch rows makes the queue ready immediately — no
    delay has to expire."""
    co = Coalescer(max_batch=4, max_delay_s=10.0)
    co.push(2, now=0.0)
    co.push(1, now=0.0)
    assert len(co) == 3 and not co.ready(0.0)
    co.push(1, now=0.0)
    assert co.ready(0.0)
    batch = co.pop(0.0)
    assert [r.rows for r in batch] == [2, 1, 1]
    assert len(co) == 0


def test_coalescer_max_delay_expiry():
    """A lone small request is served once the OLDEST arrival is
    max_delay old, not before."""
    co = Coalescer(max_batch=8, max_delay_s=0.005)
    co.push(1, now=1.000)
    assert co.next_deadline() == pytest.approx(1.005)
    assert not co.ready(1.0049) and co.pop(1.0049) == []
    assert co.ready(1.005)
    co.push(2, now=1.005)             # younger request rides along
    batch = co.pop(1.005)
    assert [r.rows for r in batch] == [1, 2]


def test_coalescer_never_splits_requests():
    """Requests are whole units: the drain stops before overflowing
    max_batch, and an oversized request is refused at push."""
    co = Coalescer(max_batch=4, max_delay_s=0.0)
    co.push(3, now=0.0)
    co.push(2, now=0.0)               # 3 + 2 > 4: must wait its turn
    batch = co.pop(0.0)
    assert [r.rows for r in batch] == [3]
    assert len(co) == 2
    assert [r.rows for r in co.pop(0.0)] == [2]
    with pytest.raises(ValueError, match="never split"):
        co.push(5, now=0.0)
    with pytest.raises(ValueError, match=">= 1 row"):
        co.push(0, now=0.0)


def test_coalescer_empty_queue_drain():
    """An empty queue drains to [] — force included — and has no
    deadline; pop(force=True) ignores an unexpired delay otherwise."""
    co = Coalescer(max_batch=4, max_delay_s=5.0)
    assert co.pop(0.0) == [] and co.pop(0.0, force=True) == []
    assert co.next_deadline() is None
    co.push(1, now=0.0)
    assert co.pop(0.001) == []            # delay not expired
    assert [r.rows for r in co.pop(0.001, force=True)] == [1]


def test_coalescer_validates_config():
    with pytest.raises(ValueError, match="max_batch"):
        Coalescer(0, 1.0)
    with pytest.raises(ValueError, match="max_delay_s"):
        Coalescer(1, -0.1)


def test_coalescer_payload_round_trip():
    co = Coalescer(max_batch=2, max_delay_s=0.0)
    co.push(1, now=0.0, payload="imgs")
    assert co.pop(0.0)[0].payload == "imgs"


# ---------------------------------------------------------------------------
# Tier ladder
# ---------------------------------------------------------------------------

def test_batch_tiers_powers_of_two():
    assert batch_tiers(1) == (1,)
    assert batch_tiers(8) == (1, 2, 4, 8)
    assert batch_tiers(6) == (1, 2, 4, 6)    # top tier covers max_batch
    with pytest.raises(ValueError, match="max_batch"):
        batch_tiers(0)


def test_batch_tiers_pad_to_mesh_data_axis():
    """Every tier is a multiple of the shared serving mesh's data axis
    (pad_to_data_axis), deduplicated ascending."""
    mesh = _FakeMesh(data=2, row=2, col=2)
    assert batch_tiers(8, mesh) == (2, 4, 8)
    assert batch_tiers(6, mesh) == (2, 4, 6)
    assert batch_tiers(3, mesh) == (2, 4)    # 3 pads to 4 on data=2


def test_tier_for_selects_smallest_fit():
    tiers = (1, 2, 4, 8)
    assert [tier_for(r, tiers) for r in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    with pytest.raises(ValueError, match="exceed"):
        tier_for(9, tiers)


def test_plan_ladder_shares_mesh_and_compiles_each_tier_once():
    """Each tier compiles exactly once per process (memo.cached_plan);
    rebuilding the ladder is pure cache hits — the compile counters in
    exec/plan.py are the evidence."""
    from repro.exec import compile_counts
    memo.clear()
    net = _small_net()
    ladder = PlanLadder(net, (1, 2, 4))
    assert ladder.tiers == (1, 2, 4) and ladder.max_batch == 4
    for t in ladder.tiers:
        assert ladder.plans[t].batch == t
    counts = compile_counts(net=net)
    assert len(counts) == 3 and set(counts.values()) == {1}
    again = PlanLadder(net, (1, 2, 4))
    assert compile_counts(net=net) == counts      # no recompiles
    assert all(again.plans[t] is ladder.plans[t] for t in ladder.tiers)
    t, plan = ladder.plan_for(3)
    assert t == 4 and plan.batch == 4
    with pytest.raises(ValueError, match="at least one tier"):
        PlanLadder(net, ())
    with pytest.raises(ValueError, match="data axis"):
        PlanLadder(net, (3,), mesh=_FakeMesh(data=2, row=1, col=1))


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 95) == 5.0
    assert percentile(xs, 100) == 5.0
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError, match="empty"):
        percentile([], 50)
    with pytest.raises(ValueError, match="q must be"):
        percentile(xs, 101)


def test_percentile_matches_numpy_inverted_cdf():
    """The pure-Python nearest-rank percentile is exactly numpy's
    ``method="inverted_cdf"`` — random inputs across sizes, the full
    q sweep including the q=0 / q=100 / singleton edges."""
    rng = np.random.RandomState(11)
    qs = [0, 1, 25, 50, 75, 90, 95, 99, 100]
    for n in [1, 2, 3, 5, 8, 17, 100]:
        xs = rng.randn(n).tolist()
        for q in qs + [float(rng.uniform(0, 100)) for _ in range(5)]:
            expect = float(np.percentile(xs, q, method="inverted_cdf"))
            assert percentile(xs, q) == expect, (n, q)
    assert percentile([4.0], 0) == 4.0 == percentile([4.0], 100)
    xs = [3.0, 1.0, 2.0]
    assert percentile(xs, 0) == float(
        np.percentile(xs, 0, method="inverted_cdf")) == 1.0
    assert percentile(xs, 100) == float(
        np.percentile(xs, 100, method="inverted_cdf")) == 3.0


def test_tier_stats_effective_vs_padded_and_delays():
    ts = TierStats(plan_batch=4)
    co = Coalescer(4, 0.0)
    co.push(2, now=0.0)
    co.push(1, now=0.5)
    ts.record(co.pop(1.0, force=True), launch_s=1.0, exec_s=0.25)
    assert ts.batches == 1 and ts.request_images == 3
    assert ts.padded_images == 4 and ts.exec_s == 0.25
    assert ts.delays_s == [1.0, 0.5]
    assert ts.delay_ms(50) == pytest.approx(500.0)
    s = DynamicServeStats(tiers={4: ts}, request_images=3, padded_images=4,
                          wall_s=0.5, warmup_steps=2)
    assert s.images_per_s == pytest.approx(6.0)
    assert s.padded_images_per_s == pytest.approx(8.0)
    assert "tier 4" in s.describe() and "warmup_steps=2" in s.describe()


# ---------------------------------------------------------------------------
# Input ring (donation)
# ---------------------------------------------------------------------------

def test_input_ring_without_donation_reuses_one_buffer():
    x = np.ones((2, 3), np.float32)
    ring = InputRing(x, donate=False)
    a, b = ring.next(), ring.next()
    assert a is b                        # no per-step upload
    assert bool(jnp.all(a == 1))


def test_input_ring_with_donation_hands_fresh_buffers():
    """Under donation every step must receive a buffer the program may
    consume: successive next() calls return distinct live buffers with
    identical contents."""
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    ring = InputRing(x, donate=True)
    a, b, c = ring.next(), ring.next(), ring.next()
    assert a is not b and b is not c
    for buf in (a, b, c):
        np.testing.assert_array_equal(np.asarray(buf), x)


# ---------------------------------------------------------------------------
# serve_dynamic under a virtual clock
# ---------------------------------------------------------------------------

class _VirtualClock:
    """Deterministic time for the serve loop: only sleep() advances."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, dt):
        assert dt > 0
        self.sleeps.append(dt)
        self.t += dt


def test_serve_dynamic_virtual_time_coalescing():
    """Deterministic end-to-end: two early arrivals coalesce at the
    max-delay deadline, the straggler is force-drained once no future
    arrival can grow the batch, delays are measured from scheduled
    arrival to batch launch."""
    from repro.launch import serve_cnn
    net = _small_net()
    clk = _VirtualClock()
    reqs = [(0.0, 1), (0.001, 2), (0.010, 3)]
    s = serve_cnn.serve_dynamic(
        net, reqs, max_batch=4, max_delay_ms=5.0, warmup=1,
        clock=clk, sleep=clk.sleep)
    assert s.warmup_steps == len(batch_tiers(4))     # once per tier
    assert s.request_images == 6
    t4 = s.tiers[4]
    assert t4.batches == 2 and t4.request_images == 6
    assert t4.padded_images == 8                     # 2 batches of tier 4
    assert s.tiers[1].batches == s.tiers[2].batches == 0
    # batch 1: requests at 0.000 + 0.001 launched at the 5ms deadline
    # batch 2: request at 0.010 force-drained on arrival (queue empty)
    assert sorted(t4.delays_s) == pytest.approx([0.0, 0.004, 0.005])
    assert s.images_per_s > 0 and s.padded_images_per_s > 0


def test_serve_dynamic_honors_warmup_zero():
    from repro.launch import serve_cnn
    net = _small_net()
    clk = _VirtualClock()
    s = serve_cnn.serve_dynamic(net, [(0.0, 2)], max_batch=2,
                                max_delay_ms=0.0, warmup=0,
                                clock=clk, sleep=clk.sleep)
    assert s.warmup_steps == 0
    assert s.request_images == 2
    with pytest.raises(ValueError, match="warmup"):
        serve_cnn.serve_dynamic(net, [(0.0, 1)], max_batch=2,
                                max_delay_ms=1.0, warmup=-1)
    with pytest.raises(ValueError, match="never split"):
        serve_cnn.serve_dynamic(net, [(0.0, 5)], max_batch=2,
                                max_delay_ms=1.0)
    with pytest.raises(ValueError, match="do not cover"):
        # explicit tiers must reach max_batch: a full coalesced batch
        # would otherwise have no plan to run on
        serve_cnn.serve_dynamic(net, [(0.0, 1)], max_batch=4,
                                max_delay_ms=1.0, tiers=(1, 2))


def test_poisson_arrivals_schedule():
    from repro.launch.serve_cnn import poisson_arrivals
    reqs = poisson_arrivals(16, rate_per_s=100.0, max_rows=3, seed=1)
    times = [t for t, _ in reqs]
    rows = [r for _, r in reqs]
    assert len(reqs) == 16 and times[0] == 0.0
    assert times == sorted(times)
    assert all(1 <= r <= 3 for r in rows) and len(set(rows)) > 1
    backlog = poisson_arrivals(4, rate_per_s=0.0, max_rows=2, seed=0)
    assert all(t == 0.0 for t, _ in backlog)
    with pytest.raises(ValueError, match="request"):
        poisson_arrivals(0, 1.0, 1)


# ---------------------------------------------------------------------------
# Donation gating (exec/run.py satellite)
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, platform):
        self.platform = platform


class _PlatformMesh:
    def __init__(self, *platforms):
        self.devices = np.array([_Dev(p) for p in platforms])


def test_donation_gates_on_mesh_platform_not_default_backend():
    """The plan's mesh may live on a different platform than
    jax.default_backend(): donation keys on the mesh's devices."""
    from repro.exec import donation_supported
    from repro.launch.mesh import mesh_platform
    assert mesh_platform(None) is None
    assert mesh_platform(_PlatformMesh("cpu", "cpu")) == "cpu"
    assert mesh_platform(_PlatformMesh("tpu", "tpu")) == "tpu"
    assert mesh_platform(_PlatformMesh("tpu", "cpu")) == "mixed"
    assert not donation_supported(_PlatformMesh("cpu", "cpu"))
    assert donation_supported(_PlatformMesh("tpu", "tpu"))
    assert donation_supported(_PlatformMesh("gpu", "gpu"))
    assert not donation_supported(_PlatformMesh("tpu", "cpu"))  # mixed
    # no mesh: fall back to the default backend (CPU in CI)
    assert donation_supported(None) == (jax.default_backend() != "cpu")


def test_execute_plan_donate_falls_back_cleanly_on_cpu():
    """donate=True on a CPU mesh/backend must not donate (XLA has no
    CPU donation): the input stays live and results match exactly."""
    from repro.cnn.mapped_net import zero_pruned_kernels
    from repro.exec import compile_plan, execute_plan
    net = _small_net()
    rng = np.random.RandomState(3)
    ks = zero_pruned_kernels(net, [
        jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                              m.layer.ic // m.group, m.layer.oc) * 0.1,
                    jnp.float32) for m in net.layers])
    first = net.layers[0].layer
    x = jnp.asarray(rng.randn(2, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    plan = compile_plan(net, executor_policy="mapped")
    y_plain = execute_plan(plan, ks, x)
    y_donate = execute_plan(plan, ks, x, donate=True)
    assert bool(jnp.all(y_plain == y_donate))
    assert bool(jnp.all(x == x + 0))     # buffer not consumed on CPU


# ---------------------------------------------------------------------------
# Adaptive delay (load-proportional coalescing) + pooled percentiles
# ---------------------------------------------------------------------------

def test_adaptive_delay_scales_with_queue_depth():
    """The policy interpolates linearly: empty queue waits the full
    cap, a queue at/above ref_rows drains immediately."""
    from repro.launch.batching import AdaptiveDelay
    pol = AdaptiveDelay(max_delay_s=0.010, ref_rows=8)
    assert pol(0) == pytest.approx(0.010)
    assert pol(4) == pytest.approx(0.005)
    assert pol(8) == 0.0
    assert pol(100) == 0.0               # clamped, never negative
    with pytest.raises(ValueError, match="ref_rows"):
        AdaptiveDelay(0.01, 0)
    with pytest.raises(ValueError, match="max_delay_s"):
        AdaptiveDelay(-1.0, 4)


def test_coalescer_adaptive_delay_moves_deadline_earlier():
    """With a delay policy the deadline is re-derived from LIVE queue
    depth on every call: the same oldest arrival expires sooner as the
    backlog deepens — and a deep backlog becomes ready immediately."""
    from repro.launch.batching import AdaptiveDelay
    co = Coalescer(max_batch=8, max_delay_s=0.010,
                   delay_policy=AdaptiveDelay(0.010, ref_rows=8))
    co.push(1, now=0.0)
    # 1 queued row of 8: deadline ~ 0 + 10ms * (1 - 1/8)
    assert co.next_deadline() == pytest.approx(0.010 * 7 / 8)
    co.push(3, now=0.001)                # depth 4 -> delay halves
    assert co.next_deadline() == pytest.approx(0.010 * 4 / 8)
    assert not co.ready(0.004)
    assert co.ready(0.005)
    co2 = Coalescer(max_batch=8, max_delay_s=0.010,
                    delay_policy=AdaptiveDelay(0.010, ref_rows=4))
    co2.push(2, now=0.0)
    co2.push(2, now=0.0)                 # depth == ref_rows: drain now
    assert co2.effective_delay_s() == 0.0
    assert co2.ready(0.0)
    assert [r.rows for r in co2.pop(0.0)] == [2, 2]


def test_coalescer_delay_policy_clamped_by_max_delay():
    """A policy may never extend the wait beyond the configured cap
    (or below zero) — the cap is the latency contract."""
    co = Coalescer(max_batch=8, max_delay_s=0.010,
                   delay_policy=lambda rows: 99.0)
    co.push(1, now=0.0)
    assert co.effective_delay_s() == pytest.approx(0.010)
    assert co.next_deadline() == pytest.approx(0.010)
    co_neg = Coalescer(max_batch=8, max_delay_s=0.010,
                       delay_policy=lambda rows: -5.0)
    co_neg.push(1, now=0.0)
    assert co_neg.effective_delay_s() == 0.0
    assert co_neg.ready(0.0)


def test_serve_dynamic_adaptive_delay_virtual_time():
    """End-to-end through serve_dynamic on a virtual clock: with load
    queued, the adaptive policy launches earlier than the fixed one
    (50ms * (1 - 3/8) vs the full 50ms cap), so the pooled queue-delay
    p50 shrinks; the trailing arrival force-drains either way."""
    from repro.launch.serve_cnn import serve_dynamic

    def virtual(adaptive):
        t = [0.0]

        def clock():
            return t[0]

        def sleep(dt):
            t[0] += dt
        net = _small_net()
        # 3 early singles never fill max_batch=8: the fixed policy
        # holds them until the last arrival force-drains the queue at
        # t=45ms, the adaptive one serves them at 50ms * (1 - 3/8)
        reqs = [(0.0, 1)] * 3 + [(0.045, 1)]
        s = serve_dynamic(net, reqs, max_batch=8, max_delay_ms=50.0,
                          mesh=None, warmup=1, adaptive_delay=adaptive,
                          clock=clock, sleep=sleep)
        assert s.request_images == 4
        return s

    fast, slow = virtual(True), virtual(False)
    assert fast.delay_ms(50) == pytest.approx(50.0 * (1 - 3 / 8))
    assert slow.delay_ms(50) == pytest.approx(45.0)
    assert fast.delay_ms(50) < slow.delay_ms(50)


def test_dynamic_stats_pooled_percentiles_match_numpy():
    """Aggregate queue-delay percentiles pool ALL per-tier samples and
    match numpy on the pooled vector — never the average of per-tier
    percentiles, which is a different (wrong) number here."""
    t1 = TierStats(plan_batch=1)
    t1.delays_s = [0.001, 0.002, 0.003, 0.100]
    t4 = TierStats(plan_batch=4)
    t4.delays_s = [0.004, 0.005, 0.200, 0.300, 0.400]
    s = DynamicServeStats(tiers={1: t1, 4: t4}, request_images=9,
                          padded_images=17, wall_s=1.0, warmup_steps=0)
    pooled = t1.delays_s + t4.delays_s
    for q in (50, 95, 99):
        expect = float(np.percentile(pooled, q,
                                     method="inverted_cdf")) * 1e3
        assert s.delay_ms(q) == pytest.approx(expect)
        avg_of_percentiles = (t1.delay_ms(q) + t4.delay_ms(q)) / 2
        assert s.delay_ms(q) != pytest.approx(avg_of_percentiles)
    assert "pooled" in s.describe()


def test_fleet_stats_pooled_percentiles_match_numpy():
    """FleetStats.delay_ms pools per-model samples the same way."""
    from repro.launch.fleet import FleetStats, ModelStats
    ma = ModelStats(name="a", slo_ms=None)
    ma.tiers[1] = TierStats(plan_batch=1)
    ma.tiers[1].delays_s = [0.010, 0.020, 0.030]
    mb = ModelStats(name="b", slo_ms=None)
    mb.tiers[2] = TierStats(plan_batch=2)
    mb.tiers[2].delays_s = [0.001, 0.002, 0.500, 0.600]
    fs = FleetStats(models={"a": ma, "b": mb}, wall_s=1.0,
                    warmup_steps=0)
    pooled = ma.tiers[1].delays_s + mb.tiers[2].delays_s
    for q in (50, 95, 99):
        expect = float(np.percentile(pooled, q,
                                     method="inverted_cdf")) * 1e3
        assert fs.delay_ms(q) == pytest.approx(expect)
    assert "pooled" in fs.describe()
