"""Fleet scheduler (launch/fleet.py): property-tested deterministic
core (conservation, per-model FIFO, no-split, deadline bound, tier
monotonicity), the determinism regression (bit-identical launch
schedules across runs and a pickle round-trip of the config), plan-
constant sharing (once per network, not per tier; bit-identical
outputs), and the shared-fleet vs dedicated-slice acceptance row.

The hypothesis suite is guarded with a soft import (NOT a module-level
importorskip: the non-property tests here must run without hypothesis);
a seeded-random fallback drives the same invariant checkers over 100
traces either way.
"""
import pickle
import random

import pytest

from repro.core import ArrayConfig, MacroGrid, map_net, memo, networks
from repro.launch import batching
from repro.launch.fleet import (FleetConfig, FleetScheduler, LaunchRecord,
                                ModelSpec, chainable_prefix,
                                mixed_poisson_trace, run_fleet)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


class _VClock:
    """Deterministic time for the fleet loop: only sleep() advances."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        assert dt > 0
        self.t += dt


class _FakeMesh:
    def __init__(self, **shape):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)


def _small_net(n_layers=2, grid=MacroGrid(2, 2)):
    return map_net("cnn8", networks.cnn8()[:n_layers], ArrayConfig(64, 64),
                   "Tetris-SDK", grid)


def _replay(cfg, trace):
    clk = _VClock()
    return run_fleet(FleetScheduler(cfg), trace, clock=clk,
                     sleep=clk.sleep)


# ---------------------------------------------------------------------------
# Shared invariant checkers (hypothesis AND the seeded fallback drive
# these — one definition of correctness)
# ---------------------------------------------------------------------------

def _check_invariants(cfg, trace, records):
    pushed = {}
    for t, m, r in trace:
        pushed.setdefault(m, []).append((t, r))
    served = {}
    for rec in records:
        spec = cfg.spec(rec.model)
        # no-split: whole requests only, each within the model's cap
        assert len(rec.rows) == len(rec.arrivals_s) >= 1
        assert all(1 <= r <= spec.max_batch for r in rec.rows)
        total = sum(rec.rows)
        assert total <= spec.max_batch
        # tier stamp = smallest ladder rung that fits the drained rows
        tiers = batching.batch_tiers(spec.max_batch)
        assert rec.tier == batching.tier_for(total, tiers)
        # deadline bound: under pure replay (virtual time, instant
        # execution) nothing launches later than max_delay past arrival
        for a in rec.arrivals_s:
            assert rec.launch_s <= a + spec.max_delay_s + 1e-9
        served.setdefault(rec.model, []).extend(
            zip(rec.arrivals_s, rec.rows))
    # conservation + per-model FIFO: every pushed request is served
    # exactly once, in arrival order (stable on tied timestamps)
    for m, events in pushed.items():
        assert served.pop(m, []) == sorted(events, key=lambda e: e[0])
    assert not served                     # nothing served but not pushed


def _random_case(rng: random.Random):
    n_models = rng.randint(1, 3)
    specs = tuple(
        ModelSpec(name=f"m{i}",
                  max_batch=rng.randint(1, 8),
                  max_delay_s=rng.choice([0.0, 0.001, 0.005, 0.02]),
                  weight=rng.choice([0.5, 1.0, 2.0]))
        for i in range(n_models))
    cfg = FleetConfig(models=specs)
    t = 0.0
    trace = []
    for _ in range(rng.randint(1, 30)):
        t += rng.choice([0.0, 0.0005, 0.002, 0.01])
        spec = specs[rng.randrange(n_models)]
        trace.append((t, spec.name, rng.randint(1, spec.max_batch)))
    return cfg, tuple(trace)


def test_fleet_invariants_seeded_fallback():
    """100 seeded-random traces through the shared checkers — the same
    coverage shape as the hypothesis suite, always runnable."""
    rng = random.Random(7)
    for _ in range(100):
        cfg, trace = _random_case(rng)
        _check_invariants(cfg, trace, _replay(cfg, trace))


if HAVE_HYPOTHESIS:
    @st.composite
    def fleet_cases(draw):
        n_models = draw(st.integers(1, 3))
        specs = tuple(
            ModelSpec(name=f"m{i}",
                      max_batch=draw(st.integers(1, 8)),
                      max_delay_s=draw(st.floats(
                          0, 0.02, allow_nan=False, allow_infinity=False)),
                      weight=draw(st.floats(
                          0.1, 4.0, allow_nan=False, allow_infinity=False)))
            for i in range(n_models))
        cfg = FleetConfig(models=specs)
        n = draw(st.integers(1, 30))
        gaps = draw(st.lists(
            st.floats(0, 0.01, allow_nan=False, allow_infinity=False),
            min_size=n, max_size=n))
        picks = draw(st.lists(st.integers(0, n_models - 1),
                              min_size=n, max_size=n))
        trace, t = [], 0.0
        for gap, mi in zip(gaps, picks):
            t += gap
            spec = specs[mi]
            trace.append((t, spec.name,
                          draw(st.integers(1, spec.max_batch))))
        return cfg, tuple(trace)

    @settings(max_examples=100, deadline=None)
    @given(case=fleet_cases())
    def test_fleet_conservation_fifo_nosplit_deadline(case):
        """For arbitrary tagged arrival sequences: every pushed row is
        served exactly once (conservation at forced flush), one model's
        requests never reorder (FIFO), requests stay whole (no-split),
        and nothing launches later than its model's max-delay past
        arrival under pure replay."""
        cfg, trace = case
        _check_invariants(cfg, trace, _replay(cfg, trace))

    @settings(max_examples=100, deadline=None)
    @given(case=fleet_cases())
    def test_fleet_schedule_deterministic(case):
        cfg, trace = case
        assert _replay(cfg, trace) == _replay(cfg, trace)

    @settings(max_examples=100, deadline=None)
    @given(max_batch=st.integers(1, 64), data=st.integers(1, 8),
           rows=st.integers(1, 64))
    def test_batch_tiers_and_tier_for_monotone_under_mesh(
            max_batch, data, rows):
        """Ladder invariants under a mesh: tiers ascend, every tier is
        a multiple of the data axis, the top covers max_batch, and
        tier_for is monotone in rows (more rows never select a smaller
        tier)."""
        mesh = _FakeMesh(data=data, row=2, col=2)
        tiers = batching.batch_tiers(max_batch, mesh)
        assert list(tiers) == sorted(set(tiers))
        assert all(t % data == 0 for t in tiers)
        assert tiers[-1] >= max_batch
        if rows <= tiers[-1]:
            t = batching.tier_for(rows, tiers)
            assert t >= rows
            if rows > 1:
                assert batching.tier_for(rows - 1, tiers) <= t
        else:
            with pytest.raises(ValueError, match="exceed"):
                batching.tier_for(rows, tiers)


# ---------------------------------------------------------------------------
# Determinism regression (ISSUE 7 satellite 2)
# ---------------------------------------------------------------------------

def test_fleet_determinism_across_runs_and_pickle():
    """The invariant documented in launch/fleet.py's docstring: same
    config + trace + fake clock => bit-identical LaunchRecord schedule,
    across independent runs AND across a pickle round-trip of the
    scheduler config."""
    cfg = FleetConfig(models=(
        ModelSpec("a", max_batch=8, max_delay_s=0.002, weight=1.0),
        ModelSpec("b", max_batch=4, max_delay_s=0.001, weight=2.0),
        ModelSpec("c", max_batch=2, max_delay_s=0.0, weight=0.5)))
    trace = mixed_poisson_trace(["a", "b", "c"], 60, 700.0,
                                {"a": 4, "b": 3, "c": 2}, seed=11)
    r1, r2 = _replay(cfg, trace), _replay(cfg, trace)
    assert r1 == r2 and len(r1) > 0
    assert all(isinstance(r, LaunchRecord) for r in r1)
    cfg2 = pickle.loads(pickle.dumps(cfg))
    assert cfg2 == cfg
    assert _replay(cfg2, trace) == r1


# ---------------------------------------------------------------------------
# Drain-policy unit cases (deadline override / weighted fair / ties)
# ---------------------------------------------------------------------------

def test_pop_deadline_override_beats_weighted_fair():
    """An expired model drains first even when another model has far
    more weighted backlog; among expired models the nearest (most
    overdue) deadline wins."""
    cfg = FleetConfig(models=(
        ModelSpec("big", max_batch=8, max_delay_s=1.0, weight=10.0),
        ModelSpec("late", max_batch=4, max_delay_s=0.001),
        ModelSpec("later", max_batch=4, max_delay_s=0.002)))
    s = FleetScheduler(cfg)
    s.push("big", 6, now=0.0)            # huge weighted backlog
    s.push("late", 1, now=0.0)           # expires at 1ms
    s.push("later", 1, now=0.0)          # expires at 2ms
    assert s.next_deadline() == pytest.approx(0.001)
    launch = s.pop(now=0.005)            # both small models overdue
    assert launch.model == "late"
    assert s.pop(now=0.005).model == "later"
    assert s.pop(now=0.005) is None      # big: not ready, not forced
    assert len(s) == 6


def test_pop_weighted_fair_and_config_order_tiebreak():
    """Among ready-by-fill models the largest queued_rows x weight
    drains; exact ties resolve to the earliest model in the config."""
    cfg = FleetConfig(models=(
        ModelSpec("a", max_batch=2, max_delay_s=9.0, weight=1.0),
        ModelSpec("b", max_batch=2, max_delay_s=9.0, weight=3.0),
        ModelSpec("c", max_batch=2, max_delay_s=9.0, weight=1.0)))
    s = FleetScheduler(cfg)
    for m in ("a", "b", "c"):
        s.push(m, 2, now=0.0)            # all ready via max-batch
    assert s.pop(now=0.0).model == "b"   # 2x3 beats 2x1
    assert s.pop(now=0.0).model == "a"   # tie with c -> config order
    assert s.pop(now=0.0).model == "c"
    assert s.pop(now=0.0) is None and len(s) == 0


def test_pop_forced_flush_drains_in_deadline_order():
    cfg = FleetConfig(models=(
        ModelSpec("a", max_batch=4, max_delay_s=5.0),
        ModelSpec("b", max_batch=4, max_delay_s=5.0)))
    s = FleetScheduler(cfg)
    s.push("b", 1, now=0.0)              # oldest obligation
    s.push("a", 1, now=0.1)
    assert s.pop(now=0.2) is None        # neither expired nor full
    assert s.pop(now=0.2, force=True).model == "b"
    assert s.pop(now=0.2, force=True).model == "a"


def test_scheduler_validates_and_launch_metadata():
    cfg = FleetConfig(models=(ModelSpec("a", max_batch=4,
                                        max_delay_s=0.0),))
    s = FleetScheduler(cfg, mesh=_FakeMesh(data=2, row=1, col=1))
    assert s.tiers["a"] == (2, 4)        # padded to the data axis
    with pytest.raises(KeyError, match="not in fleet"):
        s.push("nope", 1, now=0.0)
    s.push("a", 3, now=0.0)
    launch = s.pop(now=0.0)
    assert (launch.model, launch.tier, launch.rows) == ("a", 4, 3)
    assert s.queued_rows("a") == 0
    with pytest.raises(ValueError, match="duplicate"):
        FleetConfig(models=(ModelSpec("x", 1, 0.0),
                            ModelSpec("x", 1, 0.0)))
    with pytest.raises(ValueError, match="at least one"):
        FleetConfig(models=())
    with pytest.raises(ValueError, match="weight"):
        ModelSpec("x", 1, 0.0, weight=0.0)
    with pytest.raises(ValueError, match="do not cover"):
        FleetScheduler(cfg, tiers={"a": (1, 2)})


def test_run_fleet_validates_trace_upfront():
    cfg = FleetConfig(models=(ModelSpec("a", max_batch=2,
                                        max_delay_s=0.0),))
    clk = _VClock()
    with pytest.raises(ValueError, match="never split"):
        run_fleet(FleetScheduler(cfg), [(0.0, "a", 3)], clock=clk,
                  sleep=clk.sleep)
    with pytest.raises(KeyError, match="not in fleet"):
        run_fleet(FleetScheduler(cfg), [(0.0, "zz", 1)], clock=clk,
                  sleep=clk.sleep)


def test_mixed_poisson_trace_shape_and_chainable_prefix():
    trace = mixed_poisson_trace(["a", "b"], 32, 200.0, {"a": 3, "b": 1},
                                seed=2, weights=[3.0, 1.0])
    assert len(trace) == 32 and trace[0][0] == 0.0
    times = [t for t, _, _ in trace]
    assert times == sorted(times)
    by = {"a": 0, "b": 0}
    for _, m, r in trace:
        by[m] += 1
        assert 1 <= r <= {"a": 3, "b": 1}[m]
    assert by["a"] > by["b"]             # 3:1 traffic weights
    backlog = mixed_poisson_trace(["a"], 4, 0.0, 2, seed=0)
    assert all(t == 0.0 for t, _, _ in backlog)
    # inception is a layer SET (two disjoint blocks): the fleet serves
    # its longest chainable prefix; cnn8 chains end to end and passes
    # through unchanged
    incep = map_net("inception", networks.inception(),
                    ArrayConfig(64, 64), "Tetris-SDK", MacroGrid(1, 1))
    pre = chainable_prefix(incep)
    assert 1 <= len(pre.layers) < len(incep.layers)
    cnn = _small_net()
    assert chainable_prefix(cnn) is cnn


# ---------------------------------------------------------------------------
# Plan-constant sharing (ISSUE 7 satellite 3)
# ---------------------------------------------------------------------------

def test_constants_materialize_once_per_network_not_per_tier():
    """The prepared handle comes out of memo.cached_constants under the
    kernels' ids: every tier of the ladder gets the SAME PlanConstants
    object, the per-key counters show ONE materialization for the
    network, and outputs with the handle are bit-identical to the
    in-trace build."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.exec import (compile_plan, constant_counts, execute_plan,
                            fixed_constants)
    from repro.launch.serve_cnn import _serving_kernels
    memo.clear()
    net = _small_net()
    ladder = batching.PlanLadder(net, (1, 2))
    rng, ks = _serving_kernels(net, 0)
    c1 = fixed_constants(ladder.plans[1], ks)
    c2 = fixed_constants(ladder.plans[2], ks)
    assert c1 is c2                      # one handle across tiers
    counts = constant_counts(net=net)
    assert len(counts) == 1 and list(counts.values()) == [1]
    assert memo.stats["const_misses"] == 1
    assert memo.stats["const_hits"] == 1
    first = net.layers[0].layer
    for t in ladder.tiers:
        x = jnp.asarray(rng.randn(t, first.ic, first.i_h, first.i_w),
                        jnp.float32)
        # through jit the kernels are tracers: the in-trace build
        y_off = jax.jit(functools.partial(execute_plan,
                                          ladder.plans[t]))(ks, x)
        y_on = execute_plan(ladder.plans[t], ks, x)
        assert bool(jnp.all(y_on == y_off))
    assert fixed_constants(ladder.plans[1], ks) is c1
    assert list(constant_counts(net=net).values()) == [1]
    # other kernel objects materialize separately; numpy kernels never
    # touch the cache/counters
    ks2 = [k + 0 for k in ks]
    c3 = fixed_constants(ladder.plans[1], ks2)
    assert c3 is not c1
    assert sum(constant_counts(net=net).values()) == 2
    assert fixed_constants(ladder.plans[1],
                           [np.asarray(k) for k in ks]) is None
    assert sum(constant_counts(net=net).values()) == 2
    # the handle is keyed on the network mapping: another network's
    # plan gets its own
    other = _small_net(3)
    plan_o = compile_plan(other, executor_policy="mapped", batch=1)
    ks_o = _serving_kernels(other, 0)[1]
    c_o = fixed_constants(plan_o, ks_o)
    assert c_o is not c1 and len(c_o.weights) == len(other.layers)
    assert len(constant_counts(net=other)) == 1


def test_fleet_schedule_identical_with_and_without_sharing():
    """Constant sharing is a pure execution-side optimization: under the
    virtual clock the drain/launch schedule and per-model stats are
    identical whether the tiers read prepared blocks or, with the memo
    layer off, build them in the program."""
    from repro.exec import prepared_stats
    from repro.launch.fleet import serve_fleet
    net = _small_net()
    maps = {"a": net, "b": _small_net(3)}
    cfg = FleetConfig(models=(
        ModelSpec("a", max_batch=2, max_delay_s=0.001, slo_ms=100.0),
        ModelSpec("b", max_batch=2, max_delay_s=0.001, slo_ms=100.0)))
    trace = mixed_poisson_trace(["a", "b"], 8, 300.0, 2, seed=5)

    def run():
        clk = _VClock()
        return serve_fleet(maps, cfg, trace, warmup=1, clock=clk,
                           sleep=clk.sleep)

    memo.clear()
    s_on, r_on = run()
    on = prepared_stats()
    with memo.disabled():
        s_off, r_off = run()
    off = prepared_stats()
    assert r_on == r_off
    assert on["prepared_calls"] > 0 and on["in_trace_calls"] == 0
    assert on["materializations"] == 2          # one per network
    assert off["prepared_calls"] == on["prepared_calls"]
    assert off["in_trace_calls"] == on["prepared_calls"]
    for m in ("a", "b"):
        assert (s_on.models[m].request_images
                == s_off.models[m].request_images)
        assert s_on.models[m].slo_attainment == 1.0
    assert s_on.request_images == sum(r for _, _, r in trace)


def test_constants_shared_across_tiers_forced_multi_device():
    """Forced-8-device case (pattern from tests/test_serve_cnn.py):
    with a data=2 serving mesh the fixed kernels keep the in-trace
    build, which places the blocks where the mesh shards them: nothing
    is materialized on any tier, outputs are bit-identical to the
    traced call, and a one-device run of the same tiers shares one
    handle across them."""
    import os
    import subprocess
    import sys
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ArrayConfig, MacroGrid, map_net, networks
from repro.cnn.mapped_net import zero_pruned_kernels
from repro.exec import (compile_plan, constant_counts, execute_plan,
                        fixed_constants, prepared_stats)
from repro.launch.mesh import serving_mesh_for
assert len(jax.devices()) == 8
net = map_net("cnn8", networks.cnn8()[:3], ArrayConfig(64, 64),
              "Tetris-SDK", MacroGrid(2, 2))
mesh = serving_mesh_for(net, 4)
assert dict(mesh.shape) == {"data": 2, "row": 2, "col": 2}
plans = {t: compile_plan(net, executor_policy="mapped", mesh=mesh,
                         batch=t) for t in (2, 4)}
rng = np.random.RandomState(0)
ks = zero_pruned_kernels(net, [
    jnp.asarray(rng.randn(m.layer.k_h, m.layer.k_w,
                          m.layer.ic // m.group, m.layer.oc) * 0.2,
                jnp.float32) for m in net.layers])
assert all(fixed_constants(plans[t], ks, mesh) is None for t in (2, 4))
first = net.layers[0].layer
for t in (2, 4):
    x = jnp.asarray(rng.randn(t, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    # through jit the kernels are tracers: the in-trace build
    y_off = jax.jit(lambda ks, x: execute_plan(plans[t], ks, x,
                                               mesh=mesh))(ks, x)
    y_on = execute_plan(plans[t], ks, x, mesh=mesh)
    assert bool(jnp.all(y_on == y_off)), f"tier {t} outputs drifted"
assert constant_counts(net=net) == {}, constant_counts(net=net)
assert prepared_stats()["in_trace_calls"] == 4
one = {t: compile_plan(net, executor_policy="mapped", batch=t)
       for t in (2, 4)}
handles = [fixed_constants(one[t], ks) for t in (2, 4)]
assert handles[0] is handles[1], "tiers got distinct handles"
assert list(constant_counts(net=net).values()) == [1]
print("FLEET-CONSTS-OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "FLEET-CONSTS-OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# Acceptance: shared fleet >= dedicated slices; CLI smoke
# ---------------------------------------------------------------------------

def _parse_kv(row: str) -> dict:
    return dict(kv.split("=") for kv in row.strip().split(",")[-1].split(";")
                if "=" in kv)


@pytest.mark.slow
def test_fleet_bench_shared_beats_dedicated_slices():
    """ISSUE 7 acceptance: on the same mixed Poisson stream the shared
    fleet's aggregate effective images/s must be >= serving each model
    on a dedicated slice (interleaved medians, benchmarks/fleet_bench);
    per-model + aggregate SLO attainment are reported."""
    from benchmarks import fleet_bench
    rows = {r.name: _parse_kv(r.csv()) for r in fleet_bench.run(full=False)}
    shared, dedicated = rows["fleet/shared"], rows["fleet/dedicated"]
    assert float(shared["images_per_s"]) >= float(dedicated["images_per_s"])
    assert float(shared["speedup"]) >= 1.0
    assert 0.0 <= float(shared["slo_attainment"]) <= 1.0
    assert all(n in shared["per_model_slo"]
               for n in ("cnn8", "inception", "densenet40"))


@pytest.mark.slow
def test_fleet_cli_smoke(capsys):
    """serve_cnn --fleet end to end: per-model + aggregate CSV rows with
    SLO attainment."""
    from repro.launch import serve_cnn
    serve_cnn.main(["--fleet", "cnn8,inception", "--batch", "2",
                    "--requests", "8", "--arrival-rate", "200",
                    "--warmup", "1", "--slo-ms", "500",
                    "--ar", "64", "--ac", "64"])
    out = capsys.readouterr().out
    assert "serve_fleet/cnn8," in out
    assert "serve_fleet/inception," in out
    agg = next(ln for ln in out.splitlines()
               if ln.startswith("serve_fleet/all,"))
    kv = _parse_kv(agg)
    assert kv["models"] == "cnn8/inception"
    assert float(kv["images_per_s"]) > 0
    assert 0.0 <= float(kv["slo_attainment"]) <= 1.0
    assert "chainable prefix" in out     # inception is a layer set
