"""Measured-feedback autotuner (repro/tune): deterministic search under
an injectable fake timer/runner, analytical-seed shortlist correctness,
disk-cache round-trip (a cold process with a warm cache performs ZERO
measurements), and the real-measurement cnn8 smoke — tuned never slower
than the "auto" default on its own interleaved-median evidence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core import ArrayConfig, MacroGrid, map_net, memo, networks
from repro.exec import compile_plan

RNG = np.random.RandomState(3)


def _net(name="cnn8", layers=None, grid=MacroGrid(2, 2), groups=(1, 2)):
    layers = networks.NETWORKS[name]() if layers is None else layers
    return map_net(name, layers, ArrayConfig(64, 64), "TetrisG-SDK",
                   grid, groups=groups)


def _fake(costs, *, default=1.0):
    """A deterministic measurement fixture: a virtual clock plus a
    runner whose per-candidate step advances it by a scripted cost —
    ``costs`` maps candidate -> seconds (callables get the candidate)."""
    t = [0.0]

    def clock():
        return t[0]

    def runner(cand):
        def step():
            c = costs(cand) if callable(costs) else costs.get(cand, default)
            t[0] += c
        return step

    return clock, runner


# ---------------------------------------------------------------- measure


def test_median_and_interleaving_order():
    assert tune.median([3.0, 1.0, 2.0]) == 2.0
    assert tune.median([4.0, 1.0, 3.0, 2.0]) == 3.0   # upper median
    with pytest.raises(ValueError):
        tune.median([])
    calls = []
    outs = tune.interleaved_rounds(
        [lambda: calls.append("a"), lambda: calls.append("b")],
        rounds=2, warmup=1)
    # warmup first (a, b), then strict round-robin rounds
    assert calls == ["a", "b", "a", "b", "a", "b"]
    assert [len(o) for o in outs] == [2, 2]


def test_interleaved_medians_fake_clock():
    t = [0.0]
    costs = iter([5.0, 3.0, 4.0])       # slow's three timed rounds

    def slow():
        t[0] += next(costs)

    def fast():
        pass
    meds = tune.interleaved_medians([slow, fast], rounds=3,
                                    clock=lambda: t[0], warmup=0)
    assert meds == [4.0, 0.0]


# ----------------------------------------------------------------- space


def test_analytic_cost_ranks_policies_and_splits():
    net = _net()
    n = len(net.layers)
    ref = tune.Candidate(policy=("reference",) * n)
    mapped = tune.Candidate(policy=("mapped",) * n)
    # without a mesh no macro parallelism is realized: the weights rank
    assert tune.analytic_cost(net, ref) < tune.analytic_cost(net, mapped)
    # a data split divides the whole cost; lookahead variants tie
    split = tune.Candidate(policy=("reference",) * n,
                           mesh_split=(2, 1, 1))
    assert tune.analytic_cost(net, split) == pytest.approx(
        tune.analytic_cost(net, ref) / 2)
    assert tune.analytic_cost(net, ref) == tune.analytic_cost(
        net, tune.Candidate(policy=("reference",) * n, lookahead=2))


def test_shortlist_seeds_base_major_and_keeps_baseline():
    net = _net()
    n = len(net.layers)
    space = tune.enumerate_space(net, batch=4)
    assert len(set(space)) == len(space)
    k = 5
    short = tune.shortlist(net, space, k)
    assert len(short) == k
    # base-major promotion: distinct bases appear in non-decreasing
    # analytic cost, and a base's variants are contiguous
    costs, seen = [], []
    for c in short:
        if c.base not in seen:
            seen.append(c.base)
            costs.append(tune.analytic_cost(net, c))
        else:
            assert c.base == seen[-1], "base variants not contiguous"
    assert costs == sorted(costs)
    # the model-predicted best base (all-reference on CPU) leads
    assert short[0].policy == ("reference",) * n
    # a worst-cost baseline is forced in, displacing the tail
    worst = tune.Candidate(policy=("mapped",) * n, lookahead=7)
    short2 = tune.shortlist(net, space, k, baseline=worst)
    assert len(short2) == k and short2[-1] == worst
    with pytest.raises(ValueError, match="k >= 1"):
        tune.shortlist(net, space, 0)


# ---------------------------------------------------------------- search


def test_autotune_deterministic_fake_timer():
    """The full driver under a scripted runner: the cheapest candidate
    wins, the baseline survives to the final rounds, and the measured-
    step count honors the per-candidate budget exactly."""
    memo.clear()
    net = _net()
    n = len(net.layers)
    budget = tune.TuneBudget(shortlist=4, rounds=2, eta=2, max_rounds=4)
    base = tune.baseline_candidate(net, batch=4)

    def costs(c):                    # reference wins big, lookahead=2 best
        s = 1.0 if c.policy == ("reference",) * n else 4.0
        return s - 0.1 * c.lookahead

    clock, runner = _fake(costs)
    res = tune.autotune(net, batch=4, budget=budget, clock=clock,
                        runner=runner, store=False)
    assert not res.cached
    win = res.config.candidate
    assert win.policy == ("reference",) * n and win.lookahead == 2
    assert res.config.median_s == pytest.approx(0.8)
    # baseline measured in the SAME final rounds -> speedup is evidence
    assert res.config.baseline_s == pytest.approx(costs(base))
    assert res.config.speedup > 1
    final = [t for t in res.trials if t.rounds == res.config.rounds]
    assert any(t.candidate == base for t in final)
    assert any(t.candidate == win for t in final)
    # measurement budget: every trial cost its rounds + one warmup step
    assert res.measurements == sum(t.rounds + budget.warmup
                                   for t in res.trials)
    # rounds escalate by eta and never exceed the cap
    stages = sorted({t.rounds for t in res.trials})
    assert stages == [2, 4]
    assert tune.tuned_config(net, batch=4) is None     # store=False


def test_autotune_winner_never_slower_than_baseline_by_construction():
    """Even when every challenger is WORSE than the default, the winner
    is the default itself — tuned can tie auto but never lose to it."""
    memo.clear()
    net = _net()
    base = tune.baseline_candidate(net, batch=4)
    clock, runner = _fake(lambda c: 1.0 if c == base else 9.0)
    res = tune.autotune(net, batch=4, clock=clock, runner=runner,
                        budget=tune.SMOKE_BUDGET, store=False)
    assert res.config.candidate == base
    assert res.config.median_s <= res.config.baseline_s


def test_autotune_persists_and_cold_process_loads(tmp_path):
    """Acceptance: winners survive a process restart — with a warm disk
    cache a cold process adopts the tuned config with zero measurements
    (memo counters asserted), and `compile_plan(executor_policy=
    "tuned")` serves it; without any tuning it falls back to "auto"."""
    memo.clear()
    memo.set_disk_cache(tmp_path)
    try:
        net = _net()
        n = len(net.layers)
        # untuned: "tuned" falls back to the auto policy
        auto_plan = compile_plan(net, executor_policy="tuned", batch=2)
        assert auto_plan.executors == compile_plan(
            net, executor_policy="auto", batch=2).executors

        clock, runner = _fake(
            lambda c: 0.5 if c.policy == ("reference",) * n else 2.0)
        res = tune.autotune(net, batch=4, budget=tune.SMOKE_BUDGET,
                            clock=clock, runner=runner)
        assert res.measurements > 0
        win = res.config.candidate

        memo.clear()            # in-memory gone, disk persists = cold process
        st0 = dict(memo.stats)

        def exploding(_cand):
            raise AssertionError("cold process must not measure")
        res2 = tune.autotune(net, batch=4, clock=clock, runner=exploding)
        assert res2.cached and res2.measurements == 0
        assert res2.config == res.config
        assert memo.stats["disk_hits"] >= st0.get("disk_hits", 0) + 1

        # the serve-path entry: "tuned" compiles the winner's config
        plan = compile_plan(net, executor_policy="tuned", batch=4)
        assert plan.executors == win.policy
        assert plan.lookahead == win.lookahead
        # generic slot: other batches inherit the tuning
        assert tune.tuned_config(net, batch=16) == res.config
    finally:
        memo.set_disk_cache(None)
        memo.clear()


def test_autotune_rejects_bad_inputs():
    net = _net()
    with pytest.raises(ValueError, match="batch"):
        tune.autotune(net, batch=0)
    with pytest.raises(ValueError, match="malformed budget"):
        tune.TuneBudget(rounds=0)
    with pytest.raises(ValueError, match="malformed budget"):
        tune.TuneBudget(rounds=4, max_rounds=2)


# ------------------------------------------------------- real measurement


def test_autotune_cnn8_real_smoke():
    """ISSUE 6 acceptance (cnn8, real wall-clock): the tuned config's
    final interleaved-round median is never slower than the auto
    baseline measured in the SAME rounds, and the tuned plan executes
    correctly end to end."""
    memo.clear()
    net = _net()
    res = tune.autotune(net, batch=2, budget=tune.SMOKE_BUDGET,
                        store=True)
    assert res.measurements > 0
    assert res.config.median_s <= res.config.baseline_s
    assert res.config.speedup >= 1.0

    # the winner actually serves: tuned plan forward == reference values
    from repro.cnn.mapped_net import (reference_net_apply,
                                      zero_pruned_kernels)
    from repro.exec import execute_plan
    plan = compile_plan(net, executor_policy="tuned", batch=2)
    assert plan.executors == res.config.candidate.policy
    ks = zero_pruned_kernels(net, [
        jnp.asarray(RNG.randn(m.layer.k_h, m.layer.k_w,
                              m.layer.ic // m.group, m.layer.oc) * 0.1,
                    jnp.float32) for m in net.layers])
    first = net.layers[0].layer
    x = jnp.asarray(RNG.randn(2, first.ic, first.i_h, first.i_w),
                    jnp.float32)
    y = execute_plan(plan, ks, x)
    r = reference_net_apply(net, ks, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(r), rtol=1e-4,
        atol=1e-4 * float(jnp.max(jnp.abs(r))))
    memo.clear()


def test_report_csv_json_trajectory(tmp_path):
    memo.clear()
    net = _net()
    clock, runner = _fake(lambda c: 1.0)
    res = tune.autotune(net, batch=4, budget=tune.SMOKE_BUDGET,
                        clock=clock, runner=runner, store=False)
    results = {"cnn8": res}
    text = tune.write_csv(results, str(tmp_path / "tune_bench.csv"))
    assert text.splitlines()[0] == "name,usec,extras"
    assert any(line.startswith("tune/cnn8,") for line in text.splitlines())
    assert "speedup=" in text and "baseline_us=" in text
    assert (tmp_path / "tune_bench.csv").read_text() == text
    js = tune.write_json(results, str(tmp_path / "tune.json"))
    import json
    payload = json.loads(js)
    assert payload["cnn8"]["config"]["candidate"]["policy"]
    entry = tune.trajectory_entry(results, pr="PR 6", note="test")
    assert entry["nets"]["cnn8"]["speedup"] == pytest.approx(
        res.config.speedup)
    ledger = tmp_path / "BENCH_autotune.json"
    tune.append_trajectory(str(ledger), entry)
    tune.append_trajectory(str(ledger), entry)
    assert len(json.loads(ledger.read_text())) == 2


def test_fleet_signature_keys_platform_and_count():
    fleet = tune.fleet_signature()
    assert fleet == (jax.default_backend(), len(jax.devices()))
    key1 = tune.tuning_key("net", fleet, 4)
    key2 = tune.tuning_key("net", ("tpu", 8), 4)
    assert key1 != key2


def test_tpu_seeds_only_sdk_policies_compile_plan_accepts():
    """The tuner asks the plan compiler's own sdk predicate at its
    batch: on a TPU the auto seed runs CNN8-3 on sdk at the serving
    batch, and at a batch whose sdk working set the chip refuses no
    seed or candidate puts it there (compile_plan would raise)."""
    net = map_net("cnn8", networks.cnn8(), ArrayConfig(512, 512),
                  "TetrisG-SDK", MacroGrid(), groups=(1, 2, 4))
    assert tune.auto_policy(net, backend="tpu", batch=64)[1] == "sdk"
    huge = 4096
    assert all(p[1] != "sdk"
               for p in tune.policy_candidates(net, backend="tpu",
                                               batch=huge))
    cands = tune.enumerate_space(net, batch=huge, backend="tpu",
                                 mesh_splits=[None], lookaheads=(1,))
    assert cands and all(c.policy[1] != "sdk" for c in cands)
    for p in tune.policy_candidates(net, backend="tpu", batch=64):
        compile_plan(net, executor_policy=p, batch=64, interpret=False)
