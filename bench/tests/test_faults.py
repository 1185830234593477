"""A run with the timed path broken underneath comes out not correct.

These drive ``run.run_cell`` on the CPU — everything of a run but the
look for a chip — on the cnn8 configuration at its published widths,
with a small mix (tiers 1, 2 and 4) and a short window.  Each fault
wraps ``exec.execute_plan`` where the batch's answers are produced.
"""
import dataclasses
import time

import jax.numpy as jnp
import pytest

import cells
import run

SMALL_MIX = {"arrivals": "poisson", "pool_images": 64, "max_batch": 4,
             "max_delay_ms": 2.0,
             "sizes": {"zipf_exponent": 1.5, "min": 1, "max": 4}}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _execute():
    from repro.exec import execute_plan
    return execute_plan


def half_batch(plan, kernels, x, donate):
    """Only the first half of the batch is computed; the rest is zero."""
    y = _execute()(plan, kernels, x, donate=donate)
    keep = -(-y.shape[0] // 2)
    return y.at[keep:].set(0.0)


def altered_answer(plan, kernels, x, donate):
    """The first request of every batch gets a wrong answer."""
    y = _execute()(plan, kernels, x, donate=donate)
    return y.at[0].add(jnp.max(jnp.abs(y)) * 0.05)


class StaleAnswers:
    """Each tier hands back the answers of its first batch ever."""

    def __init__(self):
        self.first = {}

    def __call__(self, plan, kernels, x, donate):
        y = _execute()(plan, kernels, x, donate=donate)
        return self.first.setdefault(plan.batch, y)


def drop_every_third(pop):
    """The coalescer loses every third request it releases."""
    def lossy(self, now, force=False):
        return [r for r in pop(self, now, force) if r.payload % 3]
    return lossy


@pytest.fixture(scope="module")
def cell():
    c = cells.load_cell("cnn8.poisson")
    return dataclasses.replace(c, traffic=SMALL_MIX,
                               fixed={"rate_per_s": 150.0})


def _run(cell, execute=None, seed=2**33 + 1):
    return run.run_cell(cell, seed=seed, seconds=1.0, trace=False,
                        t_start=time.perf_counter(), device=DEVICE,
                        execute=execute)


def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["tiers_unchecked"]["value"] == 0
    assert res["checks"]["unanswered"]["value"] == 0


@pytest.mark.parametrize("fault", [half_batch, altered_answer,
                                   StaleAnswers()],
                         ids=["half_batch", "altered_answer", "stale"])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, execute=fault)
    assert res["correct"] is False
    chk = res["checks"]["worst_rel_err"]
    assert chk["value"] > chk["limit"]
    assert res["failed"] > 0


def test_dropped_requests_are_not_correct(cell, monkeypatch):
    from repro.launch.batching import Coalescer
    monkeypatch.setattr(Coalescer, "pop", drop_every_third(Coalescer.pop))
    res = _run(cell)
    assert res["correct"] is False
    assert res["checks"]["unanswered"]["value"] > 0
    assert res["failed"] >= res["checks"]["unanswered"]["value"]
