"""The traffic generator: every seed offers the same work in another
order."""
import numpy as np
import pytest

import traffic

MIX = {"arrivals": "poisson", "pool_images": 4096, "max_batch": 64,
       "max_delay_ms": 2.0,
       "sizes": {"zipf_exponent": 1.5, "min": 1, "max": 16}}


def test_zipf_law_as_stated():
    p = traffic.zipf_probabilities(1.5, 1, 16)
    assert p[0] == pytest.approx(0.47, abs=0.005)
    assert float((np.arange(1, 17) * p).sum()) == pytest.approx(3.14,
                                                                abs=0.01)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**62 + 5])
def test_seeds_permute_one_set_of_gaps_and_sizes(seed):
    a = traffic.schedule(MIX, 500.0, 4.0, seed=1)
    b = traffic.schedule(MIX, 500.0, 4.0, seed=seed)
    assert len(a) == len(b) == 2000
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    gaps = traffic.exponential_gaps(2000, 500.0, 4.0)
    for sched in (a, b):      # all but the first gap, which t=0 absorbs
        assert np.isclose(np.diff(sched.times)[:, None],
                          gaps[None, :]).any(axis=1).all()
    assert b.times[0] == 0 and b.times[-1] < 4.0
    assert np.all(np.diff(b.times) >= 0)
    assert np.all(b.starts + b.sizes <= MIX["pool_images"])


def test_same_seed_same_schedule_and_mean_rate():
    a = traffic.schedule(MIX, 300.0, 10.0, seed=42)
    b = traffic.schedule(MIX, 300.0, 10.0, seed=42)
    assert np.array_equal(a.times, b.times)
    assert np.array_equal(a.starts, b.starts)
    gaps = np.diff(a.times)
    assert gaps.mean() == pytest.approx(1 / 300.0, rel=0.02)
    assert a.sizes.mean() == pytest.approx(3.14, abs=0.02)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert traffic.percentile(xs, 95) == 95
    assert traffic.percentile(xs, 100) == 100
    assert traffic.percentile([3.0], 50) == 3.0
    with pytest.raises(ValueError):
        traffic.percentile([], 50)
