"""The one traffic generator: open-loop arrivals from a mix's parameters.

A mix file (``bench/traffic/<name>.json``) gives

* ``arrivals``: ``"poisson"`` — exponential gaps at the cell's rate;
* ``sizes``: ``{"zipf_exponent": s, "min": 1, "max": 16}`` — images per
  request, ``P(k) ∝ k**-s`` truncated to ``[min, max]``;
* ``pool_images``: how many distinct images the requests draw from;
* ``max_batch`` / ``max_delay_ms``: the coalescer the loop serves with;
* ``tiers`` (optional): the plan ladder's batch tiers, where the mix
  uses fewer than ``batch_tiers(max_batch)``; set-up warms these alone.

Every seed gets the same *set* of gaps and sizes in another order, so
seeds change which requests meet in a batch and which images they carry,
never how much work a run offers.  The gaps are the exponential
distribution's quantiles, scaled so the last arrival falls inside the
window; the sizes are the Zipf distribution's expected counts, rounded
so they sum to the request count.  The arrival times follow the
program's ``launch/serve_cnn.poisson_arrivals`` (cumulative exponential
gaps, the first request at t=0), and ``percentile`` is a copy of its
nearest-rank ``launch/batching.percentile``: the yardstick does not move
with the program.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent NumPy stream ``stream`` of ``seed`` (any integer)."""
    return np.random.default_rng([seed % 2**64, stream])


def zipf_probabilities(exponent: float, lo: int, hi: int) -> np.ndarray:
    k = np.arange(lo, hi + 1, dtype=np.float64)
    p = k ** -float(exponent)
    return p / p.sum()


def zipf_sizes(n: int, exponent: float, lo: int, hi: int) -> np.ndarray:
    """``n`` sizes whose counts are the truncated Zipf law's expected
    counts, rounded by largest remainder; ascending."""
    p = zipf_probabilities(exponent, lo, hi)
    exact = p * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return np.repeat(np.arange(lo, hi + 1), counts)


def exponential_gaps(n: int, rate_per_s: float, span_s: float) -> np.ndarray:
    """The ``n`` mid-quantiles of Exp(rate), scaled to sum below
    ``span_s`` when the rate would otherwise overrun it."""
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-u) / rate_per_s
    total = float(gaps.sum())
    if total >= span_s:
        gaps *= span_s * (1 - 0.5 / n) / total
    return gaps


@dataclass(frozen=True)
class Schedule:
    """Requests in arrival order: due time (s from window start),
    images, and the first of its consecutive pool rows."""

    times: np.ndarray
    sizes: np.ndarray
    starts: np.ndarray

    def __len__(self) -> int:
        return len(self.times)


def schedule(mix: dict, rate_per_s: float, seconds: float,
             seed: int) -> Schedule:
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}")
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"need a positive rate and window, got "
                         f"{rate_per_s}/s over {seconds} s")
    sz = mix["sizes"]
    lo, hi = int(sz["min"]), int(sz["max"])
    if not 1 <= lo <= hi <= int(mix["max_batch"]):
        raise ValueError(f"sizes [{lo}, {hi}] must lie in "
                         f"[1, max_batch={mix['max_batch']}]")
    pool = int(mix["pool_images"])
    if pool < hi:
        raise ValueError(f"pool of {pool} images < largest request {hi}")
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = exponential_gaps(n, rate_per_s, seconds)
    sizes = zipf_sizes(n, float(sz["zipf_exponent"]), lo, hi)
    gaps = gaps[rng_for(seed, 1).permutation(n)]
    sizes = sizes[rng_for(seed, 2).permutation(n)]
    times = np.cumsum(gaps) - gaps[0]       # first request at t=0
    starts = rng_for(seed, 3).integers(0, pool - sizes + 1)
    return Schedule(times=times, sizes=sizes.astype(np.int64),
                    starts=starts.astype(np.int64))


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sequence."""
    if len(xs) == 0:
        raise ValueError("percentile of an empty sequence")
    if not 0 <= q <= 100:
        raise ValueError(f"q must be in [0, 100], got {q}")
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100 * len(s)) - 1))]
