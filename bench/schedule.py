"""Open-loop arrival schedules, drawn from the seed.

Every seed gets the same multiset of inter-arrival gaps, in its own order:
the gaps are the quantiles (i + 1/2) / n of an exponential distribution
with the traffic's rate, so a schedule of n requests always spans the
same time and offers the same load, and the seed changes only when each
request comes.  A Poisson process of that rate has these gaps in the
limit; what it loses is the run-to-run spread of the count, which would
change the work from seed to seed.
"""
from __future__ import annotations

import numpy as np


def poisson(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in seconds from the window's start, ascending; the first
    request is due at 0."""
    n = max(int(round(rate_per_s * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def percentile(latencies_s: np.ndarray, q: float) -> float:
    """The nearest-rank q-th percentile over all requests; a request that
    failed or never came is +inf, so it counts as missing every limit."""
    x = np.sort(np.asarray(latencies_s, np.float64))
    k = max(int(np.ceil(q / 100.0 * len(x))) - 1, 0)
    return float(x[k])
