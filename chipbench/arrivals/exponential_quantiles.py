"""`{"kind": "exponential_quantiles", "rate_per_s"}`: open-loop arrivals on the
wall clock whose gaps are the n mid-quantiles of the exponential distribution
with mean 1/rate (rescaled so that their mean is exactly 1/rate), shuffled by
the seed pool after pool. Stratified, not drawn: a Poisson process's gaps in
distribution, but every seed and every pool has the same multiset of them, so
runs differ in the order of arrivals and not in how many fall into a window."""

import numpy as np


def gaps(arrivals: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    values = -np.log1p(-u)
    return values / values.mean() / float(arrivals["rate_per_s"])
