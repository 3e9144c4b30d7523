"""`{"dist": "lognormal", "median", "sigma", "min", "max"}`: the n mid-quantiles
of the lognormal, as whole numbers inside its clip."""

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def pool(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    values = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(np.int64)
