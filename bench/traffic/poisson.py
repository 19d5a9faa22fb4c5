"""Open-loop Poisson arrivals at a fixed rate: ``{"process": "poisson",
"rate_per_s": r}``.  The gaps are the exponential distribution's
quantiles at the strata, in an order drawn from the seed, so every seed
spans the same time."""

import numpy as np

from bench.traffic.generator import strata


def count(arrivals: dict, seconds: float) -> int:
    return max(1, round(arrivals["rate_per_s"] * seconds))


def due(arrivals: dict, n: int, rng) -> np.ndarray:
    gaps = rng.permutation(-np.log1p(-strata(n)) / arrivals["rate_per_s"])
    return np.cumsum(gaps)

