"""Lognormal lengths: ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}``."""

from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in u])
    return dist["median"] * np.exp(dist["sigma"] * z)
