"""Uniform whole lengths: ``{"dist": "uniform", "min": a, "max": b}``,
each of ``a..b`` equally often."""

import numpy as np


def quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    return dist["min"] + u * (dist["max"] - dist["min"] + 1) - 0.5
