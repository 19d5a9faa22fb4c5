"""A backlog: every request due when the window opens, enough of them
that the queue never empties.  ``{"process": "backlog", "base": b,
"per_second": p}`` sends ``b + ceil(p * seconds)`` requests."""

import math

import numpy as np


def count(arrivals: dict, seconds: float) -> int:
    return arrivals["base"] + math.ceil(arrivals["per_second"] * seconds)


def due(arrivals: dict, n: int, rng) -> np.ndarray:
    return np.zeros(n)
