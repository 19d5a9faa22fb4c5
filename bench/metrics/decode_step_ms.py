"""Median host time of the window's step() calls that admitted nothing
and decoded (one batched decode step each, tokens on the host)."""

import numpy as np

from bench.stats import in_window


def compute(run):
    t = [s.t1 - s.t0 for s in in_window(run)
         if s.decode_lens and not s.admitted]
    return float(np.median(t) * 1e3) if t else None
