"""The one traffic generator: a mix file's parameters -> timed requests.

A mix (``bench/traffic/<name>.json``) names its arrival process and its
length distributions; each is a module found by that name, so a new
process or distribution is a new file and no file here changes:

    {"arrivals": {"process": "poisson", "rate_per_s": 4.0}, ...}
        -> bench/traffic/poisson.py
    {"arrivals": {"process": "backlog", "base": 16, "per_second": 8}, ...}
        -> bench/traffic/backlog.py
    "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                      "min": 32, "max": 1536}
        -> bench/traffic/lengths/lognormal.py
    "output_tokens": {"dist": "uniform", "min": 256, "max": 496}
        -> bench/traffic/lengths/uniform.py
    "block": 16          (optional: stratify sizes per block of requests)

An arrival process module has ``count(arrivals, seconds)``, the number
of requests a window of ``seconds`` gets, and ``due(arrivals, n, rng)``,
their due times in seconds after the window opens, in order.  A length
module has ``quantiles(dist, u)``: the distribution's quantiles at the
probabilities ``u``, as floats (the generator rounds and clips them to
``[min, max]``).

Steadiness: the seed changes the order of the work, not the work.
Lengths (and a process's gaps) are quantiles at the strata
``(i + 0.5) / n`` (within each ``block`` where one is given, so every
whole block of a backlog holds the same sizes), and the seed only
permutes them and draws the token ids.  So every seed sends the same
multiset of prompt lengths, output lengths and gaps.
"""

from __future__ import annotations

import importlib.util
import os
import re
from dataclasses import dataclass

import numpy as np

__all__ = ["Request", "generate", "count", "sizes", "strata", "TRAFFIC"]

TRAFFIC = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@dataclass
class Request:
    due_s: float             # seconds after the window opens
    prompt: list[int]
    max_new_tokens: int


def strata(n: int) -> np.ndarray:
    """The probabilities ``(i + 0.5) / n``: one per stratum of ``n``."""
    return (np.arange(n) + 0.5) / n


def _module(traffic_dir: str, sub: str, name: str):
    """``<traffic_dir>/<sub>/<name>.py``, loaded by its name."""
    if not _NAME.match(name):
        raise ValueError(f"bad traffic module name {name!r}")
    path = os.path.join(traffic_dir, sub, name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"no traffic module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_" + (sub + "_" if sub else "")
        + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _process(mix: dict, traffic_dir: str):
    return _module(traffic_dir, "", mix["arrivals"]["process"])


def count(mix: dict, seconds: float, traffic_dir: str = TRAFFIC) -> int:
    return _process(mix, traffic_dir).count(mix["arrivals"], seconds)


def sizes(dist: dict, n: int, block: int | None = None,
          traffic_dir: str = TRAFFIC) -> np.ndarray:
    """The seed-free multiset of ``n`` lengths (stratified quantiles)."""
    x = _module(traffic_dir, "lengths", dist["dist"]).quantiles(
        dist, strata(block or n))
    one = np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    return np.resize(one, n)


def _permute(rng, x: np.ndarray, block: int | None) -> np.ndarray:
    b = block or len(x)
    out = x.copy()
    for s in range(0, len(x), b):
        out[s:s + b] = rng.permutation(out[s:s + b])
    return out


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             traffic_dir: str = TRAFFIC) -> list[Request]:
    proc = _process(mix, traffic_dir)
    n = proc.count(mix["arrivals"], seconds)
    block = mix.get("block")
    rng = np.random.default_rng([int(seed) % 2 ** 64, 0x7A11C])
    prompts = _permute(rng, sizes(mix["prompt_tokens"], n, block,
                                  traffic_dir), block)
    outputs = _permute(rng, sizes(mix["output_tokens"], n, block,
                                  traffic_dir), block)
    due = proc.due(mix["arrivals"], n, rng)
    return [Request(float(due[i]),
                    rng.integers(0, vocab, int(prompts[i])).tolist(),
                    int(outputs[i]))
            for i in range(n)]
