"""The benchmark's definition: BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by its name:

* ``bench/configs/<config>.json``  (the path is the entry's ``file``)
* ``bench/traffic/<traffic>.json``, and the modules its arrival process
  and length distributions name (``bench/traffic/<process>.py``,
  ``bench/traffic/lengths/<dist>.py``, see bench/traffic/generator.py)
* ``bench/metrics/<metric>.py``    (a ``compute(run)`` function)
* ``bench/families/<family>.py``   (the model family a configuration
  file names under ``family``, ``dense_decoder`` where it names none:
  its sizes, weights, plain reference and operation counts; the
  interface is bench/families/dense_decoder.py's docstring)

so a later cell, mix, metric or architecture is a new file and a new
entry, and no file here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

__all__ = ["ROOT", "BENCH", "load_benchmark", "workload", "config_of",
           "traffic_of", "metric_fn", "metrics_for", "peaks_for",
           "DEFAULT_FAMILY", "family"]

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DEFAULT_FAMILY = "dense_decoder"


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


def config_of(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str, bench_dir: str = BENCH) -> dict:
    return _json(os.path.join(bench_dir, "traffic", name + ".json"))


def metric_fn(name: str, bench_dir: str = BENCH):
    """The ``compute`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


@functools.lru_cache(maxsize=None)
def _family_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would, so that a family's
    # dataclasses can resolve their own module
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def family(name: str, bench_dir: str = BENCH):
    """The module ``bench/families/<name>.py``, loaded once per path."""
    path = os.path.join(os.path.abspath(bench_dir), "families",
                        name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"model family {name!r}: no module "
                                f"bench/families/{name}.py")
    return _family_module(path)


def _reports(entry: dict, wl: str) -> bool:
    return "workloads" not in entry or wl in entry["workloads"]


def metrics_for(bench: dict, wl: str, per_layer: bool) -> list[dict]:
    """The metric entries a run of cell ``wl`` prints: its end-to-end
    metrics, or (``per_layer``) the per-layer metrics that list it, or
    that list no cells and move an end-to-end metric it reports."""
    e2e = [m for m in bench["end_to_end"] if _reports(m, wl)]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}

    def listed(m):
        return wl in m["workloads"] if "workloads" in m \
            else m["moves"] in names
    return [m for m in bench["per_layer"] if listed(m)]


def peaks_for(device_kind: str, bench_dir: str = BENCH) -> dict:
    table = _json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
