"""Data-driven layout: new configurations, mixes and metrics are new
files found by name, and BENCHMARK.json keeps the contract's shape."""

import json
import os
import re

import pytest
from conftest import BENCH, ROOT, make_tiny_root

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_new_config_mix_and_metric_files_are_found_without_edits(
        tmp_path):
    root = make_tiny_root(str(tmp_path))
    with open(os.path.join(root, "bench", "metrics",
                           "steps_seen.py"), "w") as f:
        f.write("def compute(run):\n    return float(len(run.steps))\n")
    b = spec.load_benchmark(root)
    b["per_layer"].append({"name": "steps_seen", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "output_tps",
                           "workloads": ["tiny.chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    b = spec.load_benchmark(root)
    assert spec.config_of(b, "tiny", root)["config"]["hidden_size"] == 64
    bench_dir = os.path.join(root, "bench")
    assert spec.traffic_of("tiny_chat", bench_dir)["arrivals"][
        "process"] == "poisson"
    names = [m["name"] for m in spec.metrics_for(b, "tiny.chat", True)]
    assert "steps_seen" in names

    class Run:
        steps = [1, 2, 3]
    assert spec.metric_fn("steps_seen", bench_dir)(Run()) == 3.0


def test_every_metric_named_in_the_benchmark_has_a_reader():
    b = spec.load_benchmark()
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.metric_fn(m["name"]))


def test_metrics_for_follows_workloads_and_moves():
    b = {"end_to_end": [{"name": "tps"},
                        {"name": "ttft", "workloads": ["a"]}],
         "per_layer": [{"name": "x", "moves": "ttft"},
                       {"name": "y", "moves": "tps", "workloads": ["b"]}]}
    assert [m["name"] for m in spec.metrics_for(b, "b", False)] == ["tps"]
    assert [m["name"] for m in spec.metrics_for(b, "a", True)] == ["x"]
    assert [m["name"] for m in spec.metrics_for(b, "b", True)] == ["y"]


def test_benchmark_json_keeps_the_contract_shape():
    b = spec.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cf = json.load(f)
        changed = sorted(k for k in cf["published"]
                         if cf["published"][k] != cf["config"].get(k))
        assert changed == sorted(c["reduced"])
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert set(m["workloads"]) <= cells if "workloads" in m else True
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in b["end_to_end"])


def test_peaks_table_is_keyed_by_device_kind_and_refuses_others():
    p = spec.peaks_for("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        spec.peaks_for("TPU v4")
