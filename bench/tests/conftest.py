"""Shared set-up of the benchmark's own tests (CPU only).

``tiny_root`` is a checkout-like directory: a copy of bench/ with two
tiny configurations, one tiny mix and a BENCHMARK.json naming them, so a
whole run (weights, engine, warm-up, window, check) fits a test, with
the Pallas kernels in interpret mode.
"""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "tiny.chat"
TINY_LM = "tiny_lm.chat"
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
         "hbm_bytes_per_s": 819e9}
# name -> (the configuration file it is cut from, its cell, KV heads): a
# granite-shaped one (RMSNorm, full rotary, grouped-query attention) and
# a stablelm-shaped one (LayerNorm with a bias, rotary on a quarter of
# each head, as many KV heads as query heads)
TINY_CONFIGS = {"tiny": ("granite-3-2b.sc_int", TINY, 2),
                "tiny_lm": ("stablelm-2-1.6b.sc_int", TINY_LM, 4)}


def _tiny_config(name: str, base: str, kv_heads: int) -> dict:
    with open(os.path.join(BENCH, "configs", base + ".json")) as f:
        c = json.load(f)
    c["name"] = name
    c["config"].update(num_hidden_layers=2, hidden_size=64,
                       num_attention_heads=4, num_key_value_heads=kv_heads,
                       head_dim=16, intermediate_size=128, vocab_size=64)
    c["padded_vocab"] = 256
    c["engine"].update(max_slots=2, max_len=32, page_size=8,
                       prefill_chunk=8, attn_backend="pallas-interpret")
    # at this size, on the CPU, every gap of the program is 0 (its
    # kernels' float32 dots are exact there, as is the reference's exact
    # attention); the float8 control's gaps average 0.59 to 0.71
    c["check"] = {"attention": "exact", "gap_tau": 0.25,
                  "share_limit": 0.02}
    return c


def make_tiny_root(dest: str) -> str:
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, (base, _, kv_heads) in TINY_CONFIGS.items():
        with open(os.path.join(dest, "bench", "configs", name + ".json"),
                  "w") as f:
            json.dump(_tiny_config(name, base, kv_heads), f)
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 16.0},
           "prompt_tokens": {"dist": "lognormal", "median": 8,
                             "sigma": 0.5, "min": 3, "max": 15},
           "output_tokens": {"dist": "uniform", "min": 2, "max": 8}}
    with open(os.path.join(dest, "bench", "traffic", "tiny_chat.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"] = [{"name": name, "source": "a test",
                     "file": f"bench/configs/{name}.json", "reduced": [],
                     "why": "tiny"} for name in TINY_CONFIGS]
    b["workloads"] = [{"name": cell, "config": name,
                       "traffic": "tiny_chat", "chips": 1, "why": "tiny"}
                      for name, (_, cell, _) in TINY_CONFIGS.items()]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY, TINY_LM]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny_checkout")))
