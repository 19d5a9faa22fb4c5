"""Model families (bench/families): the dense decoder read through its
family module gives what the harness read before families existed, bit
for bit, and a family that exists only as a new file runs a whole cell.

The parity values were recorded before the harness went through the
family, from bench/weights.py, bench/reference/decoder.py,
bench/cost/model.py, bench/stats.py and bench/cost/sc_proj.py called
directly, at the conftest's tiny granite-shaped and stablelm-shaped
configurations."""

import hashlib
import json
import os

import jax
import numpy as np
import pytest
from conftest import PEAKS, TINY, TINY_CONFIGS, _tiny_config, make_tiny_root

from bench import run, spec, stats
from bench.cost import sc_proj
from bench.serve import RunLog, StepLog

SEED = 2 ** 33 + 7
SEQ = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 35, 58, 63], np.int32)
PARENT = {
    "tiny": {
        "weights_sha256": "b64fa7814ae4d5a5834e89cb9a13f45c5ae6f47d2c0fee44"
                          "caf069e6b466f120",
        "best": [2.6875, 3.234375, 2.6875, 2.640625, 3.234375, 2.515625,
                 2.328125, 2.34375, 2.34375, 3.09375, 2.921875, 2.421875],
        "target": [-0.291015625, 0.171875, 0.87109375, -1.8984375,
                   0.1796875, -0.1630859375, -0.30859375, 0.474609375,
                   0.43359375, -1.75, 0.1494140625, -0.50390625],
        "argmax": [0, 0, 0, 15, 0, 0, 0, 53, 53, 34, 10, 28],
        "prefill_ops": 17339392.0, "decode_ops": 207360.0,
        "prefill_calls": [(13056.0, 4368.0), (14080.0, 2320.0)] * 2,
        "decode_calls": [(7424.0, 2832.0)] * 2,
        "step_model_ops": (2724864.0, 326144.0),
        "sc_proj": (5480448.0, 91578.51520701541)},
    "tiny_lm": {
        "weights_sha256": "7581c0e5f00d374aef61fba48f8eab9e4c334b3b47d39d70"
                          "ef0127ba1d1c9aaa",
        "best": [2.890625, 2.1875, 2.203125, 2.359375, 2.859375, 1.5078125,
                 2.96875, 2.5, 2.484375, 2.84375, 2.515625, 2.0625],
        "target": [0.1455078125, 0.515625, 0.291015625, -0.158203125,
                   -1.2578125, -1.1484375, 0.30859375, 0.7890625, 2.03125,
                   -2.234375, -0.8984375, -1.0078125],
        "argmax": [41, 32, 59, 24, 22, 55, 52, 22, 32, 5, 24, 24],
        "prefill_ops": 18977792.0, "decode_ops": 223744.0,
        "prefill_calls": [(13056.0, 5408.0), (14080.0, 3360.0)] * 2,
        "decode_calls": [(7424.0, 5152.0)] * 2,
        "step_model_ops": (3019776.0, 358912.0),
        "sc_proj": (6086656.0, 97937.51680775387)},
}


def _checksum(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(TINY_CONFIGS))
def test_the_dense_family_reads_what_the_harness_read_before(name):
    base, _, kv_heads = TINY_CONFIGS[name]
    cf = _tiny_config(name, base, kv_heads)
    want = PARENT[name]
    fam = spec.family(cf.get("family", spec.DEFAULT_FAMILY))
    d = fam.Dims(cf["config"], cf["padded_vocab"])
    w = cf["weights"]
    assert _checksum(fam.program_params(SEED, d, w["alpha_a"],
                                        w["dtype"])) == want["weights_sha256"]
    ref = fam.Reference(d, w["alpha_a"], SEED, w["dtype"],
                        cf["config"]["torch_dtype"], kv_int8=True,
                        attention="exact", page=8)
    (r,) = ref.readings([SEQ], [np.roll(SEQ, -1)[None]], 16)
    np.testing.assert_array_equal(r["best"], np.float32(want["best"]))
    np.testing.assert_array_equal(r["target"][0],
                                  np.float32(want["target"]))
    np.testing.assert_array_equal(r["argmax"], want["argmax"])
    assert fam.prefill_ops(d, 100) == want["prefill_ops"]
    assert fam.decode_ops(d, 100) == want["decode_ops"]
    log = RunLog(seconds=1.0, max_slots=2, page_size=8, chunk=8,
                 kv_format="int8", dims=d, family=fam)
    step = StepLog(0.0, 0.1, admitted=[5, 13], decode_lens=[7, 20])
    assert stats.prefill_calls(log, step) == want["prefill_calls"]
    assert stats.decode_calls(log, step) == want["decode_calls"]
    assert stats.step_model_ops(log, step) == want["step_model_ops"]
    assert sc_proj.step_ops_bytes(fam, d, 37, 3) == want["sc_proj"]


# a second family, written only into a checkout's copy: the dense
# decoder's functions, counted, and one kernel name more
PROBE = '''"""The dense decoder, each call counted."""

from collections import Counter

from bench import spec

_dense = spec.family("dense_decoder")
CALLS = Counter()
KERNELS = ("probe_kernel",)
Dims = _dense.Dims
model_config = _dense.model_config


def _counted(name):
    fn = getattr(_dense, name)

    def call(*a, **k):
        CALLS[name] += 1
        return fn(*a, **k)
    return call


for _n in ("program_params", "Reference", "prefill_ops", "decode_ops",
           "projection_params", "head_params", "projection_io_bytes"):
    globals()[_n] = _counted(_n)
'''


def _add_cell(root: str, name: str, family: str) -> str:
    """A configuration of ``family`` (the tiny granite-shaped one
    otherwise) and its cell, added to the checkout's BENCHMARK.json and
    to every metric that lists cells."""
    base, _, kv_heads = TINY_CONFIGS["tiny"]
    cf = dict(_tiny_config(name, base, kv_heads), family=family)
    with open(os.path.join(root, "bench", "configs", name + ".json"),
              "w") as f:
        json.dump(cf, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    cell = name + ".chat"
    b["configs"].append({"name": name, "source": "a test",
                         "file": f"bench/configs/{name}.json",
                         "reduced": [], "why": "tiny"})
    b["workloads"].append({"name": cell, "config": name,
                           "traffic": "tiny_chat", "chips": 1,
                           "why": "tiny"})
    for m in b["end_to_end"] + b["per_layer"]:
        if TINY in m.get("workloads", ()):
            m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(b, f)
    return cell


def _fake_profile(seen):
    """The trace reduction, fed one device operation per kernel name it
    is given (a CPU profile holds no device plane)."""
    from bench import trace

    def summarize(tdir, kernels=(), modules=()):
        seen["kernels"] = tuple(kernels)
        ops = {"/device:TPU:0": [(f"%{k}_pallas.1 = f32[8] custom-call()",
                                  10 + 10 * i, 15 + 10 * i)
                                 for i, k in enumerate(kernels)]}
        seen["summary"] = trace.reduce_events(
            ops, {}, [("bench.step", 0, 10 ** 9)], kernels, modules)
        return seen["summary"]
    return summarize


def test_a_family_in_new_files_alone_runs_a_whole_cell(tmp_path,
                                                        monkeypatch):
    root = make_tiny_root(str(tmp_path))
    with open(os.path.join(root, "bench", "families", "probe.py"),
              "w") as f:
        f.write(PROBE)
    cell = _add_cell(root, "tiny_probe", "probe")
    seen = {}
    monkeypatch.setattr("bench.trace.summarize", _fake_profile(seen))
    res = run.run_cell(cell, 2 ** 31 + 23, 1.5, True, PEAKS, root=root)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert seen["kernels"] == ("paged_attn_decode", "paged_attn_prefill",
                               "probe_kernel")
    assert "probe_kernel" in seen["summary"]["kernel_s"]
    assert "mfu" in res["metrics"]
    probe = spec.family("probe", os.path.join(root, "bench"))
    assert probe.CALLS["program_params"] == 1
    assert probe.CALLS["Reference"] >= 1
    assert probe.CALLS["prefill_ops"] + probe.CALLS["decode_ops"] > 0


def test_an_unknown_family_names_its_missing_module(tmp_path):
    root = make_tiny_root(str(tmp_path))
    cell = _add_cell(root, "tiny_unknown", "no_such_family")
    with pytest.raises(FileNotFoundError,
                       match="bench/families/no_such_family.py"):
        run.Cell(cell, root)


def test_a_family_module_may_hold_dataclasses(tmp_path):
    fams = tmp_path / "bench" / "families"
    fams.mkdir(parents=True)
    (fams / "dataclass_dims.py").write_text(
        "from __future__ import annotations\n"
        "import dataclasses\n\n\n"
        "@dataclasses.dataclass\n"
        "class Dims:\n"
        "    layers: int\n"
        "    attn_layers: int = 0\n")
    fam = spec.family("dataclass_dims", str(tmp_path / "bench"))
    assert fam.Dims(4).layers == 4
    assert spec.family("dataclass_dims", str(tmp_path / "bench")) is fam
