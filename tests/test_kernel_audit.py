"""Injection suite for the static Pallas kernel auditor.

Strategy: build small hand-written :class:`LaunchPlan`s with one defect
each — an index map that runs one page past the table under the
worst-case scalar fill, a scratch allocation over the VMEM budget, a
revisited output with no declared accumulator / no ``pl.when`` guard /
a ``parallel`` revisit axis — and require that *exactly* the targeted
pass fires (the other three stay green).  Then the shipped registry
(every kernel x kv_format x autotune sweep shape) must audit clean.

The injected plans are never executed, which is the point: the auditor
must catch these from geometry alone.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

from repro.analysis.kernel_audit import (audit_registry, run_plan_audits,
                                         scalar_sets)
from repro.analysis.lint import hygiene_repo, hygiene_scan
from repro.kernels.dispatch import KERNEL_REGISTRY
from repro.kernels.paged_attention import paged_attn_decode_plan
from repro.kernels.plan import (BlockOperand, LaunchPlan, ScalarOperand,
                                estimate_vmem)

# ---------------------------------------------------------------------------
# fixtures: a clean baseline plan and one-defect mutants
# ---------------------------------------------------------------------------

PAGES, PAGE, ROWS, D = 9, 16, 64, 32


def _kernel_with_when(x_ref, o_ref):          # body is never traced
    import jax.experimental.pallas as pl      # pragma: no cover
    pl.when
    o_ref[...] = x_ref[...]


def _kernel_plain(x_ref, o_ref):              # pragma: no cover
    o_ref[...] = x_ref[...]


def _base_plan(**over):
    """A paged-gather plan shaped like the real decode kernels: a page
    table scalar selects which KV page each grid step streams."""
    kw = dict(
        name="toy_paged",
        grid=(4,),
        scalars=(ScalarOperand("table", (4,), jnp.int32,
                               max_value=PAGES - 1),),
        inputs=(BlockOperand("pages", (PAGES, PAGE, D), jnp.float32,
                             (1, PAGE, D),
                             lambda p, tbl: (tbl[p], 0, 0)),),
        outputs=(BlockOperand("o", (4, PAGE, D), jnp.float32,
                              (1, PAGE, D), lambda p, tbl: (p, 0, 0)),),
        scratch=(),
        kernel=_kernel_plain,
    )
    kw.update(over)
    return LaunchPlan(**kw)


def _passes(plan):
    res = run_plan_audits(plan, "inj")
    return {r.passname: r for r in res}


def _only_fails(plan, passname):
    """Assert exactly ``passname`` fires; return its violations."""
    byname = _passes(plan)
    assert not byname[passname].ok, \
        f"{passname} should have fired: {byname[passname].to_dict()}"
    for other, r in byname.items():
        if other != passname:
            assert r.ok, (f"{other} fired collaterally: "
                          f"{[v.message for v in r.violations]}")
    return byname[passname].violations


# ---------------------------------------------------------------------------
# clean baseline
# ---------------------------------------------------------------------------

def test_clean_plan_passes_all_four():
    byname = _passes(_base_plan())
    assert all(r.ok for r in byname.values()), \
        {k: [v.message for v in r.violations] for k, r in byname.items()}
    assert set(byname) == {"bounds", "vmem", "revisit", "grid"}


def test_scalar_sets_cover_extremes_and_declared_values():
    plan = _base_plan(scalars=(
        ScalarOperand("table", (4,), jnp.int32, max_value=PAGES - 1),
        ScalarOperand("len", (1,), jnp.int32, max_value=63,
                      values=(15, 16, 17), kernel_only=True),))
    fills = {(int(s["table"].flat[0]), int(s["len"].flat[0]))
             for s in scalar_sets(plan)}
    assert fills == {(t, l) for t in (0, PAGES - 1)
                     for l in (0, 15, 16, 17, 63)}


# ---------------------------------------------------------------------------
# pass 1: bounds
# ---------------------------------------------------------------------------

def test_bounds_catches_off_by_one_past_last_page():
    # the classic: indexing tbl[p] + 1 walks one page past the table's
    # worst-case (num_pages - 1) entry — only visible at the scalar
    # extreme, which is exactly what the fill model pins
    bad = _base_plan(inputs=(
        BlockOperand("pages", (PAGES, PAGE, D), jnp.float32, (1, PAGE, D),
                     lambda p, tbl: (tbl[p] + 1, 0, 0)),))
    vios = _only_fails(bad, "bounds")
    assert any("pages" in v.message and "out" not in v.message.split()[0]
               for v in vios)
    # in-bounds at fill 0: the violation must cite the max fill
    assert any(str(PAGES) in v.message for v in vios)


def test_bounds_catches_grid_overrun_without_scalars():
    bad = _base_plan(
        scalars=(),
        inputs=(BlockOperand("x", (ROWS, D), jnp.float32, (16, D),
                             lambda i: (i + 1, 0)),),
        outputs=(BlockOperand("o", (4, PAGE, D), jnp.float32,
                              (1, PAGE, D), lambda i: (i, 0, 0)),))
    vios = _only_fails(bad, "bounds")
    assert any("x" in v.message for v in vios)


def test_bounds_ok_for_partial_final_block():
    # 65 rows / block 16 -> 5 blocks, the last partial: still legal
    ok = _base_plan(
        scalars=(),
        inputs=(BlockOperand("x", (65, D), jnp.float32, (16, D),
                             lambda i: (i, 0)),),
        outputs=(BlockOperand("o", (4, PAGE, D), jnp.float32,
                              (1, PAGE, D), lambda i: (i, 0, 0)),))
    assert _passes(ok)["bounds"].ok


# ---------------------------------------------------------------------------
# pass 2: vmem
# ---------------------------------------------------------------------------

def test_vmem_catches_scratch_over_budget():
    bad = _base_plan(scratch=(((2048, 2048), jnp.float32),))  # 16 MiB
    vios = _only_fails(bad, "vmem")
    assert "exceeds budget" in vios[0].message
    assert estimate_vmem(bad) > 8 * 2 ** 20


def test_vmem_budget_is_configurable():
    plan = _base_plan()
    res = run_plan_audits(plan, "inj", vmem_budget=16)
    byname = {r.passname: r for r in res}
    assert not byname["vmem"].ok                # tiny budget trips it
    assert byname["bounds"].ok and byname["grid"].ok


# ---------------------------------------------------------------------------
# pass 3: revisit / race
# ---------------------------------------------------------------------------

def _revisit_plan(**over):
    """Grid (2, 3): the t axis folds onto one output block."""
    kw = dict(
        name="toy_accum",
        grid=(2, 3),
        scalars=(),
        inputs=(BlockOperand("x", (ROWS, 3 * D), jnp.float32, (32, D),
                             lambda i, t: (i, t)),),
        outputs=(BlockOperand("o", (ROWS, D), jnp.float32, (32, D),
                              lambda i, t: (i, 0)),),
        scratch=(),
        kernel=_kernel_with_when,
        accumulate={"o": "when-init-accumulate"},
        dimension_semantics=("parallel", "arbitrary"),
    )
    kw.update(over)
    return LaunchPlan(**kw)


def test_revisit_clean_accumulator_passes():
    byname = _passes(_revisit_plan())
    assert byname["revisit"].ok, \
        [v.message for v in byname["revisit"].violations]


def test_revisit_catches_undeclared_accumulation():
    vios = _only_fails(_revisit_plan(accumulate={}), "revisit")
    assert "last-write-wins" in vios[0].message


def test_revisit_catches_missing_pl_when_guard():
    vios = _only_fails(_revisit_plan(kernel=_kernel_plain), "revisit")
    assert "pl.when" in vios[0].message


def test_revisit_catches_parallel_race_axis():
    vios = _only_fails(
        _revisit_plan(dimension_semantics=("parallel", "parallel")),
        "revisit")
    assert "race" in vios[0].message


def test_revisit_catches_stale_declaration():
    # output visited once per grid step — declaring an accumulator lies
    bad = _revisit_plan(
        outputs=(BlockOperand("o", (ROWS, 3 * D), jnp.float32, (32, D),
                              lambda i, t: (i, t)),))
    vios = _only_fails(bad, "revisit")
    assert "never revisited" in vios[0].message


# ---------------------------------------------------------------------------
# pass 4: grid / arity
# ---------------------------------------------------------------------------

def test_grid_catches_index_map_arity_mismatch():
    bad = _base_plan(inputs=(
        BlockOperand("pages", (PAGES, PAGE, D), jnp.float32, (1, PAGE, D),
                     lambda p: (p, 0, 0)),))       # forgot the table arg
    vios = _only_fails(bad, "grid")
    assert "takes 1 args" in vios[0].message
    # bounds must note it skipped the operand, not crash on it
    assert any("arity" in n for n in _passes(bad)["bounds"].notes)


def test_grid_catches_unreferenced_scalar():
    bad = _base_plan(
        inputs=(BlockOperand("pages", (PAGES, PAGE, D), jnp.float32,
                             (1, PAGE, D), lambda p, tbl: (p, 0, 0)),))
    vios = _only_fails(bad, "grid")
    assert "never referenced" in vios[0].message


def test_grid_allows_kernel_only_scalar():
    ok = _base_plan(
        scalars=(ScalarOperand("table", (4,), jnp.int32,
                               max_value=PAGES - 1),
                 ScalarOperand("lengths", (4,), jnp.int32, max_value=63,
                               kernel_only=True)),
        inputs=(BlockOperand("pages", (PAGES, PAGE, D), jnp.float32,
                             (1, PAGE, D),
                             lambda p, tbl, ln: (tbl[p], 0, 0)),),
        outputs=(BlockOperand("o", (4, PAGE, D), jnp.float32,
                              (1, PAGE, D),
                              lambda p, tbl, ln: (p, 0, 0)),))
    assert _passes(ok)["grid"].ok


def test_grid_catches_block_rank_and_size():
    bad = _base_plan(outputs=(
        BlockOperand("o", (4, PAGE, D), jnp.float32, (1, PAGE, 2 * D),
                     lambda p, tbl: (p, 0, 0)),))
    vios = _only_fails(bad, "grid")
    assert "block dim" in vios[0].message


# ---------------------------------------------------------------------------
# the decode kernel's layer scalar
# ---------------------------------------------------------------------------

def _decode_plan(num_layers, page=16, fmt="int8"):
    return paged_attn_decode_plan(S=4, Hkv=2, G=2, D=16, page=page, maxp=4,
                                  num_pages=17, num_splits=2,
                                  kv_format=fmt, num_layers=num_layers)


@pytest.mark.parametrize("page", [16, 128])
@pytest.mark.parametrize("num_layers", [1, 5])
def test_decode_layer_scalar_proved_in_bounds(num_layers, page):
    """The stacked decode plan pins its ``layer`` scalar at 0 and
    ``L - 1``, every pool operand reads through it, and all four passes
    hold; ``num_layers=1`` is the one-layer (4-D pools) form."""
    plan = _decode_plan(num_layers, page)
    layer = {s.name: s for s in plan.scalars}["layer"]
    assert layer.shape == (1,) and not layer.kernel_only
    assert layer.max_value == num_layers - 1
    assert {int(f["layer"][0]) for f in scalar_sets(plan)} \
        == {0, num_layers - 1}
    pools = [op for op in plan.inputs if op.name != "q"]
    assert pools and all(op.shape[0] == num_layers for op in pools)
    byname = _passes(plan)
    assert all(r.ok for r in byname.values()), \
        {k: [v.message for v in r.violations] for k, r in byname.items()}


def test_bounds_catches_layer_past_the_stack():
    """An index map that reads one layer past ``layer`` walks off the
    stack at the scalar's max fill; only the bounds pass fires."""
    plan = _decode_plan(3)                  # 2 splits of 2 pages
    past = BlockOperand(
        "k_pages", plan.inputs[1].shape, jnp.int8, plan.inputs[1].block,
        lambda s, h, sp, p, pt, ln, ly: (ly[0] + 1, pt[s, sp * 2 + p], h,
                                         0, 0))
    assert plan.inputs[1].name == "k_pages"
    ops = (plan.inputs[0], past) + plan.inputs[2:]
    vios = _only_fails(dataclasses.replace(plan, inputs=ops), "bounds")
    assert any("k_pages" in v.message for v in vios)


# ---------------------------------------------------------------------------
# the shipped fleet
# ---------------------------------------------------------------------------

def test_registry_covers_every_kernel_and_format():
    rep = audit_registry()
    names = {l.split("/")[0] for l in rep["kernels"]}
    assert names == set(KERNEL_REGISTRY)
    fmts = {l.split("/")[1] for l in rep["kernels"]
            if l.startswith("paged_attn_decode/")}
    assert fmts == {"fp", "int8", "sc"}


def test_registry_audits_clean():
    rep = audit_registry()
    bad = {l: [v for p in c["passes"] for v in p["violations"]]
           for l, c in rep["kernels"].items() if not c["ok"]}
    assert rep["ok"] and not bad, bad


def test_registry_reports_vmem_within_budget():
    rep = audit_registry()
    for label, cell in rep["kernels"].items():
        assert 0 < cell["vmem_est"] <= rep["budget_bytes"], \
            (label, cell["vmem_est"])


# ---------------------------------------------------------------------------
# ANALYSIS.json schema stamp
# ---------------------------------------------------------------------------

def _analyze_mod():
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "tools" / "analyze.py"
    spec = importlib.util.spec_from_file_location("_analyze_tool", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_schema_stamp_round_trip(tmp_path):
    import json
    m = _analyze_mod()
    p = tmp_path / "ANALYSIS.json"
    p.write_text(json.dumps({"schema": m.ANALYSIS_SCHEMA}))
    assert m.check_artifact_schema(p) == m.ANALYSIS_SCHEMA
    p.write_text(json.dumps({"cells": {}}))     # pre-stamp artifact
    assert m.check_artifact_schema(p) == 1
    assert m.check_artifact_schema(tmp_path / "missing.json") is None


def test_unknown_schema_fails_loudly(tmp_path):
    import json
    m = _analyze_mod()
    p = tmp_path / "ANALYSIS.json"
    p.write_text(json.dumps({"schema": m.ANALYSIS_SCHEMA + 1}))
    with pytest.raises(SystemExit, match="unknown ANALYSIS.json schema"):
        m.check_artifact_schema(p)


# ---------------------------------------------------------------------------
# hygiene (satellite: no tracked bytecode)
# ---------------------------------------------------------------------------

def test_hygiene_scan_flags_bytecode_paths():
    vios = hygiene_scan(["src/repro/a.py",
                         "src/repro/__pycache__/a.cpython-310.pyc",
                         "tools/b.pyc", "README.md"])
    assert sorted(v.file for v in vios) == \
        ["src/repro/__pycache__/a.cpython-310.pyc", "tools/b.pyc"]
    assert all(v.rule == "hygiene" for v in vios)


def test_repo_tracks_no_bytecode():
    assert hygiene_repo() == []
