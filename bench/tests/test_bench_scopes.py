"""The reduction of the program's spans and scopes (bench/scopes.py), on
synthetic events, and the projections' least time (bench/cost/sc_proj)."""

import math

import pytest
from conftest import PEAKS

from bench import scopes, spec
from bench.cost import model, sc_proj
from bench.trace import reduce_events

MS = 1_000_000                               # ns
DEV = "/device:TPU:0"


def _spans():
    """Two bench steps around engine steps, a wait between them.  The
    first step's device idles under decode.prepare (10-14) and under
    decode.sync (30.5-32)."""
    return [
        ("bench.step", 0, 40 * MS),
        ("engine.step", 1 * MS, 39 * MS),
        ("engine.admit", 1 * MS, 2 * MS),
        ("engine.grow", 2 * MS, 3 * MS),
        ("engine.decode.prepare", 3 * MS, 14 * MS),
        ("engine.decode.dispatch", 14 * MS, 15 * MS),
        ("engine.decode.sync", 15 * MS, 32 * MS),
        ("engine.decode.commit", 32 * MS, 38 * MS),
        ("bench.wait", 40 * MS, 60 * MS),
        ("bench.step", 60 * MS, 100 * MS),
        ("engine.step", 60 * MS, 100 * MS),
        ("engine.admit", 60 * MS, 90 * MS),
        ("engine.prefill", 61 * MS, 70 * MS),
        ("engine.prefill.sync", 70 * MS, 90 * MS),
    ]


def _ops():
    return {DEV: [
        # (event name, start, end, op_name, program)
        ("%fusion.1 = bf16[8] fusion()", 0, 10 * MS,
         "jit(_decode_fn)/layers/while/body/sc_linear/sc_linear/dot_general",
         "jit__decode_fn"),
        ("%while.3 = (s32[]) while()", 14 * MS, 30 * MS,
         "jit(_decode_fn)/layers/while", "jit__decode_fn"),
        ("%paged_attn_decode_pallas.2 = bf16[8] custom-call()",
         14 * MS, 20 * MS,
         "jit(_decode_fn)/layers/while/body/closed_call/paged_attn/"
         "jit(paged_attn_decode_pallas)", "jit__decode_fn"),
        # an XLA copy: no op_name, inside the layer loop
        ("%copy.7 = s8[4] copy()", 20 * MS, 28 * MS, "", "jit__decode_fn"),
        # under the loop's name but no scope of ours
        ("%add.2 = f32[4] add()", 28 * MS, 30 * MS,
         "jit(_decode_fn)/layers/while/body/add", "jit__decode_fn"),
        ("%fusion.4 = s32[8] fusion()", 30 * MS, 30 * MS + MS // 2,
         "jit(_decode_fn)/sampler/argmax", "jit__decode_fn"),
        # outside any loop, no op_name
        ("%copy.9 = s8[4] copy()", 75 * MS, 80 * MS, "",
         "jit__prefill_batched_fn"),
        ("%fusion.5 = s8[4] fusion()", 80 * MS, 85 * MS,
         "jit(_prefill_batched_fn)/chunks/while/body/layers/while/body/"
         "kv_write/scatter", "jit__prefill_batched_fn"),
    ]}


def test_idle_goes_to_the_innermost_open_span_and_adds_up():
    ops = _ops()
    r = scopes.reduce_program(ops, _spans())
    idle = r["idle_by_span"]
    # busy: [0,10] [14,30.5] [75,85]; idle 10-14 under prepare, 30.5-32
    # under sync, 32-38 commit, 38-39 engine.step, 39-40 bench.step,
    # 40-60 wait, 60-61 admit, 61-70 prefill, 70-75 and 85-90 prefill
    # sync, 90-100 engine.step
    assert idle["engine.decode.prepare"] == pytest.approx(0.004)
    assert idle["engine.decode.sync"] == pytest.approx(0.0015)
    assert idle["engine.decode.commit"] == pytest.approx(0.006)
    assert idle["engine.step"] == pytest.approx(0.011)
    assert idle["bench.step"] == pytest.approx(0.001)
    assert idle["bench.wait"] == pytest.approx(0.02)
    assert idle["engine.admit"] == pytest.approx(0.001)
    assert idle["engine.prefill"] == pytest.approx(0.009)
    assert idle["engine.prefill.sync"] == pytest.approx(0.01)
    # the whole idle time, as bench/trace.py counts it on the same events
    old = reduce_events({d: [(n, s, e) for n, s, e, _, _ in v]
                         for d, v in ops.items()}, {},
                        [sp for sp in _spans()
                         if sp[0].startswith("bench.")])
    assert r["idle_s"] == pytest.approx(old["window_s"] - old["busy_s"])
    assert r["window_s"] == pytest.approx(old["window_s"])


def test_idle_outside_every_span_is_none():
    spans = [("bench.step", 0, 10 * MS), ("bench.step", 20 * MS, 30 * MS)]
    ops = {DEV: [("%a = f32[] add()", 0, 10 * MS, "", "p"),
                 ("%b = f32[] add()", 20 * MS, 30 * MS, "", "p")]}
    r = scopes.reduce_program(ops, spans)
    assert r["idle_by_span"] == {"none": pytest.approx(0.01)}


def test_device_time_by_scope_and_loop():
    by, pairs = scopes.device_by_scope(_ops(), 0, 100 * MS)
    assert by == pytest.approx({
        "sc_linear": 0.01, "paged_attn": 0.006,
        # the copy takes the loop around it; the add its own path
        "jit__decode_fn/layers": 0.008 + 0.002,
        "sampler": 0.0005, "jit__prefill_batched_fn": 0.005,
        "kv_write": 0.005})
    assert not any(op.startswith("while") for _, op, _ in pairs)
    assert ["jit__decode_fn/layers", "copy:s8",
            pytest.approx(0.008)] in pairs
    assert ["sc_linear", "fusion:bf16", pytest.approx(0.01)] in pairs


def test_scope_of_picks_the_innermost_scope():
    assert scopes.scope_of("a/sc_linear/b/kv_write/c", "p") == "kv_write"
    assert scopes.scope_of("", "p", "jit(f)/chunks/while/body/layers/"
                           "while") == "p/layers"
    assert scopes.scope_of("jit(f)/chunks/while/body/mul", "p") \
        == "p/chunks"
    assert scopes.scope_of("", "p") == "p"


def test_step_host_time_leaves_out_the_syncs():
    steps = scopes.step_host_s(_spans())
    assert [k for k, _ in steps] == ["decode", "prefill"]
    assert steps[0][1] == pytest.approx((38 - 17) * 1e-3)
    assert steps[1][1] == pytest.approx((40 - 20) * 1e-3)
    r = scopes.reduce_program(_ops(), _spans())
    assert r["step_host_ms"] == pytest.approx(21.0)
    assert r["spans_per_step"] == pytest.approx((7 + 4) / 2)


def test_a_trace_without_the_benchmark_spans_is_refused():
    with pytest.raises(ValueError):
        scopes.reduce_program(_ops(), [("engine.step", 0, 1)])


DENSE = spec.family("dense_decoder")
GRANITE = DENSE.Dims({"num_hidden_layers": 40, "hidden_size": 2048,
                      "num_attention_heads": 32, "num_key_value_heads": 8,
                      "head_dim": 64, "intermediate_size": 8192,
                      "vocab_size": 49155, "rms_norm_eps": 1e-6,
                      "rope_theta": 1e4}, 49408)


def test_projection_least_time_at_granite_widths():
    # per layer: q 2048x2048, k and v 2048x512, o 2048x2048, gate and up
    # 2048x8192, down 8192x2048; head 2048 x 49155 (real vocabulary)
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    weights = 40 * per_layer + 2048 * 49155
    assert weights == 2_533_365_760
    assert model.layer_params(GRANITE) + model.head_params(GRANITE) \
        == weights
    io = (2048 + 2048) + 2 * (2048 + 512) + (2048 + 2048) \
        + 2 * (2048 + 8192) + (8192 + 2048)
    assert sc_proj.layer_io_bytes(GRANITE) == io
    # a decode step of 16 lanes is bound by the weights' bytes
    ops, byt = sc_proj.step_ops_bytes(DENSE, GRANITE, 16, 16)
    assert ops == 2 * 16 * weights
    assert byt == pytest.approx(weights * math.log2(3) / 8
                                + 16 * 40 * io + 16 * (2048 + 49155))
    least = sc_proj.step_least_seconds(DENSE, GRANITE, PEAKS, [], 16)
    assert least == pytest.approx(byt / PEAKS["hbm_bytes_per_s"])
    assert 0.6e-3 < least < 0.65e-3
    # a 2048-token prompt is bound by the int8 peak; its LM head runs once
    ops, _ = sc_proj.step_ops_bytes(DENSE, GRANITE, 2048, 1)
    assert ops == 2 * (2048 * 40 * per_layer + 2048 * 49155)
    assert sc_proj.step_least_seconds(DENSE, GRANITE, PEAKS, [2048], 0) \
        == pytest.approx(ops / PEAKS["int8_ops_per_s"])
    assert sc_proj.step_ops_bytes(DENSE, GRANITE, 0, 0) == (0.0, 0.0)


def test_the_five_readings_of_a_traced_window():
    from bench.breakdown import readings
    from bench.serve import RunLog, StepLog
    log = RunLog(seconds=1.0, max_slots=16, dims=GRANITE, family=DENSE,
                 peaks=PEAKS)
    log.steps = [StepLog(0.0, 0.2, admitted=[300, 500]),
                 StepLog(0.2, 0.3, decode_lens=[300, 500]),
                 StepLog(0.9, 1.1, decode_lens=[301])]    # ends outside
    counters = {"prefill_tokens": 800, "prefill_tokens_padded": 2 * 512,
                "pages_in_use_peak": 12, "pages_total": 256}
    least = (sc_proj.step_least_seconds(DENSE, GRANITE, PEAKS, [300, 500],
                                        0)
             + sc_proj.step_least_seconds(DENSE, GRANITE, PEAKS, [], 2))
    prog = {"window_s": 1.0, "step_host_ms": 3.5,
            "idle_by_span": {"bench.wait": 0.3, "engine.decode.prepare":
                             0.02, "engine.step": 0.03, "none": 0.01},
            "device_by_scope": {"sc_linear": 4 * least}}
    r = readings(log, counters, prog)
    assert r == pytest.approx({
        "engine_idle_share": 0.05, "step_host_ms": 3.5,
        "prefill_token_use": 800 / 1024, "sc_proj_roofline": 25.0,
        "kv_pages_peak_share": 12 / 256})
    prog["device_by_scope"] = {}
    counters["prefill_tokens_padded"] = 0
    r = readings(log, counters, prog)
    assert r["sc_proj_roofline"] is None
    assert r["prefill_token_use"] is None


DECODE_HLO = """HloModule jit__decode_fn, is_scheduled=true

%body (p: (s32[], s8[4])) -> (s32[], s8[4]) {
  %copy.133 = s8[1,257,32,128,64]{4,2,3,1,0:T(8,128)(4,1)} copy(%constant_dynamic-slice_fusion.39), metadata={op_name="jit(_decode_fn)/layers/while/body/dynamic_slice" stack_frame_id=9}
  %copy.137 = s8[257,32,128,64]{3,2,1,0:T(8,128)(4,1)} copy(%fusion.187), metadata={op_name="jit(_decode_fn)/layers/while/body/closed_call/kv_write/scatter" stack_frame_id=162}
  %copy-start.3 = (bf16[16,64]{1,0}, bf16[16,64]{1,0:S(1)}, u32[]{:S(2)}) copy-start(%fusion.175)
}

ENTRY %main (a: s8[4]) -> s8[4] {
  %while.5 = (s32[]{:T(128)}, /*index=1*/s8[4]{0}) while(%tuple), condition=%cond, body=%body, metadata={op_name="jit(_decode_fn)/layers/while" stack_frame_id=3}
  ROOT %fusion.9 = s32[16]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(_decode_fn)/sampler/argmax" stack_frame_id=5}
}
"""
# the same program at another bucket: one instruction name, another op
OTHER_HLO = """HloModule jit__decode_fn, is_scheduled=true
ENTRY %main (a: s8[4]) -> s8[4] {
  ROOT %fusion.9 = s32[8]{0} fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(_decode_fn)/sc_linear/dot_general" stack_frame_id=5}
}
"""


def test_op_names_come_from_the_compiled_programs():
    from bench.scopes import _in_programs, _op_path, hlo_op_names
    hlo = hlo_op_names([DECODE_HLO, OTHER_HLO])
    prog = "jit__decode_fn"
    # the profile's event names: the instruction's text, no metadata
    ev = ("%copy.133 = s8[1,257,32,128,64]{4,2,3,1,0:T(8,128)(4,1)} "
          "copy(s8[1,257,32,128,64]{4,2,3,1,0:T(8,128)(4,1)} "
          "%constant_dynamic-slice_fusion.39)")
    assert _op_path(ev, prog, hlo) == \
        "jit(_decode_fn)/layers/while/body/dynamic_slice"
    assert _op_path("%copy.137 = s8[257,32,128,64]{3,2,1,0:T(8,128)(4,1)} "
                    "copy(%fusion.187)", prog, hlo).endswith("kv_write/scatter")
    assert _op_path("%while.5 = (s32[]{:T(128)}, s8[4]{0}) while(%t)",
                    prog, hlo) == "jit(_decode_fn)/layers/while"
    # one name in two programs: the result type tells them apart
    assert _op_path("%fusion.9 = s32[16]{0} fusion(%x)", prog, hlo) \
        == "jit(_decode_fn)/sampler/argmax"
    assert _op_path("%fusion.9 = s32[8]{0} fusion(%x)", prog, hlo) \
        == "jit(_decode_fn)/sc_linear/dot_general"
    assert _op_path("%fusion.9 = s32[4]{0} fusion(%x)", prog, hlo) == ""
    # XLA's own copies carry no op_name; an unknown program neither
    assert _op_path("%copy-start.3 = (bf16[16,64]{1,0}) copy-start(%f)",
                    prog, hlo) == ""
    assert _op_path(ev, "jit__prefill_batched_fn", hlo) == ""
    # where a profile keeps the metadata in the name, it is read there
    assert _op_path('%a.1 = f32[] add(), metadata={op_name="jit(f)/x"}',
                    "?", {}) == "jit(f)/x"
    mods = [("jit__decode_fn(7)", 0, 10), ("jit__prefill_batched_fn(3)",
                                            20, 30)]
    ops = [("a", 1, 2), ("b", 12, 13), ("c", 25, 26)]
    assert _in_programs(ops, mods) == \
        ["jit__decode_fn", "?", "jit__prefill_batched_fn"]
    assert _in_programs(ops, []) == ["?"] * 3