"""Each metric's arithmetic, on a synthetic event log."""

import copy
import dataclasses

import pytest
from conftest import PEAKS

from bench import spec
from bench.cost import model, paged_attention
from bench.serve import ReqLog, RunLog, StepLog

DENSE = spec.family("dense_decoder")
DIMS = DENSE.Dims({"num_hidden_layers": 2, "hidden_size": 64,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 16, "intermediate_size": 128,
                   "vocab_size": 100, "rms_norm_eps": 1e-6,
                   "rope_theta": 1e4}, 128)


def metric(name, run):
    return spec.metric_fn(name)(run)


def steady_log(stall_at=None, stall=0.0, seconds=10.0):
    """Two requests decoding one token every 10 ms; an optional host
    stall delays every step after ``stall_at``."""
    log = RunLog(seconds=seconds, max_slots=4, page_size=8, chunk=8,
                 kv_format="int8", dims=DIMS, family=DENSE, peaks=PEAKS)
    a, b = ReqLog(0.0, [1] * 10, 2000), ReqLog(0.5, [1] * 20, 2000)
    log.reqs = {0: a, 1: b}
    t, shift = 0.0, 0.0
    while t < seconds + 1:
        if stall_at is not None and t >= stall_at and shift == 0.0:
            shift = stall
        t0, t1 = t + shift, t + shift + 0.01
        st = StepLog(t0, t1)
        for r, n in ((a, 10), (b, 20)):
            if r.due <= t0:
                if not r.token_times:
                    r.submit = r.admit = t0
                    st.admitted.append(n)
                else:
                    st.decode_lens.append(n + len(r.token_times) - 1)
                r.token_times.append(t1)
        log.steps.append(st)
        t += 0.01
    return log


def test_a_stall_raises_itl_p95_and_lowers_output_tps():
    calm = steady_log()
    stalled = steady_log(stall_at=2.0, stall=0.8)
    assert metric("itl_p95_ms", calm) == pytest.approx(10.0)
    assert metric("output_tps", stalled) < metric("output_tps", calm)
    many = steady_log()
    for s in many.steps[::15]:   # every 15th step stalls 100 ms
        idx = many.steps.index(s)
        for later in many.steps[idx:]:
            later.t0 += 0.1
            later.t1 += 0.1
    for r in many.reqs.values():
        r.token_times = []
    for s in many.steps:
        for r in many.reqs.values():
            if r.due <= s.t0:
                r.token_times.append(s.t1)
    assert metric("itl_p95_ms", many) > 2 * metric("itl_p95_ms", calm)
    assert metric("output_tps", many) < metric("output_tps", calm)


def test_a_censored_request_counts_in_ttft_p95():
    log = steady_log()
    base = metric("ttft_p95_ms", log)
    # the log's family is a module, shared by the copy
    late = dataclasses.replace(log, reqs=copy.deepcopy(log.reqs))
    for i in range(2, 40):       # never served: censored at the window end
        late.reqs[i] = ReqLog(1.0, [1] * 5, 4)
    assert metric("ttft_p95_ms", late) == pytest.approx(9000.0, rel=0.01)
    assert metric("ttft_p95_ms", late) > base
    assert metric("queue_wait_p95_ms", late) == pytest.approx(9000.0,
                                                             rel=0.01)


def test_output_tps_counts_only_tokens_inside_the_window():
    log = steady_log(seconds=5.0)
    inside = sum(t <= 5.0 for r in log.reqs.values() for t in r.token_times)
    assert metric("output_tps", log) == pytest.approx(inside / 5.0)


def test_scheduler_and_step_metrics():
    log = steady_log()
    # one lane for the first half second, two after
    assert metric("batch_occupancy", log) == pytest.approx(0.4875, rel=0.01)
    assert metric("decode_step_ms", log) == pytest.approx(10.0)
    assert metric("gen_lag_p95_ms", log) == pytest.approx(0.0, abs=1e-6)
    log.window_compiles = 3
    assert metric("window_compiles", log) == 3.0
    log.setup_s = 12.5
    assert metric("setup_s", log) == 12.5


def test_mfu_is_model_operations_over_window_and_int8_peak():
    log = steady_log()
    ops = 0.0
    for s in log.steps:
        if s.t1 <= log.seconds:
            ops += sum(model.prefill_ops(DIMS, p) for p in s.admitted)
            ops += sum(model.decode_ops(DIMS, n) for n in s.decode_lens)
    want = 100 * ops / (log.seconds * PEAKS["int8_ops_per_s"])
    assert metric("mfu", log) == pytest.approx(want)
    assert model.decode_ops(DIMS, 9) - model.decode_ops(DIMS, 8) == \
        4 * DIMS.layers * DIMS.hq * DIMS.dh


def test_trace_metrics_read_nothing_without_a_trace():
    log = steady_log()
    for name in ("device_idle_share", "paged_attn_decode_roofline",
                 "paged_attn_prefill_roofline", "mfu.prefill"):
        assert metric(name, log) is None


def test_kernel_rooflines_from_trace_time():
    log = steady_log()
    calls = [paged_attention.decode_call(s.decode_lens, 4, 2, 16, "int8")
             for s in log.steps if s.decode_lens and s.t1 <= log.seconds]
    least = DIMS.layers * sum(
        max(b / PEAKS["hbm_bytes_per_s"], o / PEAKS["bf16_flops_per_s"])
        for o, b in calls)
    log.trace = {"kernel_s": {"paged_attn_decode": 4 * least,
                              "paged_attn_prefill": 1.0},
                 "module_s": {"prefill": 0.5}, "idle_share": 0.25}
    assert metric("paged_attn_decode_roofline", log) == pytest.approx(25.0)
    assert 0 < metric("paged_attn_prefill_roofline", log) < 100
    assert metric("device_idle_share", log) == 0.25
    assert metric("mfu.prefill", log) > 0


def test_paged_attention_costs_count_live_positions_only():
    ops, byt = paged_attention.decode_call([0, 9], 4, 2, 16, "int8")
    assert ops == 4 * 4 * 16 * (1 + 10)
    assert byt == (1 + 10) * 2 * 2 * (16 + 4) + 2 * 2 * 4 * 16 * 2
    # a lane whose prompt ended before the chunk adds nothing
    assert paged_attention.prefill_call([5], 8, 8, 4, 2, 16, "int8") \
        == (0.0, 0.0)
    ops, _ = paged_attention.prefill_call([12], 8, 8, 4, 2, 16, "int8")
    assert ops == 4 * 4 * 16 * sum(t + 1 for t in range(8, 12))
