"""The trace reduction, on a small synthetic trace."""

import pytest

from bench.trace import op_name, reduce_events

MS = 1_000_000                               # ns


def test_busy_idle_kernels_modules_and_gaps():
    host = [("bench.step", 0, 40 * MS), ("bench.wait", 40 * MS, 60 * MS),
            ("bench.step", 60 * MS, 100 * MS)]
    ops = {"/device:TPU:0": [
        ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", 0, 10 * MS),
        ("%paged_attn_decode_pallas.3 = bf16[8]{0} custom-call()",
         5 * MS, 15 * MS),                          # overlaps fusion.1
        ("%paged_attn_prefill_pallas = f32[2] custom-call()",
         20 * MS, 30 * MS),
        # a consumer of the kernel's output is not the kernel
        ("%fusion.7 = bf16[8] fusion(bf16[8] %paged_attn_decode_pallas.3)",
         70 * MS, 90 * MS),
        ("%while.2 = (s32[]) while((s32[]) %t)", 70 * MS, 90 * MS),
        ("%copy.2 = s8[4] copy(s8[4] %x)", 95 * MS, 120 * MS),  # past end
    ]}
    mods = {"/device:TPU:0": [("jit__prefill_batched_fn", 0, 30 * MS),
                              ("jit__decode_fn", 70 * MS, 120 * MS)]}
    t = reduce_events(ops, mods, host, ("paged_attn_decode",
                                        "paged_attn_prefill"),
                      ("prefill", "decode"))
    assert t["window_s"] == pytest.approx(0.1)
    # busy: [0, 15] + [20, 30] + [70, 90] + [95, 100] = 50 ms
    assert t["busy_s"] == pytest.approx(0.05)
    assert t["idle_share"] == pytest.approx(0.5)
    assert t["kernel_s"]["paged_attn_decode"] == pytest.approx(0.01)
    assert t["kernel_s"]["paged_attn_prefill"] == pytest.approx(0.01)
    assert t["module_s"]["prefill"] == pytest.approx(0.03)
    assert t["module_s"]["decode"] == pytest.approx(0.03)
    # the longest gap [30, 70] has its middle (50) in the host's wait
    assert t["idle_gaps"][0] == ["bench.wait", pytest.approx(0.04)]
    assert t["idle_gaps"][1][0] == "bench.step"
    assert t["device_ops"][0] == ["fusion", pytest.approx(0.03)]
    assert "while" not in dict(t["device_ops"])
    assert len(t["device_ops"]) <= 10 and len(t["idle_gaps"]) <= 10


def test_op_names_drop_operands_and_numbers():
    assert op_name("%fusion.12 = bf16[2] fusion(bf16[2] %a.1)") == "fusion"
    assert op_name("%paged_attn_decode_pallas.3 = f32[1] custom-call()") \
        == "paged_attn_decode_pallas"
    assert op_name("copy.111") == "copy"


def test_busy_is_averaged_over_devices():
    host = [("bench.step", 0, 10 * MS)]
    ops = {"/device:TPU:0": [("a", 0, 10 * MS)],
           "/device:TPU:1": [("a", 0, 5 * MS)]}
    t = reduce_events(ops, {}, host)
    assert t["busy_s"] == pytest.approx(0.0075)


def test_a_trace_without_the_benchmark_spans_is_refused():
    with pytest.raises(ValueError):
        reduce_events({"/device:TPU:0": [("a", 0, 1)]}, {}, [])
