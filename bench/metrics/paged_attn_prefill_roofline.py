"""Least time of the window's paged_attn_prefill calls (bench/cost, live
lengths: padded lanes and rows count for nothing) over the kernel's
summed device time in the trace, percent."""

from bench.stats import in_window, least_seconds, prefill_calls


def compute(run):
    t = (run.trace or {}).get("kernel_s", {}).get("paged_attn_prefill")
    calls = [c for s in in_window(run) for c in prefill_calls(run, s)]
    if not t or not calls:
        return None
    return 100.0 * least_seconds(run, calls) / t
