"""Least time of the window's paged_attn_decode calls (bench/cost, live
lengths) over the kernel's summed device time in the trace, percent."""

from bench.stats import decode_calls, in_window, least_seconds


def compute(run):
    t = (run.trace or {}).get("kernel_s", {}).get("paged_attn_decode")
    calls = [c for s in in_window(run) for c in decode_calls(run, s)]
    if not t or not calls:
        return None
    return 100.0 * least_seconds(run, calls) / t
