"""Model operations of the window's prompts over the device time of the
prefill programs in the trace, over the int8 peak, in percent: the whole
prefill step's share of the chip, which bounds what its kernels gain."""

from bench.stats import in_window, step_model_ops


def compute(run):
    t = (run.trace or {}).get("module_s", {}).get("prefill")
    ops = sum(step_model_ops(run, s)[0] for s in in_window(run))
    if not t or not ops:
        return None
    return 100.0 * ops / (t * run.peaks["int8_ops_per_s"])
