"""Model operations of every prefill and decode token of the window's
steps (bench/cost/model.py), over the window, over the int8 peak of the
device kind (the sc_int projections run int8), in percent."""

from bench.stats import in_window, step_model_ops


def compute(run):
    ops = sum(sum(step_model_ops(run, s)) for s in in_window(run))
    return 100.0 * ops / (run.seconds * run.peaks["int8_ops_per_s"])
