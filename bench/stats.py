"""Arithmetic the metric readers share (bench/metrics/*.py)."""

from __future__ import annotations

import numpy as np

from bench.cost import paged_attention
from bench.serve import prefill_bucket

__all__ = ["p95_ms", "in_window", "prefill_calls", "decode_calls",
           "least_seconds", "step_model_ops"]


def p95_ms(values_s) -> float | None:
    """95th percentile (numpy's linear interpolation) in milliseconds."""
    v = list(values_s)
    return float(np.percentile(v, 95) * 1e3) if v else None


def in_window(run):
    """Steps whose results reached the host before the window closed."""
    return [s for s in run.steps if s.t1 <= run.seconds]


def prefill_calls(run, step):
    """(operations, bytes) of every paged_attn_prefill call of a step: one
    per chunk of the admission group per attention layer (bench/cost)."""
    if not step.admitted:
        return []
    d = run.dims
    _, L, _, chunk = prefill_bucket(step.admitted, run.page_size,
                                    run.max_slots, run.chunk)
    return [paged_attention.prefill_call(step.admitted, s, chunk, d.hq,
                                         d.hkv, d.dh, run.kv_format)
            for s in range(0, L, chunk)] * d.attn_layers


def decode_calls(run, step):
    if not step.decode_lens:
        return []
    d = run.dims
    return [paged_attention.decode_call(step.decode_lens, d.hq, d.hkv,
                                        d.dh, run.kv_format)] * d.attn_layers


def least_seconds(run, calls) -> float:
    """Roofline time: per call the larger of bytes over HBM bandwidth and
    operations over the bf16 peak (the kernels' dots run on bf16/f32
    operands), summed."""
    pk = run.peaks
    return sum(max(b / pk["hbm_bytes_per_s"], o / pk["bf16_flops_per_s"])
               for o, b in calls)


def step_model_ops(run, step) -> tuple[float, float]:
    """(prefill, decode) model operations of one step, as the run's model
    family counts them (bench/families)."""
    d, fam = run.dims, run.family
    pre = sum(fam.prefill_ops(d, p) for p in step.admitted)
    dec = sum(fam.decode_ops(d, n) for n in step.decode_lens)
    return pre, dec
