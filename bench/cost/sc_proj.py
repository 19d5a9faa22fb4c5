"""Least time of one engine step's SC projections (the ``sc_linear``
scope: every q/k/v/o and MLP projection of every layer, and the LM
head).

Counted as the datapath needs it, however it is implemented: a ternary
weight holds log2(3) bits and is read once per step; each token's
activations enter and leave a projection as int8 codes, one byte an
element; a projection does ``2 x in x out`` operations per token, at
the int8 peak.  Every token of the step passes every layer; the LM head
sees the decoded lanes and, of a prompt, only its last token (the one
that gives the first output token), as bench/cost/model.py counts.
Padded lanes and positions count for nothing.  The least time of a step
is the larger of its bytes over HBM bandwidth and its operations over
the int8 peak.
"""

from __future__ import annotations

import math

from bench.cost.model import head_params, layer_params

__all__ = ["TERNARY_BYTES", "layer_io_bytes", "step_ops_bytes",
           "step_least_seconds"]

TERNARY_BYTES = math.log2(3) / 8       # bytes of one ternary weight


def layer_io_bytes(d) -> int:
    """int8 activation bytes one token reads and writes through one
    layer's seven projections; ``d`` is a bench Dims."""
    q, kv = d.hq * d.dh, d.hkv * d.dh
    return ((d.d + q) + 2 * (d.d + kv) + (q + d.d)
            + 2 * (d.d + d.ff) + (d.ff + d.d))


def step_ops_bytes(d, layer_tokens: int,
                   head_tokens: int) -> tuple[float, float]:
    """(operations, bytes) of one step that passes ``layer_tokens``
    through the layers and ``head_tokens`` through the LM head."""
    if layer_tokens <= 0:
        return 0.0, 0.0
    ops = 2.0 * (layer_tokens * layer_params(d)
                 + head_tokens * head_params(d))
    byt = ((layer_params(d) + head_params(d)) * TERNARY_BYTES
           + layer_tokens * d.layers * layer_io_bytes(d)
           + head_tokens * (d.d + d.vocab))
    return ops, byt


def step_least_seconds(d, peaks: dict, prompt_lens, decode_lanes: int
                       ) -> float:
    """Least time of a step that admits prompts of ``prompt_lens``
    tokens and decodes ``decode_lanes`` real lanes."""
    ops, byt = step_ops_bytes(d, sum(prompt_lens) + decode_lanes,
                              len(prompt_lens) + decode_lanes)
    return max(byt / peaks["hbm_bytes_per_s"],
               ops / peaks["int8_ops_per_s"])
