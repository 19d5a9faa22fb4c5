"""Model operations per token of a dense decoder (what ``mfu`` counts).

``2 x`` the projection parameters a token passes through, plus attention
over its live context (``4 * Hq * Dh`` per position attended, per
layer).  A prompt token passes the LM head only where the model needs
its logits: the last one of the prompt, which gives the first output
token; every decode token passes it.  Padding, re-quantization and the
embedding lookup are not model operations.
"""

from __future__ import annotations

__all__ = ["layer_params", "head_params", "prefill_ops", "decode_ops"]


def layer_params(d) -> int:
    """Projection parameters of all layers; ``d`` is a bench Dims."""
    attn = d.d * d.hq * d.dh + 2 * d.d * d.hkv * d.dh + d.hq * d.dh * d.d
    return d.layers * (attn + 3 * d.d * d.ff)


def head_params(d) -> int:
    return d.d * d.vocab


def _attn(d, positions_attended: float) -> float:
    return 4.0 * d.layers * d.hq * d.dh * positions_attended


def prefill_ops(d, prompt_len: int) -> float:
    """A whole prompt: every token's layers, attention over its causal
    prefix, and the LM head once."""
    attended = prompt_len * (prompt_len + 1) / 2
    return (2.0 * layer_params(d) * prompt_len + 2.0 * head_params(d)
            + _attn(d, attended))


def decode_ops(d, length: int) -> float:
    """One decode token with ``length`` tokens already cached."""
    return (2.0 * (layer_params(d) + head_params(d))
            + _attn(d, length + 1))
