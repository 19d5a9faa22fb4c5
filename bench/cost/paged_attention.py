"""Least operations and bytes of one call of each paged-attention kernel.

Counted from live lengths only: padded lanes, padded positions and
pages past a request's length are work the algorithm does not need, so
they lower the kernel's roofline share instead of raising its count.

Per position attended, per query head: ``2 * D`` for ``q . k`` and
``2 * D`` for ``p . v``.  Bytes: each live K and V row once (int8 codes,
1 byte an element, plus one f32 scale per position and KV head for each
of K and V), the queries read and the outputs written in bf16.
"""

from __future__ import annotations

__all__ = ["kv_row_bytes", "decode_call", "prefill_call"]

QO_BYTES = 2                       # bf16 queries and outputs


def kv_row_bytes(hkv: int, dh: int, kv_format: str) -> int:
    """Bytes of one cached position's K and V over all KV heads."""
    if kv_format == "int8":
        return hkv * 2 * (dh + 4)
    if kv_format == "fp":
        return hkv * 2 * dh * 2
    if kv_format == "sc":
        return hkv * 2 * (2 * dh + 4)
    raise ValueError(kv_format)


def decode_call(lengths, hq: int, hkv: int, dh: int,
                kv_format: str) -> tuple[float, float]:
    """One layer's decode call: each lane holds ``length`` cached tokens
    before the step and attends ``length + 1`` positions (its new token
    included).  Returns (operations, bytes)."""
    ops = byt = 0.0
    row = kv_row_bytes(hkv, dh, kv_format)
    for n in lengths:
        ctx = n + 1
        ops += 4.0 * hq * dh * ctx
        byt += ctx * row + 2 * hq * dh * QO_BYTES
    return ops, byt


def prefill_call(prompt_lens, start: int, chunk: int, hq: int, hkv: int,
                 dh: int, kv_format: str) -> tuple[float, float]:
    """One layer's call for the chunk ``[start, start + chunk)`` of an
    admission group: query row ``t < prompt_len`` attends ``t + 1``
    positions, and the live K/V rows are ``min(prompt_len, start +
    chunk)``.  Returns (operations, bytes)."""
    ops = byt = 0.0
    row = kv_row_bytes(hkv, dh, kv_format)
    for p in prompt_lens:
        rows = max(0, min(chunk, p - start))
        if rows == 0:
            continue
        # sum of (t + 1) for t in [start, start + rows)
        attended = rows * start + rows * (rows + 1) / 2
        ops += 4.0 * hq * dh * attended
        byt += min(p, start + chunk) * row + 2 * rows * hq * dh * QO_BYTES
    return ops, byt
