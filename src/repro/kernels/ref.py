"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth).

``ternary_matmul_ref`` is additionally proven equivalent to the bit-exact
multiplier+BSN circuit simulation in tests/test_hwmodel_sc_layers.py, so
the chain  Pallas kernel == this oracle == the silicon datapath  is closed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.kv_quant import kv_dequant

__all__ = ["ternary_matmul_ref", "bsn_sort_ref", "si_epilogue_ref",
           "gather_pages", "gather_pages_dequant", "paged_attn_decode_ref",
           "paged_attn_prefill_ref", "paged_attn_verify_ref"]


def si_epilogue_ref(sum_q: jax.Array, thresholds_q: jax.Array) -> jax.Array:
    """SI activation on accumulated sums (q domain).

    thresholds_q: (N, out_bsl) int32, ascending along the last axis.
    out_q = #{j : sum_q >= t_j} - out_bsl/2.
    """
    t = thresholds_q.astype(jnp.int32)
    out_counts = jnp.sum(sum_q[..., None] >= t, axis=-1, dtype=jnp.int32)
    return out_counts - t.shape[-1] // 2


def ternary_matmul_ref(x_q: jax.Array, w_int: jax.Array,
                       thresholds_q: jax.Array | None = None) -> jax.Array:
    """int8 activation levels x int8 ternary weights -> int32 sums.

    Functional identity with the SC datapath: the int32 accumulate equals
    the BSN's sorted popcount (minus the fixed offset), and the optional
    epilogue is the SI wiring.
    """
    sum_q = jax.lax.dot_general(
        x_q.astype(jnp.int32), w_int.astype(jnp.int32),
        (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    if thresholds_q is None:
        return sum_q
    return si_epilogue_ref(sum_q, thresholds_q)


def bsn_sort_ref(bits: jax.Array) -> jax.Array:
    """Descending sort of the trailing axis (thermometer normal form)."""
    return jnp.sort(bits, axis=-1)[..., ::-1]


def to_pages(x: jax.Array, page: int, axis: int = 1) -> jax.Array:
    """Position-major -> head-major pages, the KV pools' layout:
    ``(..., n*page, Hkv, ...)`` with the positions at ``axis`` ->
    ``(..., n, Hkv, page, ...)``.  :func:`from_pages` inverts it."""
    n = x.shape[axis] // page
    x = x.reshape(*x.shape[:axis], n, page, *x.shape[axis + 1:])
    return jnp.swapaxes(x, axis + 1, axis + 2)


def from_pages(x: jax.Array, axis: int = 1) -> jax.Array:
    """Head-major pages -> position-major: ``(..., n, Hkv, page, ...)``
    with the pages at ``axis`` -> ``(..., n*page, Hkv, ...)``."""
    x = jnp.swapaxes(x, axis + 1, axis + 2)
    return x.reshape(*x.shape[:axis], -1, *x.shape[axis + 2:])


def gather_pages(pages: jax.Array, page_tables: jax.Array,
                 layer=None) -> jax.Array:
    """Head-major (N, Hkv, page, ...) pool + (S, maxp) tables ->
    position-major (S, maxp*page, Hkv, ...).

    Works for KV pools (N, Hkv, page, Dh) and their parallel scale pools
    (N, Hkv, page) alike — the trailing axes ride along unchanged.  With
    ``layer``, ``pages`` is a stack (L, N, ...) of every layer's pools
    and one gather reads that layer's pages out of it.
    """
    if layer is None:
        g = jnp.take(pages, page_tables, axis=0)    # (S, maxp, Hkv, page, …)
    else:
        g = pages[layer, page_tables]
    return from_pages(g)


def gather_pages_dequant(pages: jax.Array, page_tables: jax.Array, *,
                         kv_format: str = "fp", scale: jax.Array | None = None,
                         resid: jax.Array | None = None,
                         layer=None) -> jax.Array:
    """Gather + dequantize a compressed pool window in one step.

    Gather commutes with the elementwise dequant, so dequantizing the
    gathered window is bit-identical to gathering a dequantized pool —
    without ever materializing fp pages.  Zero-filled positions (trash
    page, unwritten tail) dequantize to exact 0 in every format.
    """
    g = gather_pages(pages, page_tables, layer)
    if kv_format == "fp":
        return g
    sg = gather_pages(scale, page_tables, layer)
    rg = (gather_pages(resid, page_tables, layer) if kv_format == "sc"
          else None)
    return kv_dequant(g, sg, rg, fmt=kv_format)


def paged_attn_decode_ref(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, page_tables: jax.Array,
                          lengths: jax.Array, *, layer=None,
                          pin_logits=None, kv_format: str = "fp",
                          kv_aux: dict | None = None) -> jax.Array:
    """XLA gather/scatter paged decode — the paged-kernel ground truth.

    q: (S, Hkv, G, D); pools: (N, Hkv, page, D) already holding the new
    token at position ``lengths`` — or, with ``layer``, the stack
    (L, N, Hkv, page, D) of every layer's pools, as the kernel takes
    them; page_tables: (S, maxp) int32;
    lengths: (S,) int32.  Gathers each slot's full ``maxp*page`` KV
    window, masks positions past ``lengths`` and softmaxes — positions
    in padded table lanes point at the trash page but sit past the
    length, so they mask out identically to the kernel.  ``pin_logits``
    is a hook for the mesh path's sharding constraint (models/attention
    pins the KV-head axis to "model" there).  For compressed pools
    (``kv_format`` "int8"/"sc"), ``kv_aux`` carries the parallel
    ``k_scale``/``v_scale`` (N, Hkv, page) and — for sc — the
    ``k_resid``/``v_resid`` pools; dequant is fused into the gather.
    Returns (S, Hkv, G, D) in q.dtype.
    """
    S, Hkv, G, D = q.shape
    aux = kv_aux or {}
    kg = gather_pages_dequant(k_pages, page_tables, kv_format=kv_format,
                              scale=aux.get("k_scale"),
                              resid=aux.get("k_resid"),
                              layer=layer)              # (S, T, Hkv, Dh)
    vg = gather_pages_dequant(v_pages, page_tables, kv_format=kv_format,
                              scale=aux.get("v_scale"),
                              resid=aux.get("v_resid"), layer=layer)
    T = kg.shape[1]
    logits = jnp.einsum("shgd,sthd->shgt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / math.sqrt(D)
    if pin_logits is not None:
        logits = pin_logits(logits)
    valid = (jnp.arange(T)[None, :] <= lengths[:, None])    # (S, T)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("shgt,sthd->shgd", w, vg.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attn_verify_ref(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, page_tables: jax.Array,
                          lengths: jax.Array, *, pin_logits=None,
                          kv_format: str = "fp",
                          kv_aux: dict | None = None) -> jax.Array:
    """Parallel multi-token verify attention over the paged cache.

    The speculative-decoding verify step: lane ``s`` scores ``Tq``
    queries at consecutive positions ``lengths[s] + t`` (t = 0..Tq-1) in
    ONE pass, each under its own causal horizon — query t attends keys
    at positions ``<= lengths[s] + t``.  q: (S, Tq, Hkv, G, D); pools
    already hold the verify window's K/V scatter at those positions.
    Masked positions past each query's horizon softmax to exact 0, so
    row t is arithmetically the decode-ref row at length ``lengths+t``
    — the differential tests pin token identity with plain decode.
    Returns (S, Tq, Hkv, G, D) in q.dtype.
    """
    S, Tq, Hkv, G, D = q.shape
    aux = kv_aux or {}
    kg = gather_pages_dequant(k_pages, page_tables, kv_format=kv_format,
                              scale=aux.get("k_scale"),
                              resid=aux.get("k_resid"))  # (S, T, Hkv, Dh)
    vg = gather_pages_dequant(v_pages, page_tables, kv_format=kv_format,
                              scale=aux.get("v_scale"),
                              resid=aux.get("v_resid"))
    T = kg.shape[1]
    logits = jnp.einsum("sqhgd,sthd->shgqt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / math.sqrt(D)
    if pin_logits is not None:
        logits = pin_logits(logits)
    horizon = lengths[:, None] + jnp.arange(Tq)[None, :]     # (S, Tq)
    valid = (jnp.arange(T)[None, None, :] <= horizon[:, :, None])
    logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("shgqt,sthd->sqhgd", w, vg.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attn_prefill_ref(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           start, *, pin_logits=None,
                           kv_format: str = "fp",
                           kv_aux: dict | None = None) -> jax.Array:
    """XLA gather paged prefill — chunk ``[start, start+C)`` attends over
    every page written so far under the causal mask.

    q: (G, C, Hkv, Gq, D); pools: (N, Hkv, page, D) already holding the
    chunk's whole-page K/V scatter; page_tables: (G, maxp).  ``start``
    may be traced (the engine's chunk loop is rolled): the whole table
    window is gathered and positions past ``start + q_row`` are masked,
    which also hides every page the prefill has not written yet.
    Compressed pools dequantize inside the gather via ``kv_aux`` exactly
    as in :func:`paged_attn_decode_ref`.  Returns (G, C, Hkv, Gq, D) in
    q.dtype.
    """
    G, C, Hkv, Gq, D = q.shape
    aux = kv_aux or {}
    kg = gather_pages_dequant(k_pages, page_tables, kv_format=kv_format,
                              scale=aux.get("k_scale"),
                              resid=aux.get("k_resid"))  # (G, T, Hkv, Dh)
    vg = gather_pages_dequant(v_pages, page_tables, kv_format=kv_format,
                              scale=aux.get("v_scale"),
                              resid=aux.get("v_resid"))
    T = kg.shape[1]
    logits = jnp.einsum("sqhgd,sthd->shgqt", q.astype(jnp.float32),
                        kg.astype(jnp.float32)) / math.sqrt(D)
    if pin_logits is not None:
        logits = pin_logits(logits)
    causal = (jnp.arange(T)[None, :] <=
              (start + jnp.arange(C))[:, None])   # (C, T)
    logits = jnp.where(causal[None, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("shgqt,sthd->sqhgd", w, vg.astype(jnp.float32))
    return o.astype(q.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """Plain softmax attention oracle with GQA broadcast.

    q: (B,S,Hq,D); k,v: (B,S,Hkv,D)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, S, Hkv, g, D).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                        k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, D).astype(q.dtype)
