"""Pallas TPU kernels: flash-decoding paged attention (decode + prefill).

The serving engine keeps KV in a flat pool of fixed-size pages
(serving/paging.py); until now attention over that layout was an
XLA-level gather — ``jnp.take`` materializes every slot's full
``maxp * page`` KV window per decode step (ROADMAP names this the
single biggest raw-speed lever on the decode hot path).  These kernels
instead read the pages *directly through the page table*: the tables
and per-slot lengths ride in as scalar-prefetch operands
(:class:`pltpu.PrefetchScalarGridSpec`), so each grid step's KV
BlockSpec index map resolves ``page_tables[slot, page_idx]`` in SMEM
and Mosaic DMAs exactly one physical page into VMEM — HBM traffic is
q + the live pages + o, never the gathered window.

Decode (`paged_attn_decode_pallas`) is flash-decoding shaped:

* grid ``(S, Hkv, num_splits, pages_per_split)`` — slots and KV heads
  are parallel; the page axis is split-K.  Each program attends the
  slot's G grouped q heads (GQA: all q heads sharing a KV head ride in
  one program, amortizing the page loads) against one page.
* within a split, pages merge by the online-softmax ``(m, l, acc)``
  recurrence accumulated in revisited output blocks; across splits the
  partials merge in one tiny XLA log-sum-exp combine (the flash-
  decoding merge — splits are embarrassingly parallel on the grid).
* per-slot ``lengths`` masking: position ``t`` is live iff
  ``t <= lengths[slot]`` (the just-scattered token sits AT ``lengths``).
  Pages wholly past the length are skipped (``pl.when``), partially
  covered pages mask per position, and padded page-table lanes (which
  point at the reserved trash page) land beyond the length by
  construction — trash never contributes, which the poison tests prove.

Prefill (`paged_attn_prefill_pallas`) covers the chunk-aligned causal
window of ``attn_prefill_paged``: q rows are chunk positions
``[start, start + C)``, KV is every page written so far (pages
``[0, (start + C) / page)``), masked by ``k_pos <= q_pos``.  Blocks of
``block_q`` rows carry ``(m, l, acc)`` in VMEM scratch across the page
loop and normalize on the last page; future pages are skipped per
q-block (the causal early-exit) and never DMA'd.  ``start`` is a
scalar-prefetch operand, so every chunk of a prompt runs one compiled
kernel.

Pool layout is head-major: KV (and sc residual) pools are
``(N, Hkv, page, D)`` and each grid step DMAs one ``(page, D)`` tile —
block ``(1, 1, page, D)``, whose last two dims are the array's own, as
Mosaic requires of a block that is not (8, 128)-aligned.  Scale pools
are ``(N, Hkv, page)`` and one block carries a page's scales for all
heads, ``(1, Hkv, page)``; the kernel picks its head's row and turns it
into a ``(page, 1)`` column with exact selects and sums (adding zeros),
so the dequant multiplies exactly the values ``kv_dequant`` does.

The decode kernel also takes the stacks ``(L, N, Hkv, page, D)`` of
every layer's pools that the model's layer loop carries, with a
``layer`` scalar-prefetch operand in its index maps: each grid step
DMAs one page of one layer, and no layer is ever sliced out of the
stack (one layer's pools are a stack of one).  Where the pools' TPU
layout keeps the page axis minor (:func:`page_minor`) it reads the
``(D, page)`` view that layout holds, a bitcast.

Compressed KV pools (core/kv_quant.py: ``kv_format`` "int8" / "sc")
dequantize INSIDE the kernels: the scale pools (and the sc residual
pools) ride the same scalar-prefetch page-table index maps as the KV
blocks, so each grid step DMAs one page of int8 codes plus its scales
and reconstructs float K/V in VMEM — the fp window never exists in HBM.

Interpret mode (`interpret=True`) runs the same kernel bodies and is
what CPU CI runs; ``tests/test_tpu_compile.py`` compiles both kernels
for a described TPU v5e at granite-3-2b's widths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.kv_quant import SC_SHIFT, check_kv_format
from .plan import BlockOperand, LaunchPlan, ScalarOperand, call_plan

__all__ = ["paged_attn_decode_pallas", "paged_attn_prefill_pallas",
           "paged_attn_decode_plan", "paged_attn_prefill_plan"]

_NEG = -1e30
# q (rows, D) . k (page, D) contracting D of both: the scores matmul
# without a transpose of k in the kernel
_QK_DIMS = (((1,), (1,)), ((), ()))


def _head_scale_row(s, h) -> jax.Array:
    """Row ``h`` of a ``(Hkv, page)`` scale block as ``(1, page)``: one
    value selected, zeros added, so the row holds the stored scales
    exactly (no matmul, no rounding)."""
    heads = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    return jnp.sum(jnp.where(heads == h, s, 0.0), axis=0, keepdims=True)


def _head_scale_column(s, h) -> jax.Array:
    """Row ``h`` of a ``(Hkv, page)`` scale block as a ``(page, 1)``
    column, by the same exact selects and sums."""
    row = _head_scale_row(s, h)
    page = s.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (page, page), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (page, page), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _dequant_block(kv_format: str, raw, sc=None, resid=None) -> jax.Array:
    """One page of codes -> float32, ``sc`` broadcasting over it.  The
    elementwise math mirrors core.kv_quant.kv_dequant exactly, so the
    kernels match the gather-then-dequant reference bit for bit per
    element."""
    if kv_format == "fp":
        return raw.astype(jnp.float32)
    if kv_format == "int8":
        return raw.astype(jnp.float32) * sc
    fused = (resid.astype(jnp.int32)
             + raw.astype(jnp.int32) * (1 << SC_SHIFT))
    return fused.astype(jnp.float32) * (sc * (2.0 ** -SC_SHIFT))


def _load_kv_block(kv_format: str, h, x_ref, s_ref=None, r_ref=None):
    """One physical page of KV head ``h`` -> (page, D) float32, dequant
    fused.

    ``x_ref`` is the (1, 1, page, D) pool block; for compressed formats
    ``s_ref`` is the page's (1, Hkv, page) scale block and ``r_ref`` the
    sc residual block.
    """
    raw = x_ref[0, 0]                               # (page, D)
    sc = None if s_ref is None else _head_scale_column(s_ref[0], h)
    return _dequant_block(kv_format, raw, sc,
                          None if r_ref is None else r_ref[0, 0])


def _split_aux_refs(kv_format: str, rest, n_tail: int):
    """Split a kernel's ``*rest`` refs into (aux_refs, tail_refs).

    pallas passes refs positionally: the format-dependent scale/resid
    blocks sit between the fixed inputs and the outputs/scratch, so the
    kernels take ``*rest`` and cut it here.  aux order: k_scale, v_scale
    [, k_resid, v_resid].
    """
    n_aux = {"fp": 0, "int8": 2, "sc": 4}[kv_format]
    assert len(rest) == n_aux + n_tail, (kv_format, len(rest), n_tail)
    return rest[:n_aux], rest[n_aux:]


# ---------------------------------------------------------------------------
# decode: one query row per slot, split-K over pages
# ---------------------------------------------------------------------------

def page_minor(page: int, D: int) -> bool:
    """Whether the decode kernel reads the pools' ``(D, page)`` view.

    A TPU's default layout for a pool ``(..., page, D)`` whose page is a
    multiple of the 128 lanes and whose head dim is not puts the page
    axis minor: the pool lies in memory as ``(..., D, page)``, in whole
    (8, 128) tiles.  The kernel then takes that swapped view, which XLA
    lowers to a bitcast of the pool; a ``(page, D)`` operand there would
    cost a relayout copy of every pool the kernel reads.  Everywhere
    else the view is the pool itself.
    """
    return page % 128 == 0 and D % 128 != 0


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                   *rest, page: int, pps: int,
                   scale: float, kv_format: str, swapped: bool):
    del layer_ref                                   # read by the index maps
    aux, (m_ref, l_ref, acc_ref) = _split_aux_refs(kv_format, rest, 3)
    s = pl.program_id(0)
    h = pl.program_id(1)
    sp = pl.program_id(2)
    p = pl.program_id(3)

    @pl.when(p == 0)
    def _init():                                    # fresh (s, h, split)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s]
    base = (sp * pps + p) * page                    # first position in page

    def load(x_ref, s_ref=None, r_ref=None):
        # one (1, 1, 1, page, D) block -> (page, D) float32, or (D, page)
        # from the swapped view, whose scales are a (1, page) row
        sc = None
        if s_ref is not None:
            head_scales = _head_scale_row if swapped else _head_scale_column
            sc = head_scales(s_ref[0, 0], h)
        return _dequant_block(kv_format, x_ref[0, 0, 0], sc,
                              None if r_ref is None else r_ref[0, 0, 0])

    @pl.when(base <= length)                        # page holds live tokens
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)         # (G, D)
        k = load(k_ref, *aux[0::2])
        v = load(v_ref, *aux[1::2])
        if swapped:                                 # k, v: (D, page)
            logits = jnp.dot(q, k, preferred_element_type=jnp.float32)
        else:                                       # k, v: (page, D)
            logits = jax.lax.dot_general(
                q, k, _QK_DIMS, preferred_element_type=jnp.float32)
        logits = logits / scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        live = pos <= length                        # (1, page)
        logits = jnp.where(live, logits, _NEG)      # (G, page)
        m_prev = m_ref[0, 0, 0]                     # (G, 1)
        new_m = jnp.maximum(m_prev, jnp.max(logits, axis=-1,
                                            keepdims=True))
        w = jnp.where(live, jnp.exp(logits - new_m), 0.0)
        corr = jnp.exp(m_prev - new_m)
        if swapped:
            wv = jax.lax.dot_general(w, v, _QK_DIMS,
                                     preferred_element_type=jnp.float32)
        else:
            wv = jnp.dot(w, v, preferred_element_type=jnp.float32)
        m_ref[0, 0, 0] = new_m
        l_ref[0, 0, 0] = (l_ref[0, 0, 0] * corr
                          + jnp.sum(w, axis=-1, keepdims=True))
        acc_ref[0, 0, 0] = acc_ref[0, 0, 0] * corr + wv


def _kv_operands(kv_format: str, kv: dict, sc: dict) -> list:
    """The pool operands in kernel order: K, V [, scales [, residuals]].
    ``kv`` describes the KV/residual pools (one head's page per block),
    ``sc`` the scale pools (one page for all heads per block)."""
    ops = [BlockOperand("k_pages", **kv), BlockOperand("v_pages", **kv)]
    if kv_format != "fp":
        ops += [BlockOperand("k_scale", **sc),
                BlockOperand("v_scale", **sc)]
    if kv_format == "sc":
        ops += [BlockOperand("k_resid", **kv),
                BlockOperand("v_resid", **kv)]
    return ops


def paged_attn_decode_plan(*, S: int, Hkv: int, G: int, D: int,
                           page: int, maxp: int, num_pages: int,
                           num_splits: int = 1, kv_format: str = "fp",
                           q_dtype=jnp.float32, kv_dtype=None,
                           num_layers: int = 1) -> LaunchPlan:
    """Static launch geometry of the flash-decoding decode kernel.

    Single source of truth for the grid, BlockSpecs and scalar-prefetch
    operands: :func:`paged_attn_decode_pallas` executes exactly this
    plan, and the static auditor (``repro.analysis.kernel_audit``)
    proves its bounds/VMEM/revisit properties without tracing it.
    ``num_pages`` is the pool's page dim (engine pools include the
    reserved trash page), bounding legal page-table entries; ragged
    worst-case lengths straddle the last page boundary.  The pools are
    a stack of ``num_layers`` layers' pools and the ``layer`` scalar
    picks one, so every grid step DMAs one page of one layer: the
    caller never slices a layer out of the stack.  The per-split
    partials ``m``/``l`` are ``(G, 1)`` columns and ``acc`` is
    ``(G, D)``: full trailing dims, legal for every ``num_splits``.
    """
    check_kv_format(kv_format)
    if kv_dtype is None:
        kv_dtype = jnp.float32 if kv_format == "fp" else jnp.int8
    num_splits = max(1, min(num_splits, maxp))
    pps = -(-maxp // num_splits)                    # pages per split
    maxp_pad = num_splits * pps                     # trash-padded lanes
    swapped = page_minor(page, D)

    kernel = functools.partial(_decode_kernel, page=page, pps=pps,
                               scale=math.sqrt(D), kv_format=kv_format,
                               swapped=swapped)

    def kv_index(s, h, sp, p, pt, ln, ly):
        del ln
        return (ly[0], pt[s, sp * pps + p], h, 0, 0)

    def scale_index(s, h, sp, p, pt, ln, ly):
        del h, ln
        return (ly[0], pt[s, sp * pps + p], 0, 0)

    tile = (D, page) if swapped else (page, D)
    kv = dict(shape=(num_layers, num_pages, Hkv) + tile, dtype=kv_dtype,
              block=(1, 1, 1) + tile, index_map=kv_index)
    sc = dict(shape=(num_layers, num_pages, Hkv, page), dtype=jnp.float32,
              block=(1, 1, Hkv, page), index_map=scale_index)
    inputs = [BlockOperand("q", (S, Hkv, G, D), q_dtype, (1, 1, G, D),
                           lambda s, h, sp, p, pt, ln, ly: (s, h, 0, 0))]
    inputs += _kv_operands(kv_format, kv, sc)

    part_index = lambda s, h, sp, p, pt, ln, ly: (s, h, sp, 0, 0)  # noqa: E731
    max_len = maxp * page
    return LaunchPlan(
        name="paged_attn_decode",
        grid=(S, Hkv, num_splits, pps),
        scalars=(
            ScalarOperand("page_tables", (S, maxp_pad), jnp.int32,
                          max_value=num_pages - 1),
            # the just-scattered token sits AT lengths, so legal values
            # are < max_len; worst cases straddle the last page
            # boundary: plen = length+1 with plen % page in {0,1,page-1}
            ScalarOperand("lengths", (S,), jnp.int32,
                          max_value=max_len - 1,
                          values=(max_len - page, max_len - page + 1,
                                  max(0, max_len - page - 1)),
                          kernel_only=True),
            ScalarOperand("layer", (1,), jnp.int32,
                          max_value=num_layers - 1),
        ),
        inputs=tuple(inputs),
        outputs=(
            BlockOperand("m", (S, Hkv, num_splits, G, 1), jnp.float32,
                         (1, 1, 1, G, 1), part_index),
            BlockOperand("l", (S, Hkv, num_splits, G, 1), jnp.float32,
                         (1, 1, 1, G, 1), part_index),
            BlockOperand("acc", (S, Hkv, num_splits, G, D), jnp.float32,
                         (1, 1, 1, G, D), part_index),
        ),
        scratch=(),
        kernel=kernel,
        # the pps axis revisits each partial block only when a split
        # spans more than one page; with pps == 1 every block is written
        # exactly once (the @pl.when(p == 0) init always fires) and an
        # accumulate declaration would be stale metadata
        accumulate=({"m": "online-softmax", "l": "online-softmax",
                     "acc": "online-softmax"} if pps > 1 else {}),
        single_output=False,
    )


def _aux_operands(kv_format: str, k_scale, v_scale, k_resid, v_resid):
    ops = []
    if kv_format != "fp":
        ops += [k_scale, v_scale]
    if kv_format == "sc":
        ops += [k_resid, v_resid]
    return ops


@functools.partial(jax.jit,
                   static_argnames=("num_splits", "interpret", "kv_format"))
def paged_attn_decode_pallas(q: jax.Array, k_pages: jax.Array,
                             v_pages: jax.Array, page_tables: jax.Array,
                             lengths: jax.Array, *, layer=None,
                             num_splits: int = 1,
                             interpret: bool = False,
                             kv_format: str = "fp",
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None,
                             k_resid: jax.Array | None = None,
                             v_resid: jax.Array | None = None) -> jax.Array:
    """Batched one-token paged decode.

    q: (S, Hkv, G, D) grouped queries; k_pages/v_pages: one layer's
    (N, Hkv, page, D) pools, or the stack (L, N, Hkv, page, D) of every
    layer's pools with ``layer`` the int32 index of the one to read
    (traced: the model's layer loop passes its counter, and the kernel
    reads that layer's pages straight out of the stack).  The pools
    already hold the new token at position ``lengths``; page_tables:
    (S, maxp) int32; lengths: (S,) int32.  For compressed pools
    (``kv_format`` "int8"/"sc") the parallel ``k_scale``/``v_scale``
    ((L,) N, Hkv, page) — and for sc the ``k_resid``/``v_resid`` — pools
    ride the SAME page-table index maps as the KV blocks, so each grid
    step DMAs one page of codes + its scales and dequantizes in VMEM: no
    fp pages ever materialize in HBM.  Returns the attention context
    (S, Hkv, G, D) in q.dtype.
    """
    check_kv_format(kv_format)
    S, Hkv, G, D = q.shape
    aux_ops = _aux_operands(kv_format, k_scale, v_scale, k_resid, v_resid)
    if k_pages.ndim == 4:                 # one layer's pools: a stack of one
        k_pages, v_pages = k_pages[None], v_pages[None]
        aux_ops = [a[None] for a in aux_ops]
        layer = 0
    assert layer is not None, "a stack of pools needs its layer index"
    num_layers, num_pages, _, page, _ = k_pages.shape
    maxp = page_tables.shape[1]
    plan = paged_attn_decode_plan(
        S=S, Hkv=Hkv, G=G, D=D, page=page, maxp=maxp,
        num_pages=num_pages, num_splits=num_splits,
        kv_format=kv_format, q_dtype=q.dtype, kv_dtype=k_pages.dtype,
        num_layers=num_layers)
    maxp_pad = plan.scalars[0].shape[1]
    if maxp_pad != maxp:
        # pad table lanes with the trash page: they sit past ``lengths``
        # (which is < maxp*page by construction) so masking kills them
        page_tables = jnp.pad(page_tables, ((0, 0), (0, maxp_pad - maxp)))
    if page_minor(page, D):
        # the view the pools' TPU layout already holds (a bitcast there)
        k_pages, v_pages = (jnp.swapaxes(a, -1, -2) for a in (k_pages,
                                                             v_pages))
        aux_ops = [jnp.swapaxes(a, -1, -2) if a.ndim == 5 else a
                   for a in aux_ops]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    m, l, acc = call_plan(plan, (page_tables, lengths, layer, q, k_pages,
                                 v_pages, *aux_ops), interpret=interpret)
    m, l = m[..., 0], l[..., 0]                       # (S, Hkv, splits, G)

    # flash-decoding LSE merge across splits (exact: splits with no live
    # pages carry m=-1e30, l=0 and weigh zero)
    m_star = jnp.max(m, axis=2)                       # (S, Hkv, G)
    alpha = jnp.exp(m - m_star[:, :, None])           # (S, Hkv, splits, G)
    l_tot = jnp.sum(l * alpha, axis=2)
    o = jnp.sum(acc * alpha[..., None], axis=2)
    o = o / jnp.maximum(l_tot, 1e-30)[..., None]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# prefill: chunk-aligned causal window over the pages written so far
# ---------------------------------------------------------------------------

def _prefill_kernel(pt_ref, st_ref, q_ref, k_ref, v_ref,
                    *rest, bq: int, page: int, n_pg: int, Hq: int,
                    Gq: int, scale: float, kv_format: str):
    aux, (o_ref, m_sc, l_sc, acc_sc) = _split_aux_refs(kv_format, rest, 4)
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    pg = pl.program_id(2)
    h = (bh % Hq) // Gq                             # this q head's KV head
    start = st_ref[0]

    @pl.when(pg == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_hi = start + (qi + 1) * bq - 1                # last q position

    @pl.when(pg * page <= q_hi)                     # causal early-exit
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)            # (bq, D)
        k = _load_kv_block(kv_format, h, k_ref, *aux[0::2])  # (page, D)
        v = _load_kv_block(kv_format, h, v_ref, *aux[1::2])
        logits = jax.lax.dot_general(
            q, k, _QK_DIMS, preferred_element_type=jnp.float32) / scale
        q_pos = start + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, page), 0)
        k_pos = pg * page + jax.lax.broadcasted_iota(
            jnp.int32, (bq, page), 1)
        causal = k_pos <= q_pos
        logits = jnp.where(causal, logits, _NEG)    # (bq, page)
        m_prev = m_sc[...]                          # (bq, 1)
        new_m = jnp.maximum(m_prev, jnp.max(logits, axis=-1,
                                            keepdims=True))
        w = jnp.where(causal, jnp.exp(logits - new_m), 0.0)
        corr = jnp.exp(m_prev - new_m)
        m_sc[...] = new_m
        l_sc[...] = l_sc[...] * corr + jnp.sum(w, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jnp.dot(
            w, v, preferred_element_type=jnp.float32)

    @pl.when(pg == n_pg - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attn_prefill_plan(*, G: int, C: int, Hkv: int, Gq: int, D: int,
                            page: int, num_pages: int, table_width: int,
                            block_q: int = 32, kv_format: str = "fp",
                            q_dtype=jnp.float32,
                            kv_dtype=None) -> LaunchPlan:
    """Static launch geometry of the chunked-prefill kernel (see
    :func:`paged_attn_decode_plan` for the contract).

    The chunk's first position ``start`` is a scalar-prefetch operand,
    not a static: one compiled kernel serves every chunk of a prompt,
    so the engine's chunk loop is a rolled loop.  The page axis spans
    the whole ``table_width``; pages past a q block's last position are
    skipped in the body, and their index map repeats the last needed
    page, so the pipeline never DMAs a page the block cannot see.
    """
    check_kv_format(kv_format)
    if kv_dtype is None:
        kv_dtype = jnp.float32 if kv_format == "fp" else jnp.int8
    assert C % page == 0, (C, page)
    assert table_width * page >= C, (table_width, page, C)
    Hq = Hkv * Gq
    bq = min(block_q, C)
    if C % bq:
        bq = math.gcd(C, bq)

    kernel = functools.partial(_prefill_kernel, bq=bq, page=page,
                               n_pg=table_width, Hq=Hq, Gq=Gq,
                               scale=math.sqrt(D), kv_format=kv_format)

    def seen_page(qi, pg, st):
        # the last page this q block attends; later grid steps re-map
        # onto it (same block index -> no new DMA)
        return jnp.minimum(pg, (st[0] + (qi + 1) * bq - 1) // page)

    def kv_index(bh, qi, pg, pt, st):
        return (pt[bh // Hq, seen_page(qi, pg, st)], (bh % Hq) // Gq,
                0, 0)

    def scale_index(bh, qi, pg, pt, st):
        return (pt[bh // Hq, seen_page(qi, pg, st)], 0, 0)

    inputs = [BlockOperand("q", (G * Hq, C, D), q_dtype, (1, bq, D),
                           lambda bh, qi, pg, pt, st: (bh, qi, 0))]
    inputs += _kv_operands(
        kv_format,
        dict(shape=(num_pages, Hkv, page, D), dtype=kv_dtype,
             block=(1, 1, page, D), index_map=kv_index),
        dict(shape=(num_pages, Hkv, page), dtype=jnp.float32,
             block=(1, Hkv, page), index_map=scale_index))

    return LaunchPlan(
        name="paged_attn_prefill",
        grid=(G * Hq, C // bq, table_width),
        scalars=(
            ScalarOperand("page_tables", (G, table_width), jnp.int32,
                          max_value=num_pages - 1),
            # the chunk lies inside the table; the index maps are
            # monotone in start, so 0 and the max bound every chunk
            ScalarOperand("start", (1,), jnp.int32,
                          max_value=table_width * page - C),
        ),
        inputs=tuple(inputs),
        outputs=(
            BlockOperand("o", (G * Hq, C, D), q_dtype, (1, bq, D),
                         lambda bh, qi, pg, pt, st: (bh, qi, 0)),
        ),
        scratch=(((bq, 1), jnp.float32),
                 ((bq, 1), jnp.float32),
                 ((bq, D), jnp.float32)),
        kernel=kernel,
        # every page revisits the same o block; (m,l,acc) live in VMEM
        # scratch and o is written once, under @pl.when(last page) — a
        # single-page launch writes each block exactly once
        accumulate=({"o": "scratch-finalize"} if table_width > 1 else {}),
        single_output=True,
    )


@functools.partial(jax.jit,
                   static_argnames=("block_q", "interpret", "kv_format"))
def paged_attn_prefill_pallas(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_tables: jax.Array,
                              *, start, block_q: int = 32,
                              interpret: bool = False,
                              kv_format: str = "fp",
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None,
                              k_resid: jax.Array | None = None,
                              v_resid: jax.Array | None = None) -> jax.Array:
    """One prefill chunk attending over the paged cache.

    q: (G, C, Hkv, Gq, D) — chunk ``[start, start + C)`` of each request
    in the admission group, C a multiple of the page size (static) and
    ``start`` a chunk-aligned int32 scalar (traced: every chunk shares
    one compiled kernel); pools: (N, Hkv, page, D), already holding the
    chunk's whole-page K/V scatter; page_tables: (G, maxp), covering
    ``start + C``.  Compressed pools dequantize in VMEM through the same
    page-table index maps (see :func:`paged_attn_decode_pallas`).
    Returns the context (G, C, Hkv, Gq, D) in q.dtype.  The causal mask
    matches the reference exactly: ``k_pos <= start + q_row``.
    """
    check_kv_format(kv_format)
    G, C, Hkv, Gq, D = q.shape
    page = k_pages.shape[2]
    Hq = Hkv * Gq
    plan = paged_attn_prefill_plan(
        G=G, C=C, Hkv=Hkv, Gq=Gq, D=D, page=page,
        num_pages=k_pages.shape[0], table_width=page_tables.shape[1],
        block_q=block_q, kv_format=kv_format, q_dtype=q.dtype,
        kv_dtype=k_pages.dtype)

    # head-major (G*Hq, C, D): program bh serves q head bh % Hq of
    # request bh // Hq; its KV head is (bh % Hq) // Gq (GQA grouping as
    # in flash_attention's kv index map)
    qh = jnp.moveaxis(q.reshape(G, C, Hq, D), 2, 1).reshape(G * Hq, C, D)
    st = jnp.asarray(start, jnp.int32).reshape(1)

    aux_ops = _aux_operands(kv_format, k_scale, v_scale, k_resid, v_resid)
    out = call_plan(plan, (page_tables, st, qh, k_pages, v_pages,
                           *aux_ops), interpret=interpret)
    out = jnp.moveaxis(out.reshape(G, Hq, C, D), 1, 2)
    return out.reshape(G, C, Hkv, Gq, D)
