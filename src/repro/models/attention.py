"""GQA attention: flash-style (triangle-exact) training/prefill + cached decode.

The chunked path scans over exactly the lower-triangle (q-block, kv-block)
pairs with an online-softmax carry, so (a) no (S, S) logits tensor ever
materializes (required for the 32k prefill cells) and (b) the HLO FLOPs
match the true causal work — no 2x masked overcompute polluting the
roofline (DESIGN.md §6).

Decode attends one query against the full KV cache directly; with the
cache sequence-sharded (long_500k) GSPMD lowers the softmax into the
flash-decoding LSE-merge pattern automatically.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.kv_quant import kv_format_of, kv_quant
from repro.distributed.sharding import constrain, current_rules
from repro.kernels import dispatch as kernel_dispatch
from repro.kernels import ref as kernel_ref
from .common import (DATA, MODEL, apply_rope, dense_apply, dense_init,
                     dense_spec, norm_apply, norm_init, norm_spec)

__all__ = ["attn_init", "attn_spec", "attn_train", "attn_decode",
           "attn_decode_paged", "attn_prefill_paged", "flash_attention"]


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    q = cfg.quant
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, hq * dh, q, dtype=dt),
        "wk": dense_init(ks[1], cfg.d_model, hkv * dh, q, dtype=dt),
        "wv": dense_init(ks[2], cfg.d_model, hkv * dh, q, dtype=dt),
        "wo": dense_init(ks[3], hq * dh, cfg.d_model, q, dtype=dt),
    }
    if getattr(cfg, "qk_norm", False):
        p["q_norm"] = norm_init(dh, "rmsnorm")
        p["k_norm"] = norm_init(dh, "rmsnorm")
    return p


def attn_spec(cfg: ModelConfig, serving: bool = False) -> dict:
    """Training: Megatron TP (wq/wk/wv column-, wo row-parallel — the
    all-reduce amortizes over the token batch).  Serving: EVERY
    projection is column-parallel (output channels over "model", no
    contraction dim sharded).  Decode is weight-resident by design, and
    the SC datapaths make contraction sharding wrong, not just slow: the
    approximate BSN adder (``sc_int_approx``) is a nonlinear per-output-
    channel accumulator, so splitting its K inputs across chips changes
    the answer — whole adders must live on one device.  Column-parallel
    keeps each channel's accumulation device-local (mesh-on output is
    token-identical to mesh-off) at the cost of all-gathering the (tiny)
    decode activations instead of all-reducing partials."""
    q = cfg.quant
    in_ax = None if serving else DATA
    s = {
        "wq": dense_spec(in_ax, MODEL, q),
        "wk": dense_spec(in_ax, MODEL, q),
        "wv": dense_spec(in_ax, MODEL, q),
        "wo": dense_spec(None, MODEL, q) if serving
        else dense_spec(MODEL, DATA, q),
    }
    if getattr(cfg, "qk_norm", False):
        s["q_norm"] = norm_spec("rmsnorm")
        s["k_norm"] = norm_spec("rmsnorm")
    return s


# ---------------------------------------------------------------------------
# flash attention (pair-list scan, exact triangle FLOPs)
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool, chunk: int) -> jax.Array:
    """q: (B,S,Hkv,G,Dh); k,v: (B,S,Hkv,Dh) -> (B,S,Hkv,G,Dh).

    Scans (i, j) block pairs — j<=i for causal, all for bidirectional —
    carrying (m, l, acc) online-softmax state per q block; each row i is
    flushed into the output buffer at its final pair.
    """
    B, S, H, G, D = q.shape
    c = min(chunk, S)
    if S % c:
        c = math.gcd(S, c)
    n = S // c
    scale = 1.0 / math.sqrt(D)
    qb = (q * scale).astype(jnp.float32).reshape(B, n, c, H, G, D)
    kb = k.astype(jnp.float32).reshape(B, n, c, H, D)
    vb = v.astype(jnp.float32).reshape(B, n, c, H, D)

    if causal:
        pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    else:
        pairs = [(i, j) for i in range(n) for j in range(n)]
    pi = jnp.asarray([p[0] for p in pairs], jnp.int32)
    pj = jnp.asarray([p[1] for p in pairs], jnp.int32)

    neg = -1e30
    m0 = jnp.full((B, H, G, c), neg, jnp.float32)
    l0 = jnp.zeros((B, H, G, c), jnp.float32)
    a0 = jnp.zeros((B, H, G, c, D), jnp.float32)
    tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])  # (cq, ck)

    # The step is checkpointed: its backward recomputes the (c, c) logits
    # tile instead of saving one per pair (the stacked residual would be
    # n_pairs x tile — 10s of GB/device at 32k — the flash point exactly).
    @jax.checkpoint
    def step(carry, ij):
        m, l, acc = carry
        i, j = ij
        fresh = (j == 0)
        m = jnp.where(fresh, neg, m)
        l = jnp.where(fresh, 0.0, l)
        acc = jnp.where(fresh, 0.0, acc)
        qi = jax.lax.dynamic_index_in_dim(qb, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj)
        if causal:  # mask only the diagonal block's upper triangle
            diag = (i == j)
            logits = jnp.where(jnp.logical_or(~diag, tri), logits, neg)
        blk_max = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(logits - new_m[..., None])
        corr = jnp.exp(m - new_m)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vj)
        # emit this pair's normalized tile; the post-scan gather keeps only
        # each row's final (diagonal / last-column) emission
        o_blk = (acc / jnp.maximum(l[..., None], 1e-30))
        o_blk = jnp.moveaxis(o_blk, -2, 1).astype(q.dtype)    # (B,c,H,G,D)
        return (new_m, l, acc), o_blk

    (_, _, _), ys = jax.lax.scan(step, (m0, l0, a0), (pi, pj))
    if causal:  # row i finalized at its diagonal pair
        final_idx = jnp.asarray([i * (i + 1) // 2 + i for i in range(n)])
    else:
        final_idx = jnp.asarray([(i + 1) * n - 1 for i in range(n)])
    out = jnp.moveaxis(ys[final_idx], 0, 1)                   # (B,n,c,H,G,D)
    return out.reshape(B, S, H, G, D)


# ---------------------------------------------------------------------------
# full layers
# ---------------------------------------------------------------------------

def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array):
    B, S, _ = x.shape
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = dense_apply(p["wq"], x, cfg.quant).reshape(B, S, hq, dh)
    k = dense_apply(p["wk"], x, cfg.quant).reshape(B, S, hkv, dh)
    v = dense_apply(p["wv"], x, cfg.quant).reshape(B, S, hkv, dh)
    if "q_norm" in p:
        q = norm_apply(p["q_norm"], q, "rmsnorm")
        k = norm_apply(p["k_norm"], k, "rmsnorm")
    q = apply_rope(q, positions, dh, cfg.rope_fraction, cfg.rope_theta)
    k = apply_rope(k, positions, dh, cfg.rope_fraction, cfg.rope_theta)
    return q, k, v


def attn_train(p: dict, x: jax.Array, cfg: ModelConfig,
               positions: jax.Array):
    """Full-sequence attention (train / prefill). Returns (y, (k, v))."""
    B, S, _ = x.shape
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = hq // hkv
    q, k, v = _project_qkv(p, x, cfg, positions)
    qg = q.reshape(B, S, hkv, g, dh)
    o = flash_attention(qg, k, v, cfg.causal, cfg.attn_q_chunk)
    o = o.reshape(B, S, hq * dh)
    y = dense_apply(p["wo"], o, cfg.quant)
    return y, (k, v)


def _decode_kv_time_axis(cfg: ModelConfig, batch: int) -> str | None:
    """Which logical axis carries the KV cache's time dimension — must
    mirror launch/dryrun.py's cache_specs choice so the attention einsums
    are constrained consistently with the cache's input sharding."""
    rules = current_rules()
    if rules is None:
        return None
    sizes = dict(zip(rules.mesh.axis_names, rules.mesh.devices.shape))
    if batch == 1:
        return "seq"                              # long_500k context shard
    if cfg.n_kv_heads % sizes.get("model", 1) != 0:
        return "model"                            # flash-decoding split-KV
    return None                                   # heads carry "model"


def attn_decode(p: dict, x: jax.Array, cfg: ModelConfig,
                k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array):
    """One-token decode. x: (B, 1, D); caches: (B, T, Hkv, Dh); pos scalar.

    Returns (y (B,1,D), new k_cache, new v_cache).  When the cache's time
    axis is sharded ("model" for small-KV-head archs, "seq" for long
    contexts), the logits/output einsums are constrained to keep the
    partials sharded over time and merge via psum — flash-decoding —
    instead of letting GSPMD all-gather the whole cache (54 GB/step for
    qwen3 decode_32k; see EXPERIMENTS.md §Perf).
    """
    B, _, _ = x.shape
    T = k_cache.shape[1]
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = hq // hkv
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), pos, axis=1)
    qg = q.reshape(B, hkv, g, dh)
    t_axis = _decode_kv_time_axis(cfg, B)
    logits = jnp.einsum("bhgd,bthd->bhgt", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) / math.sqrt(dh)
    if t_axis is not None:
        logits = constrain(logits, "batch" if B > 1 else None,
                           None, None, t_axis)
    valid = (jnp.arange(T) <= pos)[None, None, None, :]
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", w, v_cache.astype(jnp.float32))
    o = o.reshape(B, 1, hq * dh).astype(x.dtype)
    y = dense_apply(p["wo"], o, cfg.quant)
    return y, k_cache, v_cache


# ---------------------------------------------------------------------------
# paged KV cache (ServeEngine v2)
# ---------------------------------------------------------------------------
#
# The serving engine stores KV in a flat pool of fixed-size pages shared
# by every request (serving/paging.py owns the allocation); the
# functions below scatter the new K/V into the pools and attend over
# that layout.  Pools are head-major, ``(N, Hkv, page, Dh)`` (scales
# ``(N, Hkv, page)``): per slot ``s`` position ``t`` of KV head ``h``
# lives at ``pool[page_tables[s, t // page], h, t % page]``.  Page-table
# padding
# points at the reserved trash page (writes land there harmlessly; reads
# are masked by ``lengths``), so no cross-request leakage is possible by
# construction.
#
# The attention math itself routes through kernels/dispatch.py: the
# flash-decoding Pallas kernel (kernels/paged_attention.py) reads pages
# directly through the table, with the XLA gather/scatter path
# (kernels/ref.py) as the reference oracle.  Under active mesh rules the
# constrained reference always serves: the kernel is a single-device
# program, and the serving contract keeps KV heads device-local over
# "model", so the per-device work IS the unsharded math — mesh-on is
# token-identical to the kernel path (tests/test_sharded_serving.py).
#
# Both functions take the engine's pool dict (``pools``): always
# ``k_pages``/``v_pages``/``page_tables``, plus the parallel
# ``k_scale``/``v_scale`` (+ sc ``k_resid``/``v_resid``) leaves when the
# cache is compressed (core/kv_quant.py — the dict's keys ARE the
# format).  New K/V quantize on scatter: only the just-written positions
# are encoded, existing pages are never touched, so batched and
# sequential serving stay bit-identical within a format.

_AUX_KEYS = ("k_scale", "v_scale", "k_resid", "v_resid")


def _pin_pool(a: jax.Array, head_axis: int = 1) -> jax.Array:
    """Pools stay KV-head-sharded across steps (weights-resident layout);
    scatter indices are replicated, so the update is device-local.  Works
    for KV/resid pools (N, Hkv, page, Dh) and scale pools (N, Hkv, page),
    and for stacks of them (``head_axis`` 2)."""
    return constrain(a, *(None,) * head_axis, "model",
                     *(None,) * (a.ndim - head_axis - 1))


def _scatter_pools(pools: dict, fmt: str, k_new: jax.Array,
                   v_new: jax.Array, put, head_axis: int = 1) -> dict:
    """Quantize-on-scatter: encode the new K/V rows and write every pool
    leaf through ``put(pool, values)`` (same indices for codes, scales
    and residuals — the pools are position-parallel).  Traced under the
    ``kv_write`` name scope."""
    out = {}
    with jax.named_scope("kv_write"):
        for name, val in (("k", k_new), ("v", v_new)):
            qd = kv_quant(val, fmt)
            for leaf, key in (("pages", "q"), ("scale", "scale"),
                              ("resid", "resid")):
                if key in qd:
                    pool = f"{name}_{leaf}"
                    out[pool] = _pin_pool(put(pools[pool], qd[key]),
                                          head_axis)
    return out


def _stack_put(layer, phys: jax.Array, off: jax.Array):
    """``put`` for :func:`_scatter_pools` into layer ``layer`` of the
    pool stacks the decode step carries: (L, N, Hkv, page, Dh) codes /
    residuals, (L, N, Hkv, page) scales; lane s's values (Hkv[, Dh])
    land at page ``phys[s]``, offset ``off[s]``.

    One dynamic-update-slice per lane, which XLA applies in place in
    whatever layout the stack is carried.  A scatter does not: on a TPU
    a scatter of (Hkv, Dh) windows relayouts the whole stack so that the
    window dims lie minor, and a scatter of single values runs value by
    value.
    """
    def put(pool, val):
        val = val.astype(pool.dtype)
        for s in range(val.shape[0]):
            row = val[s][None, None, :, None]       # (1, 1, Hkv, 1[, Dh])
            start = (layer, phys[s], 0, off[s]) + (0,) * (pool.ndim - 4)
            pool = jax.lax.dynamic_update_slice(pool, row, start)
        return pool
    return put


def _kv_aux(pools: dict) -> dict:
    return {k: pools[k] for k in _AUX_KEYS if k in pools}


def attn_decode_paged(p: dict, x: jax.Array, cfg: ModelConfig,
                      pools: dict, lengths: jax.Array):
    """Batched one-token decode over the paged KV cache.

    x: (S, 1, D) — one new token per active slot; ``pools`` holds the
    stacks (L, N, Hkv, page, Dh) of every layer's KV pools (+ any
    scale/resid stacks), as the decode step's layer loop carries them,
    the int32 ``layer`` this call reads and writes, and (S, maxp) int32
    ``page_tables``; lengths: (S,) int32 tokens already in the cache
    (== the new token's position).  The new K/V go into layer ``layer``
    in place and the kernel reads that layer out of the stack, so no
    layer's pool is ever sliced or copied.  Returns (y (S, 1, D),
    new_pools) — the updated stacks, page_tables and layer excluded.
    """
    page_tables = pools["page_tables"]
    page = pools["k_pages"].shape[3]
    layer = pools["layer"]
    fmt = kv_format_of(pools)
    S = x.shape[0]
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = hq // hkv
    positions = lengths[:, None]                            # (S, 1)
    q, k, v = _project_qkv(p, x, cfg, positions)
    # scatter the new K/V row: one (phys_page, offset) per slot.  Distinct
    # active slots own distinct pages, so indices never collide; padded
    # lanes all hit the trash page, where last-writer-wins is fine.
    phys = jnp.take_along_axis(page_tables, (lengths // page)[:, None],
                               axis=1)[:, 0]
    off = lengths % page
    new_pools = _scatter_pools(pools, fmt, k[:, 0], v[:, 0],
                               _stack_put(layer, phys, off), head_axis=2)

    qg = q.reshape(S, hkv, g, dh)
    aux = _kv_aux(new_pools)
    with jax.named_scope("paged_attn"):
        if current_rules() is not None:
            # mesh path: the constrained XLA reference (KV-head axis stays
            # "model"-sharded through the logits; see module comment above)
            o = kernel_ref.paged_attn_decode_ref(
                qg, new_pools["k_pages"], new_pools["v_pages"],
                page_tables, lengths, layer=layer, kv_format=fmt,
                kv_aux=aux,
                pin_logits=lambda lg: constrain(lg, None, "model", None,
                                                None))
        else:
            o = kernel_dispatch.paged_attn_decode(
                qg, new_pools["k_pages"], new_pools["v_pages"],
                page_tables, lengths, layer=layer, kv_format=fmt,
                kv_aux=aux)
    o = o.reshape(S, 1, hq * dh).astype(x.dtype)
    # gather the head-sharded context BEFORE wo: the serving wo is
    # column-parallel, so its hq*dh contraction must be device-local
    # (never partial-summed — see attn_spec's serving rationale)
    o = constrain(o, None, None, None)
    y = dense_apply(p["wo"], o, cfg.quant)
    return y, new_pools


def attn_verify_paged(p: dict, x: jax.Array, cfg: ModelConfig,
                      pools: dict, lengths: jax.Array):
    """Batched multi-token speculative-verify over the paged KV cache.

    x: (S, T, D) — the verify window per slot (last committed token +
    the T-1 draft tokens), token t sitting at cache position
    ``lengths + t``.  Scatters all T K/V rows (overwriting whatever the
    draft pass left there), then scores all T queries in ONE parallel
    attention pass, each under its own causal horizon — so the whole
    window costs one step of projections/attention instead of T decode
    steps.  Returns (y (S, T, D), new_pools).

    A draft window can straddle a page boundary; the per-position
    (phys, off) scatter below handles that, and distinct lanes own
    distinct pages so indices never collide (padded lanes hit the trash
    page).  There is no Pallas verify kernel yet — this routes through
    the XLA reference unconditionally (see ROADMAP), with the mesh
    path's logit pin matching decode.
    """
    page_tables = pools["page_tables"]
    page = pools["k_pages"].shape[2]
    fmt = kv_format_of(pools)
    S, T = x.shape[0], x.shape[1]
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = hq // hkv
    positions = lengths[:, None] + jnp.arange(T)[None, :]   # (S, T)
    q, k, v = _project_qkv(p, x, cfg, positions)
    phys = jnp.take_along_axis(page_tables, positions // page, axis=1)
    off = positions % page                                  # (S, T)
    new_pools = _scatter_pools(
        pools, fmt, k, v,
        lambda pool, val: pool.at[phys, :, off].set(
            val.astype(pool.dtype)))

    qg = q.reshape(S, T, hkv, g, dh)
    aux = _kv_aux(new_pools)
    with jax.named_scope("paged_attn"):
        if current_rules() is not None:
            o = kernel_ref.paged_attn_verify_ref(
                qg, new_pools["k_pages"], new_pools["v_pages"],
                page_tables, lengths, kv_format=fmt, kv_aux=aux,
                pin_logits=lambda lg: constrain(lg, None, "model",
                                                None, None, None))
        else:
            o = kernel_ref.paged_attn_verify_ref(
                qg, new_pools["k_pages"], new_pools["v_pages"],
                page_tables, lengths, kv_format=fmt, kv_aux=aux)
    o = o.reshape(S, T, hq * dh).astype(x.dtype)
    o = constrain(o, None, None, None)
    y = dense_apply(p["wo"], o, cfg.quant)
    return y, new_pools


def attn_prefill_paged(p: dict, x: jax.Array, cfg: ModelConfig,
                       pools: dict, start):
    """One prefill chunk written straight into the decode page layout.

    x: (G, C, D) — chunk ``[start, start+C)`` of each request in the
    admission group, with ``C`` a multiple of the page size (static) and
    ``start`` a chunk-aligned int32 scalar (traced: the chunk loop is
    rolled, one compiled body for every chunk).  K/V of the chunk are
    scattered as whole pages (quantized on scatter for compressed
    ``pools``), then the chunk's queries attend over every page written
    so far (positions < start + C) under the causal mask — the online
    equivalent of flash prefill, sharing the decode cache layout so no
    re-layout pass sits between prefill and decode.

    Returns (y (G, C, D), new_pools).
    """
    page_tables = pools["page_tables"]
    page = pools["k_pages"].shape[2]
    fmt = kv_format_of(pools)
    G, C, _ = x.shape
    assert C % page == 0, (C, page)
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = hq // hkv
    positions = start + jnp.broadcast_to(
        jnp.arange(C, dtype=jnp.int32), (G, C))
    q, k, v = _project_qkv(p, x, cfg, positions)            # (G,C,H,Dh)

    # whole-page scatter: chunk pages j cover positions start + j*page
    npg = C // page
    phys = jax.lax.dynamic_slice_in_dim(
        page_tables, start // page, npg, axis=1).reshape(-1)  # (G*npg,)

    def put(pool, val):
        # (G, C, Hkv, ...) -> (G*npg, Hkv, page, ...) whole pages
        val = kernel_ref.to_pages(val, page)
        val = val.reshape(G * npg, *val.shape[2:])
        return pool.at[phys].set(val.astype(pool.dtype))

    new_pools = _scatter_pools(pools, fmt, k, v, put)

    qg = q.reshape(G, C, hkv, g, dh)
    aux = _kv_aux(new_pools)
    with jax.named_scope("paged_attn"):
        if current_rules() is not None:
            o = kernel_ref.paged_attn_prefill_ref(
                qg, new_pools["k_pages"], new_pools["v_pages"],
                page_tables, start, kv_format=fmt, kv_aux=aux,
                pin_logits=lambda lg: constrain(lg, None, "model",
                                                None, None, None))
        else:
            o = kernel_dispatch.paged_attn_prefill(
                qg, new_pools["k_pages"], new_pools["v_pages"],
                page_tables, start, kv_format=fmt, kv_aux=aux)
    o = o.reshape(G, C, hq * dh).astype(x.dtype)
    o = constrain(o, None, None, None)      # see attn_decode_paged
    y = dense_apply(p["wo"], o, cfg.quant)
    return y, new_pools
