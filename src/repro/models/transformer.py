"""Unified LM: dense / MoE / SSM / hybrid / encoder / VLM-backbone.

The architecture is a *period* of heterogeneous layers (cfg.period)
repeated ``cfg.n_periods`` times; parameters are stacked over the period
axis and the forward pass is a single ``lax.scan`` (compile time stays
flat in depth — required for the 94-layer qwen3 dry-run), with per-period
``jax.checkpoint`` remat.

High-precision-residual fusion (paper §III): in ``sc_qat`` mode the
datapath matmuls run at ``act_bsl`` while the residual stream re-quantizes
at ``resid_bsl`` after every add (learned scales ``alpha_r*``), the LM
analogue of Fig 6(b).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig
from repro.core.kv_quant import check_kv_format
from repro.core.sc_layers import sc_residual_quant
from repro.distributed.sharding import constrain, constrain_tree

from . import attention, ffn, mamba, moe, rwkv6
from .common import (DATA, MODEL, add_leading_none, dense_apply, dense_init,
                     dense_spec, embed_init, embed_spec, norm_apply,
                     norm_init, norm_spec)

__all__ = ["init_params", "param_specs", "forward", "loss_fn", "init_cache",
           "cache_specs", "paged_cache_specs", "decode_step", "prefill",
           "batch_specs", "make_dummy_batch", "init_paged_cache",
           "paged_decode_step", "paged_prefill", "supports_paged_prefill"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

_MIXER_INIT = {"attn": attention.attn_init, "mamba": mamba.mamba_init,
               "rwkv6": rwkv6.rwkv_tmix_init}
_MIXER_SPEC = {"attn": attention.attn_spec, "mamba": mamba.mamba_spec,
               "rwkv6": rwkv6.rwkv_tmix_spec}


def _ffn_init(key, cfg: ModelConfig, kind: str):
    if kind == "dense":
        return ffn.ffn_init(key, cfg)
    if kind == "moe":
        return moe.moe_init(key, cfg)
    if kind == "rwkv_cmix":
        return rwkv6.rwkv_cmix_init(key, cfg)
    raise ValueError(kind)


def _ffn_spec(cfg: ModelConfig, kind: str, serving: bool = False):
    if kind == "dense":
        return ffn.ffn_spec(cfg, serving=serving)
    if kind == "moe":
        return moe.moe_spec(cfg, serving=serving)
    if kind == "rwkv_cmix":
        return rwkv6.rwkv_cmix_spec(cfg)
    raise ValueError(kind)


def _position_init(key: jax.Array, cfg: ModelConfig, spec: LayerSpec) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"norm1": norm_init(cfg.d_model, cfg.norm),
         "mixer": _MIXER_INIT[spec.mixer](k1, cfg)}
    if spec.ffn != "none":
        p["norm2"] = norm_init(cfg.d_model, cfg.norm)
        p["ffn"] = _ffn_init(k2, cfg, spec.ffn)
    if cfg.quant.enabled:
        p["alpha_r1"] = jnp.asarray(0.05, jnp.float32)
        p["alpha_r2"] = jnp.asarray(0.05, jnp.float32)
    return p


def _period_init(key: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, len(cfg.period))
    return {f"p{i}": _position_init(ks[i], cfg, spec)
            for i, spec in enumerate(cfg.period)}


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_per, k_head, k_front = jax.random.split(key, 4)
    params = {"embed": embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                  dtype)}
    period_keys = jax.random.split(k_per, cfg.n_periods)
    params["periods"] = jax.vmap(partial(_period_init, cfg=cfg))(period_keys)
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm)
    params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.padded_vocab,
                                   cfg.quant, dtype=dtype)
    if cfg.frontend == "vision_stub":
        kv1, kv2 = jax.random.split(k_front)
        params["frontend"] = {
            "w1": dense_init(kv1, 1024, cfg.d_model, cfg.quant, dtype=dtype),
            "w2": dense_init(kv2, cfg.d_model, cfg.d_model, cfg.quant,
                             dtype=dtype)}
    elif cfg.frontend == "audio_stub":
        params["frontend"] = {
            "w1": dense_init(k_front, 512, cfg.d_model, cfg.quant,
                             dtype=dtype)}
    return params


def param_specs(cfg: ModelConfig, serving: bool = False) -> dict:
    def mixer_spec(spec: LayerSpec) -> dict:
        if spec.mixer == "attn":
            return attention.attn_spec(cfg, serving=serving)
        return _MIXER_SPEC[spec.mixer](cfg)

    def pos_spec(spec: LayerSpec) -> dict:
        s = {"norm1": norm_spec(cfg.norm),
             "mixer": mixer_spec(spec)}
        if spec.ffn != "none":
            s["norm2"] = norm_spec(cfg.norm)
            s["ffn"] = _ffn_spec(cfg, spec.ffn, serving=serving)
        if cfg.quant.enabled:
            s["alpha_r1"] = P()
            s["alpha_r2"] = P()
        return s

    periods = {f"p{i}": pos_spec(spec) for i, spec in enumerate(cfg.period)}
    specs = {
        "embed": embed_spec(),
        "periods": add_leading_none(periods),
        "final_norm": norm_spec(cfg.norm),
        # serving: vocab column-parallel with the d_model contraction
        # local (same no-split-accumulator rule as attn/ffn specs)
        "lm_head": dense_spec(None if serving else DATA, MODEL, cfg.quant),
    }
    if cfg.frontend == "vision_stub":
        specs["frontend"] = {"w1": dense_spec(None, None, cfg.quant),
                             "w2": dense_spec(None, None, cfg.quant)}
    elif cfg.frontend == "audio_stub":
        specs["frontend"] = {"w1": dense_spec(None, None, cfg.quant)}
    return specs


# ---------------------------------------------------------------------------
# shared layer application
# ---------------------------------------------------------------------------

def _residual_add(x, dx, lp, name, cfg: ModelConfig):
    # dtype-preserving residual quant: an f32 round-trip here would promote
    # the whole backward pass (every TP all-reduce) to f32 — §Perf cell C
    y = x + dx
    if cfg.quant.enabled and cfg.quant.mode == "sc_qat":
        y = sc_residual_quant(y, lp[name], cfg.quant)
    return y


def _verify_scan(fn, x, state):
    """Scan a per-token DECODE mixer over the (S, T, D) verify window.

    Speculative verify must produce bit-identical hidden states to T
    successive decode steps — so rather than trust a batched recurrence
    kernel to reassociate identically, it literally runs the decode-mode
    update once per window token (the recurrent cores are a handful of
    ops; the heavy attention/FFN work around them stays batched over
    the window).  Returns (dx (S, T, D), snaps) where snaps stacks the
    post-token state pytree along a leading T axis — the engine commits
    exactly one snapshot per lane (its accepted-prefix length).
    """
    def body(st, xt):
        dx, st2 = fn(xt[:, None, :], st)
        return st2, (dx[:, 0, :], st2)
    _, (dxs, snaps) = jax.lax.scan(body, state, jnp.moveaxis(x, 0, 1))
    return jnp.moveaxis(dxs, 0, 1), snaps


def _apply_position(lp: dict, spec: LayerSpec, x, cfg: ModelConfig,
                    positions, mode: str, cstate: dict | None, pos):
    """One layer (mixer + ffn). Returns (x, aux, new_cache_entry)."""
    aux = jnp.zeros((), jnp.float32)
    centry = {}
    # Paged serving runs under the column-parallel serving specs: every
    # projection output is feature-sharded over "model", so the residual
    # stream is pinned back to replicated after each add.  This is the
    # "all-gather activations" half of the serving layout — and it keeps
    # every norm/quantizer reduction device-local, which is what makes
    # mesh-on decode token-identical to mesh-off (no resharded float
    # reductions).  `constrain` is the identity when no mesh is active.
    paged = (mode == "paged_prefill"
             or (cstate is not None and "page_tables" in cstate))
    def repl(y):
        return constrain(y, None, None, None) if paged else y
    h = norm_apply(lp["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        if mode == "decode" and "k_pages" in (cstate or {}):
            # batched paged decode: pos is the (S,) per-slot length
            # vector; the pool dict's keys carry the kv_format (scale /
            # residual leaves present iff the cache is compressed)
            dx, centry = attention.attn_decode_paged(
                lp["mixer"], h, cfg, cstate, pos)
        elif mode == "paged_prefill":
            dx, centry = attention.attn_prefill_paged(
                lp["mixer"], h, cfg, cstate, cstate["start"])
        elif mode == "verify":
            # speculative verify: all T window queries in one parallel
            # pass, each under its own causal horizon (pos = lengths)
            dx, centry = attention.attn_verify_paged(
                lp["mixer"], h, cfg, cstate, pos)
        elif mode == "decode":
            dx, kc, vc = attention.attn_decode(
                lp["mixer"], h, cfg, cstate["k"], cstate["v"], pos)
            centry = {"k": kc, "v": vc}
        else:
            dx, (k, v) = attention.attn_train(lp["mixer"], h, cfg, positions)
            if mode == "prefill":
                centry = {"k": k, "v": v}
    elif spec.mixer == "mamba":
        # prefill (exact AND chunked-paged) runs the chunk-resumable
        # per-token recurrence: exact prefill is the one-chunk special
        # case (zero state in), so chunked serving prefill is bit-equal
        # to it at every split.  Train keeps the associative scan.
        if mode == "decode":
            dx, centry = mamba.mamba_decode(lp["mixer"], h, cfg, cstate)
        elif mode == "verify":
            dx, centry = _verify_scan(
                lambda xt, st: mamba.mamba_decode(lp["mixer"], xt, cfg, st),
                h, {"h": cstate["h"], "conv": cstate["conv"]})
        elif mode == "paged_prefill":
            dx, centry = mamba.mamba_prefill_chunk(
                lp["mixer"], h, cfg,
                {"h": cstate["h"], "conv": cstate["conv"]},
                valid=cstate["valid"])
        elif mode == "prefill":
            dx, centry = mamba.mamba_prefill_chunk(
                lp["mixer"], h, cfg,
                mamba.mamba_state_init(cfg, h.shape[0], h.dtype))
        else:
            dx, _ = mamba.mamba_train(lp["mixer"], h, cfg)
    elif spec.mixer == "rwkv6":
        if mode == "decode":
            dx, centry = rwkv6.rwkv_tmix_decode(lp["mixer"], h, cfg, cstate)
        elif mode == "verify":
            dx, centry = _verify_scan(
                lambda xt, st: rwkv6.rwkv_tmix_decode(
                    lp["mixer"], xt, cfg, st),
                h, {"s": cstate["s"], "shift": cstate["shift"]})
        elif mode == "paged_prefill":
            dx, centry = rwkv6.rwkv_tmix_prefill_chunk(
                lp["mixer"], h, cfg,
                {"s": cstate["s"], "shift": cstate["shift"]},
                valid=cstate["valid"])
        elif mode == "prefill":
            dx, centry = rwkv6.rwkv_tmix_prefill_chunk(
                lp["mixer"], h, cfg,
                rwkv6.rwkv_state_init(cfg, h.shape[0], h.dtype))
        else:
            dx, _ = rwkv6.rwkv_tmix_train(lp["mixer"], h, cfg)
    else:
        raise ValueError(spec.mixer)
    x = repl(_residual_add(x, repl(dx), lp, "alpha_r1", cfg))

    if spec.ffn != "none":
        h2 = norm_apply(lp["norm2"], x, cfg.norm)
        if spec.ffn == "dense":
            dx2 = ffn.ffn_apply(lp["ffn"], h2, cfg)
        elif spec.ffn == "moe":
            dx2, aux_l = moe.moe_apply(lp["ffn"], h2, cfg)
            aux = aux + aux_l
        elif spec.ffn == "rwkv_cmix":
            if mode == "decode":
                dx2, cshift = rwkv6.rwkv_cmix_decode(
                    lp["ffn"], h2, cfg, cstate["cmix"] if cstate else None)
                centry = dict(centry, cmix=cshift)
            elif mode == "verify":
                dx2, cshift = _verify_scan(
                    lambda xt, st: rwkv6.rwkv_cmix_decode(
                        lp["ffn"], xt, cfg, st),
                    h2, cstate["cmix"])
                centry = dict(centry, cmix=cshift)
            elif mode == "paged_prefill":
                dx2, cshift = rwkv6.rwkv_cmix_prefill_chunk(
                    lp["ffn"], h2, cfg, cstate["cmix"],
                    valid=cstate["valid"])
                centry = dict(centry, cmix=cshift)
            elif mode == "prefill":
                dx2, cshift = rwkv6.rwkv_cmix_prefill_chunk(
                    lp["ffn"], h2, cfg,
                    {"shift": jnp.zeros((h2.shape[0], cfg.d_model),
                                        h2.dtype)})
                centry = dict(centry, cmix=cshift)
            else:
                dx2, _ = rwkv6.rwkv_cmix_train(lp["ffn"], h2, cfg)
        x = repl(_residual_add(x, repl(dx2), lp, "alpha_r2", cfg))
    return x, aux, centry


def _cstate_for(spec: LayerSpec, cperiod, idx):
    if cperiod is None:
        return None
    entry = cperiod[f"p{idx}"]
    if spec.ffn == "rwkv_cmix" and spec.mixer == "rwkv6":
        return entry          # holds both tmix keys and "cmix"
    return entry


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ModelConfig):
    table = params["embed"]["table"]
    if cfg.frontend == "vision_stub":
        fe = params["frontend"]
        ximg = jax.nn.gelu(dense_apply(fe["w1"], batch["patch_embeds"]
                                       .astype(table.dtype), cfg.quant))
        ximg = dense_apply(fe["w2"], ximg, cfg.quant)
        xtxt = jnp.take(table, batch["tokens"], axis=0)
        x = jnp.concatenate([ximg, xtxt], axis=1)
    elif cfg.frontend == "audio_stub":
        x = dense_apply(params["frontend"]["w1"],
                        batch["frames"].astype(table.dtype), cfg.quant)
    else:
        x = jnp.take(table, batch["tokens"], axis=0)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


def _vocab_bias(cfg: ModelConfig, dtype):
    """-inf on padded vocab slots."""
    iota = jnp.arange(cfg.padded_vocab)
    return jnp.where(iota < cfg.vocab_size, 0.0, -1e9).astype(dtype)


def forward(params: dict, batch: dict, cfg: ModelConfig, mode: str = "train",
            return_hidden: bool = False):
    """Returns (logits_or_hidden, aux, cache_periods_or_None)."""
    assert mode in ("train", "prefill")
    x, positions = _embed_inputs(params, batch, cfg)
    x = constrain(x, "batch", None, None)

    def period_body(carry, pp):
        x, aux = carry
        centries = {}
        for idx, spec in enumerate(cfg.period):
            x, aux_l, ce = _apply_position(pp[f"p{idx}"], spec, x, cfg,
                                           positions, mode, None, None)
            aux = aux + aux_l
            if mode == "prefill":
                centries[f"p{idx}"] = ce
        x = constrain(x, "batch", None, None)
        return (x, aux), centries

    body = period_body
    if cfg.remat == "full":
        body = jax.checkpoint(period_body, prevent_cse=False)
    (x, aux), cache_periods = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["periods"])

    x = norm_apply(params["final_norm"], x, cfg.norm)
    if return_hidden:
        return x, aux, (cache_periods if mode == "prefill" else None)
    logits = dense_apply(params["lm_head"], x, cfg.quant)
    logits = logits + _vocab_bias(cfg, logits.dtype)
    logits = constrain(logits, "batch", None, "model")
    return logits, aux, (cache_periods if mode == "prefill" else None)


def _nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    tl = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return lse - tl


def loss_fn(params: dict, batch: dict, cfg: ModelConfig):
    targets = batch["targets"]
    mask = batch.get("loss_mask")
    if cfg.ce_chunks > 1:
        # chunked CE: the (B, S, V) logits tensor never materializes —
        # each sequence chunk projects + reduces under jax.checkpoint, so
        # backward recomputes the chunk logits instead of saving them
        # (§Perf: the 256k-vocab archs are dominated by CE traffic).
        hidden, aux, _ = forward(params, batch, cfg, mode="train",
                                 return_hidden=True)
        B, S, _ = hidden.shape
        nc = cfg.ce_chunks
        while S % nc:
            nc -= 1
        bias = _vocab_bias(cfg, jnp.float32)

        @jax.checkpoint
        def chunk_nll(xc, tc):
            lc = dense_apply(params["lm_head"], xc, cfg.quant)
            return _nll(lc.astype(jnp.float32) + bias, tc)

        def body(_, inp):
            return None, chunk_nll(*inp)

        xcs = hidden.reshape(B, nc, S // nc, -1).swapaxes(0, 1)
        tcs = targets.reshape(B, nc, S // nc).swapaxes(0, 1)
        _, nll_c = jax.lax.scan(body, None, (xcs, tcs))
        nll = nll_c.swapaxes(0, 1).reshape(B, S)
    else:
        logits, aux, _ = forward(params, batch, cfg, mode="train")
        nll = _nll(logits, targets)
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (nll * mask).sum() / denom
    else:
        ce = nll.mean()
    loss = ce + 1e-2 * aux
    metrics = {"loss": loss, "ce": ce, "aux": aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# caches / decode
# ---------------------------------------------------------------------------

def _cache_entry_shapes(cfg: ModelConfig, spec: LayerSpec, batch: int,
                        max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    e = {}
    if spec.mixer == "attn":
        dh, hkv = cfg.head_dim, cfg.n_kv_heads
        e["k"] = jnp.zeros((batch, max_len, hkv, dh), dtype)
        e["v"] = jnp.zeros((batch, max_len, hkv, dh), dtype)
    elif spec.mixer == "mamba":
        e.update(mamba.mamba_state_init(cfg, batch, dtype))
    elif spec.mixer == "rwkv6":
        e.update(rwkv6.rwkv_state_init(cfg, batch, dtype))
    if spec.ffn == "rwkv_cmix":
        e["cmix"] = {"shift": jnp.zeros((batch, cfg.d_model), dtype)}
    return e


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    one = {f"p{i}": _cache_entry_shapes(cfg, spec, batch, max_len)
           for i, spec in enumerate(cfg.period)}
    periods = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy(), one)
    return {"pos": jnp.zeros((), jnp.int32), "periods": periods}


def cache_specs(cfg: ModelConfig, seq_shard: bool = False,
                kv_head_shard: bool = True) -> dict:
    """Logical-axis tuples per cache leaf (resolved by MeshRules).

    ``seq_shard``: shard KV time over the "seq" (data) axis — long_500k
    context parallelism.  ``kv_head_shard=False``: KV head count doesn't
    divide the model axis (e.g. qwen3 kv=4 over TP=16 would pad 4x HBM);
    shard KV time over "model" instead (flash-decoding split-KV).
    """
    if seq_shard:
        # long-context: batch==1, the "seq"(=data) axis takes the KV time
        # dim — batch must not also claim it (duplicate-axis spec)
        kv_b, kv_seq, kv_h = None, "seq", None
    elif kv_head_shard:
        kv_b, kv_seq, kv_h = "batch", None, "model"
    else:
        kv_b, kv_seq, kv_h = "batch", "model", None
    def entry(spec: LayerSpec) -> dict:
        e = {}
        if spec.mixer == "attn":
            e["k"] = (None, kv_b, kv_seq, kv_h, None)
            e["v"] = (None, kv_b, kv_seq, kv_h, None)
        elif spec.mixer == "mamba":
            e["h"] = (None, "batch", "model", None)
            e["conv"] = (None, "batch", None, "model")
        elif spec.mixer == "rwkv6":
            e["s"] = (None, "batch", "model", None, None)
            e["shift"] = (None, "batch", None)
        if spec.ffn == "rwkv_cmix":
            e["cmix"] = {"shift": (None, "batch", None)}
        return e

    periods = {f"p{i}": entry(spec) for i, spec in enumerate(cfg.period)}
    return {"pos": (), "periods": periods}


def decode_step(params: dict, cache: dict, tokens: jax.Array,
                cfg: ModelConfig):
    """tokens: (B, 1) int32. Returns (logits (B,1,V), new cache)."""
    assert not cfg.is_encoder, "encoder archs have no decode step"
    pos = cache["pos"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    x = constrain(x, "batch", None, None)

    def period_body(x, inp):
        pp, cper = inp
        new_entries = {}
        for idx, spec in enumerate(cfg.period):
            cst = _cstate_for(spec, cper, idx)
            x, _, ce = _apply_position(pp[f"p{idx}"], spec, x, cfg,
                                       None, "decode", cst, pos)
            new_entries[f"p{idx}"] = ce
        return x, new_entries

    x, new_periods = jax.lax.scan(period_body, x,
                                  (params["periods"], cache["periods"]))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = dense_apply(params["lm_head"], x, cfg.quant)
    logits = logits + _vocab_bias(cfg, logits.dtype)
    return logits, {"pos": pos + 1, "periods": new_periods}


def prefill(params: dict, batch: dict, cfg: ModelConfig):
    """Full-context forward that also builds the decode cache."""
    logits, aux, cache_periods = forward(params, batch, cfg, mode="prefill")
    seq = logits.shape[1]
    return logits, {"pos": jnp.asarray(seq, jnp.int32),
                    "periods": cache_periods}


# ---------------------------------------------------------------------------
# paged serving cache (ServeEngine v2)
# ---------------------------------------------------------------------------
#
# Layout: attention positions hold *shared* head-major page pools
# ``(num_pages, Hkv, page, Dh)`` (which request owns which page is the
# engine's page table, serving/paging.py); recurrent positions hold
# per-slot state ROWS ``(max_slots + 1, ...)`` — row ``max_slots`` is the
# scratch lane that padded lanes read/write so bucket padding never
# touches a live request: padded DECODE lanes gather/scatter it by slot
# id, and padded PREFILL lanes scatter their (frozen-at-zero) final
# state into it.  Prefill never READS the rows — prompt state always
# starts from zero, so a recycled slot's stale rows are dead by
# construction.  All entries carry the usual leading ``n_periods`` axis
# so the period scan is identical to train/decode.


def supports_paged_prefill(cfg: ModelConfig) -> bool:
    """Chunked paged prefill covers EVERY decoder period: attention
    positions scatter whole K/V pages, recurrent positions (mamba /
    rwkv6 / rwkv_cmix) thread chunk-resumable state — conv tail +
    SSM/WKV state + token shift — across chunk boundaries, order-exact
    (see mamba_prefill_chunk / rwkv_tmix_prefill_chunk).  Only frontend
    archs (vision/audio stubs) are excluded: their inputs aren't token
    prompts, so they take the exact-length per-request path."""
    return cfg.frontend == "none"


def init_paged_cache(cfg: ModelConfig, max_slots: int, num_pages: int,
                     page_size: int, kv_format: str = "fp") -> dict:
    """``kv_format`` (core/kv_quant.py) picks the attention pool storage:
    "fp" keeps cfg.dtype pages; "int8"/"sc" store int8 level pools plus a
    parallel per-position-per-head f32 scale pool (+ the sc int8 residual
    pool).  Pools are head-major — KV ``(num_pages, Hkv, page, Dh)``,
    scales ``(num_pages, Hkv, page)`` — so a Pallas block of one head's
    page ``(page, Dh)`` spans whole trailing dims (kernels/
    paged_attention.py).  All-zero init dequantizes to exact 0 in every
    format, so the trash page and unwritten positions behave identically
    to fp."""
    check_kv_format(kv_format)
    dtype = jnp.dtype(cfg.dtype)
    rows = max_slots + 1                      # + scratch lane
    dh, hkv = cfg.head_dim, cfg.n_kv_heads

    def entry(spec: LayerSpec) -> dict:
        e = {}
        if spec.mixer == "attn":
            kv_dt = dtype if kv_format == "fp" else jnp.int8
            e["k_pages"] = jnp.zeros((num_pages, hkv, page_size, dh), kv_dt)
            e["v_pages"] = jnp.zeros((num_pages, hkv, page_size, dh), kv_dt)
            if kv_format != "fp":
                sshape = (num_pages, hkv, page_size)
                e["k_scale"] = jnp.zeros(sshape, jnp.float32)
                e["v_scale"] = jnp.zeros(sshape, jnp.float32)
            if kv_format == "sc":
                rshape = (num_pages, hkv, page_size, dh)
                e["k_resid"] = jnp.zeros(rshape, jnp.int8)
                e["v_resid"] = jnp.zeros(rshape, jnp.int8)
        elif spec.mixer == "mamba":
            e.update(mamba.mamba_state_init(cfg, rows, dtype))
        elif spec.mixer == "rwkv6":
            e.update(rwkv6.rwkv_state_init(cfg, rows, dtype))
        if spec.ffn == "rwkv_cmix":
            e["cmix"] = {"shift": jnp.zeros((rows, cfg.d_model), dtype)}
        return e

    one = {f"p{i}": entry(spec) for i, spec in enumerate(cfg.period)}
    periods = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy(), one)
    return {"periods": periods}


def paged_cache_specs(cfg: ModelConfig, kv_format: str = "fp") -> dict:
    """Logical-axis tuples per paged-cache leaf (shard_tree(logical=True)).

    KV page pools shard over their head axis ("model" carries KV heads —
    each device holds every page but only its heads); recurrent state
    rows shard their channel axis the same way.  Page/row axes stay
    unsharded: which page a request owns is HOST bookkeeping
    (serving/paging.py) and must remain device-count-agnostic.  Leaves
    whose channel count doesn't divide the mesh axis degrade to
    replicated via ``fit_spec``.  ``kv_format`` must match
    :func:`init_paged_cache`'s — ``shard_tree`` maps the spec tree over
    the cache tree leaf-for-leaf, so the scale/residual specs exist
    exactly when their pools do (same head axis over "model").
    """
    check_kv_format(kv_format)
    def entry(spec: LayerSpec) -> dict:
        e = {}
        if spec.mixer == "attn":
            # (n_periods, num_pages, Hkv, page, Dh)
            e["k_pages"] = (None, None, "model", None, None)
            e["v_pages"] = (None, None, "model", None, None)
            if kv_format != "fp":
                # (n_periods, num_pages, Hkv, page)
                e["k_scale"] = (None, None, "model", None)
                e["v_scale"] = (None, None, "model", None)
            if kv_format == "sc":
                e["k_resid"] = (None, None, "model", None, None)
                e["v_resid"] = (None, None, "model", None, None)
        elif spec.mixer == "mamba":
            # h: (n_periods, rows, d_inner, n); conv: (…, k-1, d_inner)
            e["h"] = (None, None, "model", None)
            e["conv"] = (None, None, None, "model")
        elif spec.mixer == "rwkv6":
            # s: (n_periods, rows, heads, dh, dh)
            e["s"] = (None, None, "model", None, None)
            e["shift"] = (None, None, None)
        if spec.ffn == "rwkv_cmix":
            e["cmix"] = {"shift": (None, None, None)}
        return e

    periods = {f"p{i}": entry(spec) for i, spec in enumerate(cfg.period)}
    return {"periods": periods}


# shared page-pool leaves (passed whole to every lane, never gathered by
# slot id) vs per-slot state rows; the scale/residual pools of the
# compressed kv_formats are pools like the pages they describe
_POOL_KEYS = ("k_pages", "v_pages", "k_scale", "v_scale",
              "k_resid", "v_resid")


def _split_pools(cache: dict, cfg: ModelConfig) -> tuple[dict, dict]:
    """The cache's period entries split into the page-pool stacks and the
    per-slot state rows, each ``{"p<i>": {leaf: (n_periods, ...)}}``."""
    pools, rows = {}, {}
    for i in range(len(cfg.period)):
        pe = cache["periods"][f"p{i}"]
        pools[f"p{i}"] = {k: v for k, v in pe.items() if k in _POOL_KEYS}
        rows[f"p{i}"] = {k: v for k, v in pe.items() if k not in _POOL_KEYS}
    return pools, rows


def paged_decode_step(params: dict, cache: dict, tokens: jax.Array,
                      slot_ids: jax.Array, page_tables: jax.Array,
                      lengths: jax.Array, cfg: ModelConfig):
    """One batched decode step over the paged cache — every active slot
    advances one token in a single traced computation.

    tokens / slot_ids / lengths: (S,) int32 (S = padded slot bucket);
    page_tables: (S, maxp) int32.  Padded lanes carry slot_id ==
    max_slots (scratch row), length 0 and trash-page tables.  Returns
    (logits (S, V), new cache); retraces only when S or maxp change.

    Attention inside runs the flash-decoding paged Pallas kernel via
    kernels/dispatch (``attn_backend_scope`` pins it; the XLA gather is
    the reference oracle, and the only path under active mesh rules).
    """
    assert not cfg.is_encoder, "encoder archs have no decode step"
    x = jnp.take(params["embed"]["table"], tokens[:, None], axis=0)  # (S,1,D)
    x = constrain(x, None, None, None)   # embed table is vocab-sharded

    pools, rows = _split_pools(cache, cfg)
    layer_ids = jnp.arange(cfg.n_periods, dtype=jnp.int32)

    def period_body(carry, inp):
        # the pool stacks ride the carry whole: each attention position
        # scatters its new K/V into layer ``li`` in place and its kernel
        # reads layer ``li`` out of the stack, so no layer's pool is
        # sliced out or copied back.  State rows stay the loop's xs/ys.
        x, pools = carry
        pp, rper, li = inp
        pools, new_rows = dict(pools), {}
        for idx, spec in enumerate(cfg.period):
            key = f"p{idx}"
            cst = {k: jax.tree.map(lambda a: a[slot_ids], v)
                   for k, v in rper[key].items()}
            cst.update(pools[key])
            cst["page_tables"] = page_tables
            cst["layer"] = li
            x, _, ce = _apply_position(pp[key], spec, x, cfg,
                                       None, "decode", cst, lengths)
            pools[key] = {k: ce[k] for k in pools[key]}
            new_rows[key] = {
                k: jax.tree.map(
                    lambda full, rw: full.at[slot_ids].set(rw), v, ce[k])
                for k, v in rper[key].items()}
        return (x, pools), new_rows

    # the layer loop is named, so a profile can place the operations XLA
    # adds to it without a scope of their own
    with jax.named_scope("layers"):
        (x, pools), rows = jax.lax.scan(
            period_body, (x, pools), (params["periods"], rows, layer_ids))
    new_periods = {key: {**pools[key], **rows[key]} for key in pools}
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = dense_apply(params["lm_head"], x, cfg.quant)
    logits = logits + _vocab_bias(cfg, logits.dtype)
    # serving lm_head is column-parallel: pin the product's vocab axis to
    # "model" so GSPMD gathers exactly once — the sampler (or argmax)
    # downstream re-pins its crop to replicated, which is what makes the
    # categorical draw identical on and off the mesh
    logits = constrain(logits, None, None, "model")
    return logits[:, 0], {"periods": new_periods}


def paged_verify_step(params: dict, cache: dict, tokens: jax.Array,
                      slot_ids: jax.Array, page_tables: jax.Array,
                      lengths: jax.Array, cfg: ModelConfig):
    """Batched multi-token speculative-VERIFY step over the paged cache.

    tokens: (S, T) int32 — per lane, the last committed token followed
    by the T-1 draft tokens, occupying cache positions ``lengths`` ..
    ``lengths + T - 1``; other args exactly as
    :func:`paged_decode_step`.  One target-datapath forward scores the
    whole window: attention runs all T queries in parallel under
    per-query causal horizons (:func:`attention.attn_verify_paged`);
    recurrent mixers scan their decode-mode update per token
    (:func:`_verify_scan`), so logits row t is bit-arithmetically the
    decode-step logits after committing window tokens ``0..t`` — the
    spec-on == spec-off identity the differential tests pin.

    Returns ``(logits (S, T, V), new_cache, snaps)``:

    * the new cache holds the target-datapath K/V scatter for all T
      window positions (rows past the accepted prefix are dead — they
      sit beyond the committed length, so every later read masks them
      out and every later write lands on them first), while recurrent
      state ROWS are deliberately left untouched;
    * ``snaps`` stacks each period's post-token recurrent state along
      ``(n_periods, T, S, ...)`` — the engine picks lane s's
      accepted-prefix snapshot with :func:`select_state_snapshot` and
      commits it via :func:`scatter_state_rows`, all inside the same
      jit.
    """
    assert not cfg.is_encoder, "encoder archs have no decode step"
    x = jnp.take(params["embed"]["table"], tokens, axis=0)     # (S,T,D)
    x = constrain(x, None, None, None)

    def period_body(x, inp):
        pp, cper = inp
        new_entries, snaps = {}, {}
        for idx, spec in enumerate(cfg.period):
            entry = cper[f"p{idx}"]
            cst = {k: (v if k in _POOL_KEYS
                       else jax.tree.map(lambda a: a[slot_ids], v))
                   for k, v in entry.items()}
            cst["page_tables"] = page_tables
            x, _, ce = _apply_position(pp[f"p{idx}"], spec, x, cfg,
                                       None, "verify", cst, lengths)
            new_entries[f"p{idx}"] = {
                k: (ce[k] if k in _POOL_KEYS else entry[k])
                for k in entry}
            snaps[f"p{idx}"] = {k: v for k, v in ce.items()
                                if k not in _POOL_KEYS}
        return x, (new_entries, snaps)

    with jax.named_scope("layers"):
        x, (new_periods, snaps) = jax.lax.scan(
            period_body, x, (params["periods"], cache["periods"]))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    logits = dense_apply(params["lm_head"], x, cfg.quant)
    logits = logits + _vocab_bias(cfg, logits.dtype)
    logits = constrain(logits, None, None, "model")
    return logits, {"periods": new_periods}, snaps


def gather_state_rows(cache: dict, slot_ids: jax.Array) -> dict:
    """Snapshot the per-slot recurrent state rows (leaves
    ``(n_periods, S, ...)``) — the pre-draft checkpoint the engine
    restores after a draft pass, so the drafter's approximate
    arithmetic never contaminates the target-datapath state."""
    return jax.tree.map(
        lambda a: a[:, slot_ids],
        {p: {k: v for k, v in e.items() if k not in _POOL_KEYS}
         for p, e in cache["periods"].items()})


def scatter_state_rows(cache: dict, rows: dict,
                       slot_ids: jax.Array) -> dict:
    """Write :func:`gather_state_rows`-shaped rows back into the cache
    (attention pools pass through untouched)."""
    out = {}
    for p, e in cache["periods"].items():
        out[p] = {k: (v if k in _POOL_KEYS
                      else jax.tree.map(
                          lambda full, rw: full.at[:, slot_ids].set(rw),
                          v, rows[p][k]))
                  for k, v in e.items()}
    return {"periods": out}


def select_state_snapshot(snaps: dict, m: jax.Array) -> dict:
    """Pick one per-token state snapshot per lane.

    snaps: :func:`paged_verify_step` output, leaves
    ``(n_periods, T, S, ...)``; m: (S,) int32 in ``[0, T-1]`` — the
    window index of the last committed token.  Returns rows shaped for
    :func:`scatter_state_rows` (leaves ``(n_periods, S, ...)``): lane
    s's state after consuming window tokens ``0..m[s]``."""
    def sel(leaf):
        S = leaf.shape[2]
        return leaf[:, m, jnp.arange(S)]
    return jax.tree.map(sel, snaps)


def _group_state_entry(cfg: ModelConfig, spec: LayerSpec, G: int,
                       dtype) -> dict:
    """Zero recurrent state for the G prefill lanes (decode row shapes,
    batch axis = lane)."""
    e = {}
    if spec.mixer == "mamba":
        e.update(mamba.mamba_state_init(cfg, G, dtype))
    elif spec.mixer == "rwkv6":
        e.update(rwkv6.rwkv_state_init(cfg, G, dtype))
    if spec.ffn == "rwkv_cmix":
        e["cmix"] = {"shift": jnp.zeros((G, cfg.d_model), dtype)}
    return e


def _group_state_specs(cfg: ModelConfig, idx: int) -> dict:
    """Logical pins for the carried group state, DERIVED from
    :func:`paged_cache_specs` by dropping the leading period axis (the
    rows axis becomes the lane axis, replicated either way) — same
    channel axes over "model", one source of truth, so the
    chunk-to-chunk carry keeps the cache's sharding and mesh-on prefill
    stays token-identical to mesh-off."""
    entry = paged_cache_specs(cfg)["periods"][f"p{idx}"]
    return jax.tree.map(lambda lg: tuple(lg)[1:],
                        {k: v for k, v in entry.items()
                         if k not in _POOL_KEYS},
                        is_leaf=lambda s: isinstance(s, tuple))


def paged_prefill(params: dict, cache: dict, tokens: jax.Array,
                  page_tables: jax.Array, prompt_lens: jax.Array,
                  cfg: ModelConfig, *, chunk: int,
                  slot_ids: jax.Array | None = None):
    """Batched *chunked* prefill writing straight into the decode cache
    layout, for EVERY decoder period type (:func:`supports_paged_prefill`).

    tokens: (G, L) right-padded prompts (L a multiple of ``chunk``,
    ``chunk`` a multiple of the page size); page_tables: (G, maxp)
    covering at least ceil(L/page) entries (padding = trash page);
    prompt_lens: (G,); slot_ids: (G,) int32 slot of each lane (padding =
    the scratch row) — required when the period holds recurrent state.
    Each chunk runs the full period scan then dies — peak logits cost is
    (G, chunk, V) never (G, L, V).  Attention positions scatter the
    chunk's K/V as whole pages and attend over the pages written so far
    (the chunked paged-prefill Pallas kernel via kernels/dispatch, same
    backend chain as decode);
    recurrent positions consume the carried state (conv tail + SSM/WKV
    state + token shifts, zeros before the first chunk) and emit the
    updated carry, with right-padded positions masked so each lane's
    state freezes at its last real token (``valid`` select — exact, so
    any chunk size reproduces the one-shot prefill bit for bit).  The
    final carries scatter into the per-slot state rows at the end, all
    inside the caller's jit.  Returns (last_token_logits (G, V), new
    cache).
    """
    assert supports_paged_prefill(cfg), \
        "paged prefill serves token prompts only (frontend == none)"
    G, L = tokens.shape
    assert L % chunk == 0, (L, chunk)
    table = params["embed"]["table"]
    h_last = jnp.zeros((G, cfg.d_model), table.dtype)
    # split the cache: shared page pools ride the chunk loop's carry;
    # per-slot state rows are untouched until the final scatter (prompt
    # state starts from zero, never from a previous occupant's rows)
    pools, rows = _split_pools(cache, cfg)
    has_state = len(jax.tree_util.tree_leaves(rows)) > 0
    if has_state and slot_ids is None:
        raise ValueError("recurrent periods need slot_ids to place their "
                         "carried state rows")
    one = {f"p{i}": _group_state_entry(cfg, spec, G, table.dtype)
           for i, spec in enumerate(cfg.period)}
    gstate = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), one)

    layer_ids = jnp.arange(cfg.n_periods, dtype=jnp.int32)

    def chunk_body(carry, c):
        # one chunk = the full period scan.  The chunk loop is rolled
        # (``start`` is traced): one compiled body serves every chunk,
        # and the pools are a loop CARRY updated one layer at a time
        # (dynamic index in, ``.at[layer].set`` out), so XLA updates
        # them in place — prefill memory does not grow with the number
        # of chunks.
        pools, gstate, h_last = carry
        start = c * chunk
        xc = jnp.take(table, jax.lax.dynamic_slice_in_dim(
            tokens, start, chunk, axis=1), axis=0)
        xc = constrain(xc, None, None, None)
        valid = (start + jnp.arange(chunk, dtype=jnp.int32))[None, :] \
            < prompt_lens[:, None]                        # (G, chunk)

        def period_body(carry, inp):
            x, pools = carry
            pp, gsper, li = inp
            pools, new_gs = dict(pools), {}
            for idx, spec in enumerate(cfg.period):
                key = f"p{idx}"
                cst = {k: v[li] for k, v in pools[key].items()}
                cst.update(gsper[key])
                cst["page_tables"] = page_tables
                cst["start"] = start
                cst["valid"] = valid
                x, _, ce = _apply_position(pp[key], spec, x, cfg,
                                           None, "paged_prefill", cst, None)
                pools[key] = {k: v.at[li].set(ce[k])
                              for k, v in pools[key].items()}
                new_gs[key] = constrain_tree(
                    {k: v for k, v in ce.items() if k not in _POOL_KEYS},
                    _group_state_specs(cfg, idx))
            return (x, pools), new_gs

        with jax.named_scope("layers"):
            (xc, pools), gstate = jax.lax.scan(
                period_body, (xc, pools),
                (params["periods"], gstate, layer_ids))
        # keep the hidden state of each request's last real token
        last = prompt_lens - 1 - start
        rws = jnp.take_along_axis(
            xc, jnp.clip(last, 0, chunk - 1)[:, None, None], axis=1)[:, 0]
        h_last = jnp.where(((last >= 0) & (last < chunk))[:, None],
                           rws, h_last)
        return (pools, gstate, h_last), None

    with jax.named_scope("chunks"):
        (pools, gstate, h_last), _ = jax.lax.scan(
            chunk_body, (pools, gstate, h_last),
            jnp.arange(L // chunk, dtype=jnp.int32))

    # scatter each lane's final carry into its slot's state rows (padded
    # lanes land in the scratch row, whose contents no live request
    # reads)
    new_periods = {}
    for i in range(len(cfg.period)):
        entry = dict(pools[f"p{i}"])
        for name, rv in rows[f"p{i}"].items():
            gv = gstate[f"p{i}"][name]
            entry[name] = jax.tree.map(
                lambda full, g: full.at[:, slot_ids].set(
                    g.astype(full.dtype)), rv, gv)
        new_periods[f"p{i}"] = entry

    h = norm_apply(params["final_norm"], h_last[:, None, :], cfg.norm)
    logits = dense_apply(params["lm_head"], h, cfg.quant)[:, 0]
    logits = logits + _vocab_bias(cfg, logits.dtype)
    # same vocab-axis pin as paged_decode_step: sampling the first
    # generated token must see mesh-invariant logit rows
    logits = constrain(logits, None, "model")
    return logits, {"periods": new_periods}


# ---------------------------------------------------------------------------
# batch construction (shared by data pipeline / dryrun input_specs)
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, kind: str) -> dict:
    """Logical sharding tuples for each batch field."""
    if cfg.frontend == "vision_stub":
        d = {"patch_embeds": ("batch", None, None), "tokens": ("batch", None)}
    elif cfg.frontend == "audio_stub":
        d = {"frames": ("batch", None, None)}
    else:
        d = {"tokens": ("batch", None)}
    if kind == "train":
        d["targets"] = ("batch", None)
        d["loss_mask"] = ("batch", None)
    return d


def make_dummy_batch(cfg: ModelConfig, batch: int, seq: int, kind: str,
                     img_tokens: int = 0) -> dict:
    """Concrete (tiny) batches for smoke tests; dryrun uses ShapeDtypeStructs
    with the same structure (launch/dryrun.py)."""
    out = {}
    if cfg.frontend == "vision_stub":
        img = img_tokens or max(seq // 4, 1)
        out["patch_embeds"] = jnp.zeros((batch, img, 1024), jnp.bfloat16)
        out["tokens"] = jnp.zeros((batch, seq - img), jnp.int32)
    elif cfg.frontend == "audio_stub":
        out["frames"] = jnp.zeros((batch, seq, 512), jnp.bfloat16)
    else:
        out["tokens"] = jnp.zeros((batch, seq), jnp.int32)
    if kind == "train":
        out["targets"] = jnp.zeros((batch, seq), jnp.int32)
        out["loss_mask"] = jnp.ones((batch, seq), jnp.float32)
    return out
