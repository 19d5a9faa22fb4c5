"""Shared building blocks: SC-aware dense, norms, RoPE, embeddings.

Every projection in the zoo routes through :func:`dense_apply`, which is
where the paper's technique plugs into arbitrary architectures: with
``quant.mode == "sc_qat"`` the matmul becomes ternary-weight x thermometer-
activation fake-quant (LSQ), with ``"none"`` it is a plain matmul, and
with ``"sc_int"`` it runs the silicon-equivalent integer datapath
(``sc_linear_int_from_qat``: int8 codes x ternary weights, int32 / BSN
accumulate) — what ServeEngine's ``datapath="sc_int"`` serves.

Param/spec convention: each ``*_init`` returns a pytree of arrays and each
``*_spec`` returns the matching pytree of ``PartitionSpec`` (physical axes
``"data"`` = FSDP, ``"model"`` = TP).  Stacked-layer leading axes are added
by the caller (transformer.py) with ``add_leading_none``.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sc_layers import SCQuantConfig, sc_linear_qat

DATA, MODEL = "data", "model"

__all__ = [
    "DATA", "MODEL",
    "dense_init", "dense_spec", "dense_apply",
    "norm_init", "norm_spec", "norm_apply",
    "embed_init", "embed_spec",
    "rope_freqs", "apply_rope",
    "ACT_FNS", "add_leading_none", "softcap", "big_neg",
]


def big_neg(dtype) -> float:
    return float(jnp.finfo(dtype).min) * 0.5


# ---------------------------------------------------------------------------
# dense (SC-quantization aware)
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int, quant: SCQuantConfig,
               dtype=jnp.bfloat16, scale: float | None = None) -> dict:
    std = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)
    p = {"w": w}
    if quant.enabled:
        if quant.per_channel:
            aw = jnp.full((d_out,), 1.4 * std * 0.8, jnp.float32)
        else:
            aw = jnp.asarray(1.4 * std * 0.8, jnp.float32)
        p["alpha_w"] = aw
        p["alpha_a"] = jnp.asarray(2.0 / math.sqrt(max(quant.act_half, 1)),
                                   jnp.float32)
    return p


def dense_spec(in_axis: str | None, out_axis: str | None,
               quant: SCQuantConfig) -> dict:
    """PartitionSpecs for one dense layer's params.

    Axis convention: training uses Megatron pairs (column-parallel
    ``(DATA, MODEL)`` feeding row-parallel ``(MODEL, DATA)``); the
    serving layout (attn_spec/ffn_spec ``serving=True``) passes
    ``(None, MODEL)`` everywhere — output channels shard, contractions
    stay device-local so the per-channel SC accumulators never split
    across chips (see serving/README.md).  Per-channel ``alpha_w``
    follows the out axis so the quantizer scale lives with its column.
    """
    s = {"w": P(in_axis, out_axis)}
    if quant.enabled:
        s["alpha_w"] = P(out_axis) if quant.per_channel else P()
        s["alpha_a"] = P()
    return s


def dense_apply(p: dict, x: jax.Array, quant: SCQuantConfig) -> jax.Array:
    """The SC integration point (see module docstring).

    Quantizer math runs f32 (LSQ grads need it) but the fake-quant VALUES
    are cast back to the compute dtype before the matmul: quantized values
    are exact small multiples of alpha, so bf16 carries them with ~1e-3
    relative rounding while halving weight-gather traffic and doubling MXU
    rate vs an f32 datapath (§Perf iteration 1).
    """
    from repro.core.quant import ternary_weight_quant, thermometer_act_quant
    if not quant.enabled:
        return x @ p["w"]
    if quant.mode == "sc_int":
        # serving: the silicon-equivalent integer path (int8 x ternary ->
        # int32 accumulate, optionally through the approximate BSN adder)
        from repro.core.sc_layers import sc_linear_int_from_qat
        return sc_linear_int_from_qat(p, x, quant)
    if quant.mode != "sc_qat":
        return x @ p["w"]
    x_fq = thermometer_act_quant(x, p["alpha_a"], quant.act_bsl)
    w_fq = ternary_weight_quant(p["w"], p["alpha_w"])
    return x_fq @ w_fq.astype(x_fq.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_spec(kind: str) -> dict:
    s = {"scale": P(None)}
    if kind == "layernorm":
        s["bias"] = P(None)
    return s


def _row_mean(x: jax.Array) -> jax.Array:
    """Mean over the last axis (kept), summed as a fixed pairwise tree of
    elementwise adds.

    XLA lowers a reduce in an order that follows the array's shape and
    layout — on a TPU a (1, 1, d) row and a (4, 64, d) batch are summed
    differently — so a row's norm statistic would change with its batch
    neighbours.  Halving adds fix the order per row, which keeps batched
    serving bit-identical to the one-request oracle.
    """
    n = x.shape[-1]
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        y = x[..., :h] + x[..., h:2 * h]
        if x.shape[-1] % 2:
            y = jnp.concatenate([y[..., :1] + x[..., 2 * h:], y[..., 1:]],
                                axis=-1)
        x = y
    return x / n


def norm_apply(p: dict, x: jax.Array, kind: str, eps: float = 1e-6,
               groups: int = 0) -> jax.Array:
    """rmsnorm / layernorm / (grouped layernorm when groups > 0, for RWKV)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if groups:
        shp = xf.shape[:-1] + (groups, xf.shape[-1] // groups)
        xg = xf.reshape(shp)
        mu = _row_mean(xg)
        var = _row_mean((xg - mu) ** 2)
        xf = ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(xf.shape)
        out = xf * p["scale"] + p.get("bias", 0.0)
        return out.astype(dt)
    if kind == "rmsnorm":
        var = _row_mean(xf * xf)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    elif kind == "layernorm":
        mu = _row_mean(xf)
        var = _row_mean((xf - mu) ** 2)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        raise ValueError(kind)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.bfloat16) -> dict:
    t = jax.random.normal(key, (vocab, d), jnp.float32) * (1.0 / math.sqrt(d))
    return {"table": t.astype(dtype)}


def embed_spec() -> dict:
    return {"table": P(MODEL, DATA)}


# ---------------------------------------------------------------------------
# rotary position embeddings (partial-fraction support for stablelm)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, fraction: float, theta: float):
    rot_dim = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                           / rot_dim))
    return rot_dim, inv                      # (rot_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, head_dim: int,
               fraction: float, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) or (S,)."""
    rot_dim, inv = rope_freqs(head_dim, fraction, theta)
    if rot_dim == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv     # (B,S,R/2)
    cos = jnp.cos(ang)[..., None, :]                          # (B,S,1,R/2)
    sin = jnp.sin(ang)[..., None, :]
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# activations / misc
# ---------------------------------------------------------------------------

ACT_FNS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap else x


def add_leading_none(spec_tree):
    """Prepend a None (stacked-layer) axis to every PartitionSpec leaf."""
    return jax.tree.map(lambda s: P(None, *s),
                        spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
