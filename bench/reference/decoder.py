"""Plain reference of the served dense decoders.

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernels, no cache, no batching, one sequence at a time, one layer at a
time.  It imports nothing of the program.  Its weights are regenerated
from the seed by bench/weights.py, layer by layer.

It computes in float32 and rounds every activation to the precision the
configuration states (``torch_dtype``, bfloat16 here) after each
operation that yields one, as a model held in that dtype does: the
hidden state after every residual add, norm outputs, every projection's
output, q and k after the rotary embedding, the attention output, the
SwiGLU's sigmoid, its product with the gate and with ``up``, the
logits.  Rounding in a narrower float instead (float8 e4m3, below
bfloat16) makes the control that shows the check can fail.

What it computes is the model as the configuration runs it (the
configuration file lists where that departs from the published model):

* pre-norm blocks: RMSNorm, or LayerNorm with a bias, eps from the file;
* every projection on the ``sc_int`` datapath: the activation rounded to
  one of 9 levels, ``clip(round(x / alpha_a), -4, 4)``, the weight to a
  ternary ``clip(round(w / alpha_w), -1, 1)`` per output channel, the
  integer product rescaled by ``alpha_a * alpha_w``;
* rotary embedding on the first ``partial_rotary_factor`` of each head,
  rotating interleaved pairs ``(x[2i], x[2i+1])``;
* K and V stored as int8 levels, one scale per position and head,
  ``amax / 127``, as the configuration's ``kv_format`` states;
* grouped-query (or multi-head) causal softmax attention, scaled by
  ``1 / sqrt(head_dim)``;
* a SwiGLU MLP, ``silu(gate) * up`` then ``down``;
* a final norm and an untied LM head (itself an ``sc_int`` projection).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Dims, global_weights, layer_weights, seed_key

__all__ = ["Reference", "rounding"]

ACT_HALF = 4                 # 9 activation levels: act_bsl 8
KV_HALF = 127                # int8 K/V levels


def rounding(dtype: str):
    """Round float32 to ``dtype`` and back (identity for float32)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.float32:
        return lambda x: x
    return lambda x: x.astype(dt).astype(jnp.float32)


def _norm(x, scale, bias, kind, eps):
    if kind == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _sc_int(x, w, alpha_w, alpha_a):
    xq = jnp.clip(jnp.round(x / alpha_a), -ACT_HALF, ACT_HALF)
    wq = jnp.clip(jnp.round(w.astype(jnp.float32) / alpha_w), -1, 1)
    return (xq @ wq) * (alpha_a * alpha_w)


def _rope(x, fraction, theta):
    """x: (T, H, Dh), positions 0..T-1, interleaved pairs."""
    T, _, dh = x.shape
    rot = int(dh * fraction) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, R/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([y.reshape(T, -1, rot), x[..., rot:]], axis=-1)


def _int8_kv(x):
    """(T, H, Dh) -> dequantized int8 levels, one scale per (T, H)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.maximum(amax / KV_HALF, jnp.finfo(jnp.float32).tiny)
    return jnp.clip(jnp.round(x / s), -KV_HALF, KV_HALF) * s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _exact_attention(qg, k, v, n_valid, page, operand):
    T, _, _, dh = qg.shape
    s = jnp.einsum("thgd,uhd->hgtu", qg, k) / math.sqrt(dh)
    pos = jnp.arange(T)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_valid)
    s = jnp.where(mask, s, -1e30)
    return jnp.einsum("hgtu,uhd->thgd", jax.nn.softmax(s, axis=-1), v)


def _paged_attention(qg, k, v, n_valid, page, operand):
    """Softmax attention taken one page of ``page`` positions at a time
    with a running maximum, sum and output, ``operand`` applied to the
    operands of each page's two products (the order of a paged
    flash-attention kernel)."""
    T, hkv, g, dh = qg.shape
    kp = operand(k).reshape(T // page, page, hkv, dh)
    vp = operand(v).reshape(T // page, page, hkv, dh)
    rows = jnp.arange(T)

    def body(carry, j):
        m, l, acc = carry
        s = jnp.einsum("thgd,phd->hgtp", qg, kp[j]) / math.sqrt(dh)
        cols = j * page + jnp.arange(page)
        live = (cols[None, :] <= rows[:, None]) & (cols[None, :] < n_valid)
        s = jnp.where(live, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        w = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(w, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("hgtp,phd->hgtd",
                                                 operand(w), vp[j])
        return (m_new, l, acc), None
    init = (jnp.full((hkv, g, T), -1e30), jnp.zeros((hkv, g, T)),
            jnp.zeros((hkv, g, T, dh)))
    (_, l, acc), _ = jax.lax.scan(body, init, jnp.arange(T // page))
    return jnp.transpose(acc / l[..., None], (2, 0, 1, 3))


ATTENTION = {"exact": (_exact_attention, lambda x: x),
             "paged_f32": (_paged_attention, lambda x: x),
             "paged_bf16": (_paged_attention, _bf16)}


class Reference:
    """Logit readings of the reference (``act_dtype`` the configuration's
    dtype) or of its control (a narrower ``act_dtype``) for
    teacher-forced token sequences."""

    def __init__(self, dims: Dims, alpha_a: dict, seed: int,
                 weight_dtype, act_dtype, kv_int8: bool = True,
                 attention: str = "exact", page: int = 128):
        self.dims, self.alpha_a = dims, alpha_a
        self.attention, self.page = ATTENTION[attention], page
        self.act = rounding(act_dtype)
        dt = jnp.dtype(act_dtype)
        if dt in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
            # a transcendental is the dtype's own op, not a rounded
            # float32 one: the sigmoid of a bfloat16 model is computed
            # in bfloat16
            self.sigmoid = lambda v: jax.nn.sigmoid(v.astype(dt)) \
                .astype(jnp.float32)
        else:
            self.sigmoid = lambda v: self.act(jax.nn.sigmoid(v))
        self.key = seed_key(seed)
        self.dtype = jnp.dtype(weight_dtype)
        self.kv_int8 = kv_int8
        d = dims
        # every op rounds where it is written: no float32 kept across
        # an op that the model holds in its dtype
        jit = partial(jax.jit,
                      compiler_options={"xla_allow_excess_precision": False})
        self._layer_w = jit(lambda k, l: layer_weights(k, l, d, self.dtype))
        self._layer = jit(self._layer_fn)
        self._head = jit(self._head_fn)

    def _layer_fn(self, x, w, n_valid):
        d, a, r = self.dims, self.alpha_a, self.act
        T = x.shape[0]

        def norm(v, n):
            return r(_norm(v, w[n + "_scale"], w.get(n + "_bias"), d.norm,
                           d.eps))

        def proj(name, inp):
            return r(_sc_int(inp, w[name], w[name + "_alpha_w"], a[name]))
        h = norm(x, "norm1")
        q = proj("wq", h).reshape(T, d.hq, d.dh)
        k = proj("wk", h).reshape(T, d.hkv, d.dh)
        v = proj("wv", h).reshape(T, d.hkv, d.dh)
        q = r(_rope(q, d.rope_fraction, d.rope_theta))
        k = r(_rope(k, d.rope_fraction, d.rope_theta))
        if self.kv_int8:
            k, v = _int8_kv(k), _int8_kv(v)
        fn, operand = self.attention
        o = fn(q.reshape(T, d.hkv, d.hq // d.hkv, d.dh), k, v, n_valid,
               self.page, operand)
        x = r(x + proj("wo", r(o.reshape(T, d.hq * d.dh))))
        h2 = norm(x, "norm2")
        gate, up = proj("w_gate", h2), proj("w_up", h2)
        f = r(r(gate * self.sigmoid(gate)) * up)
        return r(x + proj("w_down", f))

    def _head_fn(self, x, g, targets):
        """Per position: best logit, the logits of ``targets`` (k, T),
        the argmax."""
        d = self.dims
        h = self.act(_norm(x, g["final_scale"], g.get("final_bias"),
                           d.norm, d.eps))
        logits = self.act(_sc_int(h, g["lm_head"], g["lm_head_alpha_w"],
                                  self.alpha_a["lm_head"]))[:, :d.vocab]
        tgt = logits[jnp.arange(logits.shape[0])[None, :], targets]
        return jnp.max(logits, axis=1), tgt, jnp.argmax(logits, axis=1)

    def readings(self, seqs: list[np.ndarray], targets: list[np.ndarray],
                 pad_to: int) -> list[dict]:
        """For each token sequence (padded to ``pad_to``), run the model
        and read, at every position, the best logit, the logits of the
        rows of ``targets`` (int (k, len) per sequence: tokens to read
        there) and the argmax."""
        with jax.default_matmul_precision("highest"):
            g = jax.jit(partial(global_weights, dims=self.dims,
                                dtype=self.dtype))(self.key)
            xs, nv = [], []
            for s in seqs:
                tok = np.zeros((pad_to,), np.int32)
                tok[:len(s)] = s
                xs.append(self.act(g["embed"][jnp.asarray(tok)]
                                   .astype(jnp.float32)))
                nv.append(len(s))
            for layer in range(self.dims.layers):
                w = self._layer_w(self.key, jnp.int32(layer))
                xs = [self._layer(x, w, jnp.int32(n))
                      for x, n in zip(xs, nv)]
            out = []
            for x, n, t in zip(xs, nv, targets):
                tt = np.zeros((t.shape[0], pad_to), np.int32)
                tt[:, :t.shape[1]] = t
                best, tgt, arg = jax.device_get(
                    self._head(x, g, jnp.asarray(tt)))
                out.append({"best": best[:n], "target": tgt[:, :n],
                            "argmax": arg[:n]})
        return out
