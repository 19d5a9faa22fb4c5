"""Seeded random weights, made on the device, bit for bit reproducible.

Every weight is an integer drawn from the seed, times a power of two:
projections and the embedding take levels in [-127, 127], norm scales
``1 + k / 256``, norm biases ``k / 256``, per-channel weight scales
``c * (1 + k / 256)``.  Integer draws and exact products give the same
bits in any program, so the plain reference (bench/reference) regenerates
a layer alone and gets exactly the weights that the engine was given in
one stacked call.

Canonical names (``LAYER_NAMES`` / ``GLOBAL_NAMES``) belong to the
benchmark; :func:`program_params` lays them out as the engine's parameter
tree and checks it against the program's own abstract tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["Dims", "seed_key", "layer_weights", "global_weights",
           "program_params", "PROJECTIONS"]

# per layer: (name, kind) -- kind picks the draw
PROJECTIONS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_NAMES = ("norm1_scale", "norm1_bias", "norm2_scale", "norm2_bias")\
    + PROJECTIONS + tuple(p + "_alpha_w" for p in PROJECTIONS)
GLOBAL_NAMES = ("embed", "final_scale", "final_bias", "lm_head",
                "lm_head_alpha_w")
_IDS = {n: i + 1 for i, n in enumerate(LAYER_NAMES + GLOBAL_NAMES)}
_LEVELS = 127
# std of integer levels uniform on [-127, 127]
_LEVEL_STD = math.sqrt((_LEVELS * (_LEVELS + 1)) / 3.0)
# ternary threshold scale of a column: 1.12 x its std, as the program's
# own initialiser sets it (1.4 * 0.8 * std)
_ALPHA_W = 1.12


class Dims:
    """Sizes of a dense decoder, read from a configuration file's
    ``config`` section (Hugging Face key names)."""

    def __init__(self, c: dict, padded_vocab: int):
        self.layers = c["num_hidden_layers"]
        self.attn_layers = self.layers         # every layer attends
        self.d = c["hidden_size"]
        self.hq = c["num_attention_heads"]
        self.hkv = c["num_key_value_heads"]
        self.dh = c["head_dim"]
        self.ff = c["intermediate_size"]
        self.vocab = c["vocab_size"]
        self.padded_vocab = padded_vocab
        self.norm = "layernorm" if "layer_norm_eps" in c else "rmsnorm"
        self.eps = c.get("layer_norm_eps", c.get("rms_norm_eps"))
        self.rope_fraction = c.get("partial_rotary_factor", 1.0)
        self.rope_theta = float(c["rope_theta"])

    def shape(self, proj: str) -> tuple[int, int]:
        d, q, kv, ff = self.d, self.hq * self.dh, self.hkv * self.dh, self.ff
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d),
                "lm_head": (d, self.padded_vocab),
                "embed": (self.padded_vocab, d)}[proj]


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: its low and high 32 bits both count."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _pow2_scale(d_in: int) -> float:
    """Power of two that brings the levels' std nearest 1/sqrt(d_in)."""
    return 2.0 ** round(math.log2(1.0 / (math.sqrt(d_in) * _LEVEL_STD)))


def _levels(key, shape, lo=-_LEVELS, hi=_LEVELS):
    return jax.random.randint(key, shape, lo, hi + 1, jnp.int32)


def _matrix(key, shape, dtype):
    s = _pow2_scale(shape[0])
    return (_levels(key, shape).astype(jnp.float32) * s).astype(dtype)


def _alpha_w(key, d_in, n):
    """Per output channel: 1.12 x the column's std, varied by up to 1/8."""
    c = _pow2_scale(d_in) * _LEVEL_STD * _ALPHA_W
    return jnp.float32(c) * (1.0 + _levels(key, (n,), -32, 32)
                             .astype(jnp.float32) / 256.0)


def _norm(key, d, bias: bool):
    k = _levels(key, (d,), -32, 32).astype(jnp.float32) / 256.0
    return k if bias else 1.0 + k


def layer_weights(key: jax.Array, layer, dims: Dims, dtype) -> dict:
    """One layer's canonical weights; ``layer`` may be traced."""
    def k(name):
        return jax.random.fold_in(jax.random.fold_in(key, _IDS[name]),
                                  layer)
    w = {}
    for n in ("norm1", "norm2"):
        w[n + "_scale"] = _norm(k(n + "_scale"), dims.d, False)
        if dims.norm == "layernorm":
            w[n + "_bias"] = _norm(k(n + "_bias"), dims.d, True)
    for p in PROJECTIONS:
        shape = dims.shape(p)
        w[p] = _matrix(k(p), shape, dtype)
        w[p + "_alpha_w"] = _alpha_w(k(p + "_alpha_w"), *shape)
    return w


def global_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    def k(name):
        return jax.random.fold_in(key, _IDS[name])
    g = {"embed": _matrix(k("embed"), dims.shape("embed"), dtype),
         "lm_head": _matrix(k("lm_head"), dims.shape("lm_head"), dtype),
         "lm_head_alpha_w": _alpha_w(k("lm_head_alpha_w"),
                                     *dims.shape("lm_head")),
         "final_scale": _norm(k("final_scale"), dims.d, False)}
    if dims.norm == "layernorm":
        g["final_bias"] = _norm(k("final_bias"), dims.d, True)
    return g


def _dense(w, alpha_w, alpha_a):
    return {"w": w, "alpha_w": alpha_w,
            "alpha_a": jnp.broadcast_to(jnp.float32(alpha_a),
                                        alpha_w.shape[:-1])}


def _norm_tree(scale, bias):
    return {"scale": scale} if bias is None else {"scale": scale,
                                                  "bias": bias}


def program_params(seed: int, dims: Dims, alpha_a: dict, dtype,
                   abstract: dict | None = None) -> dict:
    """The engine's parameter tree, made on the device in one jitted call.

    ``alpha_a`` maps each projection (and ``lm_head``) to its activation
    scale.  ``abstract`` (``jax.eval_shape`` of the program's own
    initialiser) is checked leaf for leaf: same paths, shapes, dtypes.
    """
    dtype = jnp.dtype(dtype)

    def make(key):
        layers = jax.lax.map(
            lambda l: layer_weights(key, l, dims, dtype),
            jnp.arange(dims.layers, dtype=jnp.int32))
        g = global_weights(key, dims, dtype)

        def dense(p):
            return _dense(layers[p], layers[p + "_alpha_w"], alpha_a[p])
        block = {
            "norm1": _norm_tree(layers["norm1_scale"],
                                layers.get("norm1_bias")),
            "mixer": {p: dense(p) for p in ("wq", "wk", "wv", "wo")},
            "norm2": _norm_tree(layers["norm2_scale"],
                                layers.get("norm2_bias")),
            "ffn": {p: dense(p) for p in ("w_gate", "w_up", "w_down")},
            # the residual quantizer scales of the QAT path: the integer
            # datapath never reads them
            "alpha_r1": jnp.full((dims.layers,), 0.05, jnp.float32),
            "alpha_r2": jnp.full((dims.layers,), 0.05, jnp.float32),
        }
        return {"embed": {"table": g["embed"]},
                "periods": {"p0": block},
                "final_norm": _norm_tree(g["final_scale"],
                                         g.get("final_bias")),
                "lm_head": _dense(g["lm_head"], g["lm_head_alpha_w"],
                                  alpha_a["lm_head"])}

    if abstract is not None:
        want = jax.tree_util.tree_structure(abstract)
        got_abs = jax.eval_shape(make, seed_key(0))
        got = jax.tree_util.tree_structure(got_abs)
        if want != got:
            raise ValueError(f"parameter tree differs from the program's:"
                             f"\n{got}\n!=\n{want}")
        bad = [(jax.tree_util.keystr(p), a.shape, a.dtype, b.shape, b.dtype)
               for (p, a), b in zip(
                   jax.tree_util.tree_leaves_with_path(got_abs),
                   jax.tree_util.tree_leaves(abstract))
               if a.shape != b.shape or a.dtype != b.dtype]
        if bad:
            raise ValueError(f"parameter leaves differ: {bad}")
    return jax.jit(make)(seed_key(seed))
