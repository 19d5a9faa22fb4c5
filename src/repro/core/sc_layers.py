"""SC-quantized layers: the paper's datapath as composable JAX modules.

Two execution modes per layer (selected by ``config.quant`` at the model
level):

* ``sc_qat``  — differentiable fake-quant training path: LSQ ternary
  weights + thermometer activations, high-precision residual stream
  (paper §III-B).  This is what ``train_step`` lowers.
* ``sc_int``  — the integer inference datapath that is bit-equivalent to
  the silicon: int8 activations (q domain) x int8 ternary weights with an
  int32 accumulate (== BSN popcount) and an SI threshold epilogue.  This is
  what ``serve_step --quant sc_int`` lowers and what the Pallas
  ``ternary_matmul`` kernel implements.

The equivalence (qat-rounded values == alpha-scaled int path == bit-exact
bitstream path) is asserted in tests/test_sc_layers.py.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import si as si_mod
from .quant import (init_alpha, lsq_fake_quant, ternary_weight_init_alpha,
                    ternary_weight_quant, thermometer_act_quant)

__all__ = [
    "SCQuantConfig",
    "SC_OFF",
    "init_sc_linear",
    "sc_linear_qat",
    "export_sc_linear",
    "sc_linear_int",
    "sc_linear_int_approx",
    "sc_linear_int_from_qat",
    "sc_residual_quant",
]


@dataclass(frozen=True)
class SCQuantConfig:
    """Per-model SC quantization settings (paper notation W-A-R/BSL)."""
    mode: str = "none"              # none | sc_qat | sc_int
    weight_bsl: int = 2             # ternary weights
    act_bsl: int = 8                # datapath activation BSL
    resid_bsl: int = 16             # high-precision residual BSL
    per_channel: bool = True        # per-output-channel weight scales
    # sc_int only: accumulate through the paper's approximate BSN adder
    # (kernels/dispatch) instead of the exact int32 dot
    int_approx: bool = False

    @property
    def enabled(self) -> bool:
        return self.mode != "none"

    @property
    def act_half(self) -> int:
        return self.act_bsl // 2

    @property
    def resid_half(self) -> int:
        return self.resid_bsl // 2

    def with_mode(self, mode: str) -> "SCQuantConfig":
        return replace(self, mode=mode)


SC_OFF = SCQuantConfig(mode="none")

# HBM bound on one block of sc_linear_int_approx's partial-product
# counts: one granite-3-2b decode lane's lm_head alone is 405 MB of them
_COUNTS_BYTES = 256 * 2 ** 20


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def init_sc_linear(key: jax.Array, in_dim: int, out_dim: int,
                   cfg: SCQuantConfig,
                   w_init_scale: float | None = None,
                   dtype=jnp.float32) -> dict:
    """Linear params + LSQ scales. ``w`` stored (in_dim, out_dim)."""
    scale = w_init_scale if w_init_scale is not None else 1.0 / np.sqrt(in_dim)
    w = jax.random.normal(key, (in_dim, out_dim), dtype) * scale
    params = {"w": w}
    if cfg.enabled:
        if cfg.per_channel:
            aw = jnp.maximum(1.4 * jnp.mean(jnp.abs(w), axis=0), 1e-8)
        else:
            aw = ternary_weight_init_alpha(w)
        params["alpha_w"] = aw.astype(jnp.float32)
        # activation scale initialized for unit-variance inputs
        params["alpha_a"] = jnp.asarray(
            2.0 / np.sqrt(max(cfg.act_half, 1)), jnp.float32)
    return params


# ---------------------------------------------------------------------------
# QAT path
# ---------------------------------------------------------------------------

def sc_linear_qat(params: dict, x: jax.Array, cfg: SCQuantConfig) -> jax.Array:
    """Fake-quant linear: quantize activations + weights, matmul in the
    compute dtype. With mode == none this is a plain matmul."""
    w = params["w"]
    if not cfg.enabled:
        return x @ w
    x_fq = thermometer_act_quant(x, params["alpha_a"], cfg.act_bsl)
    w_fq = ternary_weight_quant(w, params["alpha_w"])
    return x_fq.astype(x.dtype) @ w_fq.astype(x.dtype)


def sc_residual_quant(r: jax.Array, alpha_r: jax.Array,
                      cfg: SCQuantConfig) -> jax.Array:
    """High-precision residual fake-quant (16-bit BSL by default, §III)."""
    if not cfg.enabled:
        return r
    return lsq_fake_quant(r, alpha_r, -cfg.resid_half, cfg.resid_half)


# ---------------------------------------------------------------------------
# integer (silicon-equivalent) path
# ---------------------------------------------------------------------------

def export_sc_linear(params: dict, cfg: SCQuantConfig,
                     act_fn: Callable[[np.ndarray], np.ndarray] | None = None,
                     out_bsl: int | None = None,
                     alpha_out: float | None = None) -> dict:
    """Quantize trained params into the deployable integer form.

    Returns ``{"w_int": int8 (in,out), "alpha_w", "alpha_a",
    "thresholds": int32 (out_bsl,) or None, "alpha_out"}``.

    The SI thresholds realize ``act_fn`` on the *accumulated* integer sum:
    sum value = alpha_a*alpha_w * sum_q, so the threshold table is designed
    over the sum's level range with effective input scale alpha_a*alpha_w.
    Per-channel weight scales get per-channel threshold tables (stacked).
    """
    w = np.asarray(params["w"], np.float32)
    aw = np.asarray(params["alpha_w"], np.float32)
    aa = float(params["alpha_a"])
    w_int = np.clip(np.round(w / aw), -1, 1).astype(np.int8)
    out = {"w_int": w_int, "alpha_w": aw, "alpha_a": aa, "thresholds": None,
           "alpha_out": None}
    if act_fn is not None:
        if out_bsl is None or alpha_out is None:
            raise ValueError("SI epilogue needs out_bsl and alpha_out")
        in_dim = w.shape[0]
        half = cfg.act_half
        sum_max = in_dim * half          # |sum_q| <= in_dim * L/2
        aw_vec = np.atleast_1d(aw)
        tables = [si_mod.si_thresholds(act_fn, 2 * sum_max, out_bsl,
                                       alpha_in=float(a) * aa,
                                       alpha_out=alpha_out)
                  for a in aw_vec]
        out["thresholds"] = np.stack(tables)      # (C or 1, out_bsl)
        out["alpha_out"] = alpha_out
        out["sum_max"] = sum_max
    return out


def _si_epilogue(int_params: dict, sum_q: jax.Array) -> jax.Array:
    """Optional SI threshold activation on accumulated q-domain sums."""
    thresholds = int_params.get("thresholds")
    if thresholds is None:
        return sum_q
    t = jnp.asarray(thresholds)                    # (C or 1, out_bsl)
    sum_max = int(int_params["sum_max"])
    counts = sum_q + sum_max                       # count domain
    # counts (..., C) -> (..., C, 1) vs t (C, out_bsl): broadcast compare
    out_counts = jnp.sum(counts[..., None] >= t, axis=-1, dtype=jnp.int32)
    out_bsl = t.shape[-1]
    return out_counts - out_bsl // 2               # back to q domain


def sc_linear_int(int_params: dict, x_q: jax.Array,
                  matmul_fn: Callable | None = None) -> jax.Array:
    """Integer datapath: x_q int8 levels @ ternary int8 weights -> int32 sum
    (== the exact BSN's popcount, proven in tests), then optional SI
    epilogue.

    ``matmul_fn(x_q, w_int)`` may be supplied to route through the Pallas
    kernel; default is the jnp reference (int32 accumulate).  For the
    paper's proposed approximate adder use :func:`sc_linear_int_approx`.
    """
    w_int = jnp.asarray(int_params["w_int"])
    if matmul_fn is None:
        sum_q = jax.lax.dot_general(
            x_q.astype(jnp.int32), w_int.astype(jnp.int32),
            (((x_q.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:
        sum_q = matmul_fn(x_q, w_int)
    return _si_epilogue(int_params, sum_q)


def sc_linear_int_approx(int_params: dict, x_q: jax.Array,
                         act_bsl: int,
                         spec: "ApproxBSNSpec | None" = None,
                         *, cycles: int = 1,
                         backend: str | None = None) -> jax.Array:
    """Integer datapath with the *approximate* progressive-sorting adder.

    Replaces the exact accumulation of :func:`sc_linear_int` with the
    paper's Fig 10b/12 BSN, executed by the fused Pallas kernel through
    the dispatch layer (kernels/dispatch.py) — this is the silicon the
    efficiency results are about.  Per output channel the ``K`` partial
    products (levels in ``[-act_bsl/2, act_bsl/2]``, i.e. thermometer
    codes of BSL ``act_bsl``) enter the adder in the count domain; the
    compressed output code is re-scaled by ``spec.scale`` (a power of
    two, the §III-C residual re-scaler) back to the q domain, then the
    usual SI epilogue applies.

    ``spec`` defaults to :func:`default_approx_spec` of the accumulation
    width; with ``cycles > 1`` the temporal-reuse kernel folds
    ``cycles * spec.width == K`` inputs onto the small spatial pipeline.
    Exactness: with a degenerate spec (no clip, stride 1) the result
    equals :func:`sc_linear_int` bit-for-bit (asserted in tests).
    """
    from repro.core.bsn import approx_bsn, default_approx_spec
    w_int = jnp.asarray(int_params["w_int"])       # (K, N)
    k, _ = w_int.shape
    if spec is None:
        spec = default_approx_spec(k // cycles, act_bsl)
    if cycles * spec.width != k:
        raise ValueError(f"cycles*width={cycles * spec.width} != K={k}")
    if spec.in_bsl != act_bsl:
        raise ValueError(f"spec.in_bsl={spec.in_bsl} != act_bsl={act_bsl}")
    half = act_bsl // 2
    n = w_int.shape[1]

    def accumulate(xq):
        # partial products, one thermometer code per (input, channel)
        prod_q = xq[..., :, None].astype(jnp.int32) \
            * w_int.astype(jnp.int32)
        counts = jnp.swapaxes(prod_q, -1, -2) + half  # (..., N, K), [0, bsl]
        return approx_bsn(counts, spec, cycles=cycles, backend=backend)

    # the (tokens, N, K) int32 counts are materialized for the BSN
    # kernel; past _COUNTS_BYTES they are built one token block at a
    # time (the adder is per row, so blocking cannot change a result)
    lead = x_q.shape[:-1]
    rows = math.prod(lead)
    per_block = max(1, _COUNTS_BYTES // (4 * n * k))
    if rows <= per_block:
        out = accumulate(x_q)
    else:
        blocks = -(-rows // per_block)
        xf = jnp.pad(x_q.reshape(rows, k),
                     ((0, blocks * per_block - rows), (0, 0)))
        out = jax.lax.map(accumulate, xf.reshape(blocks, per_block, k))
        out = out.reshape(blocks * per_block, n)[:rows].reshape(*lead, n)
    sum_q = spec.scale * (out - cycles * spec.out_bsl // 2)
    return _si_epilogue(int_params, sum_q)


def sc_linear_int_from_qat(params: dict, x: jax.Array,
                           cfg: SCQuantConfig, *,
                           backend: str | None = None) -> jax.Array:
    """Run a QAT-trained linear on the integer SC datapath, on the fly.

    This is what lets the *whole model zoo* serve on the silicon path
    without an export step: ``params`` are the live QAT params
    (``w/alpha_w/alpha_a``); activations and weights are quantized to
    their integer codes exactly as the fake-quant forward would round
    them, the accumulation runs int8 x ternary -> int32 (== the exact
    BSN popcount), and the result is rescaled back to the float residual
    stream.  With ``cfg.int_approx`` the accumulation instead goes
    through the paper's approximate progressive-sorting BSN
    (:func:`sc_linear_int_approx`), which dispatches to the fused Pallas
    kernel via kernels/dispatch — an ambient ``backend_scope`` (e.g. the
    one ServeEngine installs) picks pallas / interpret / reference.

    Numerics: with the exact accumulator the only difference from
    ``sc_linear_qat`` is summation order (int32 exact vs float dot), so
    q-domain values agree bit-for-bit and the float output to ~1 ulp.
    """
    half = cfg.act_half
    # mirror lsq_fake_quant's dtype discipline: the rounding boundary is
    # computed against alpha cast to the activation dtype
    aa = params["alpha_a"].astype(x.dtype)
    aw = params["alpha_w"].astype(jnp.float32)
    x_q = jnp.clip(jnp.round(x / aa), -half, half).astype(jnp.int8)
    w = params["w"].astype(jnp.float32)
    w_int = jnp.clip(jnp.round(w / aw), -1, 1).astype(jnp.int8)
    int_params = {"w_int": w_int}
    if cfg.int_approx:
        sum_q = sc_linear_int_approx(int_params, x_q, cfg.act_bsl,
                                     backend=backend)
    else:
        sum_q = sc_linear_int(int_params, x_q)
    y = sum_q.astype(jnp.float32) * (aa.astype(jnp.float32)
                                     * jnp.atleast_1d(aw))
    return y.astype(x.dtype)
