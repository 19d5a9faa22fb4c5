"""Pallas TPU kernel: the SC integer datapath (ternary matmul + SI epilogue).

This is the compute hot-spot of the paper's accelerator, adapted to the
TPU's memory hierarchy (DESIGN.md §2): the ternary-multiplier bank + BSN +
SI of one output tile become

    int8 activations (bm, bk) x int8 ternary weights (bk, bn)
      -> MXU int32 accumulate in VMEM scratch        (== BSN popcount)
      -> threshold-count epilogue                    (== SI wiring)

Tiling: grid (M/bm, N/bn, K/bk) with the K axis innermost ("arbitrary"
semantics) so the accumulator tile lives in VMEM across the contraction.
Block shapes default to MXU-aligned (128k) multiples; int8 operands allow
2x the bf16 MXU throughput on v5e.

VMEM budget at defaults (bm=256, bn=256, bk=512):
    x 256*512 + w 512*256 (int8)            = 0.25 MiB
    acc 256*256 int32 + out 256*256 int32   = 0.50 MiB
    thresholds 256*out_bsl(<=32) int32      = 0.03 MiB
well under the 16 MiB/core VMEM of v5e, leaving room for double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .plan import compiler_params

__all__ = ["ternary_matmul_pallas"]


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    """Plain accumulate variant (no epilogue)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.int32),
                            w_ref[...].astype(jnp.int32),
                            preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _matmul_si_kernel(x_ref, w_ref, t_ref, o_ref, acc_ref, *, out_bsl: int):
    """Fused SI epilogue: out_q = #{j: sum >= t_j} - out_bsl/2.

    The threshold loop is static (out_bsl <= 32) — it unrolls into out_bsl
    vectorized compares on the (bm, bn) accumulator tile, i.e. the SI is
    free relative to the MXU work exactly as the wiring is free in silicon.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.int32),
                            w_ref[...].astype(jnp.int32),
                            preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        acc = acc_ref[...]
        counts = jnp.zeros_like(acc)
        for j in range(out_bsl):                       # static unroll
            tj = t_ref[:, j][None, :]                  # (1, bn)
            counts = counts + (acc >= tj).astype(jnp.int32)
        o_ref[...] = counts - out_bsl // 2


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret"))
def ternary_matmul_pallas(x_q: jax.Array, w_int: jax.Array,
                          thresholds_q: jax.Array | None = None,
                          *, block_m: int = 256, block_n: int = 256,
                          block_k: int = 512,
                          interpret: bool = False) -> jax.Array:
    """2-D core: x_q (M, K) int8 levels, w_int (K, N) int8 in {-1,0,1}.

    thresholds_q: optional (N, out_bsl) int32 SI table in the q domain.
    Shapes must already be padded to block multiples (ops.py handles
    ragged shapes and batching).
    """
    m, k = x_q.shape
    k2, n = w_int.shape
    assert k == k2, (x_q.shape, w_int.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    grid = (m // block_m, n // block_n, k // block_k)

    common = dict(
        grid=grid,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    x_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    w_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))

    if thresholds_q is None:
        return pl.pallas_call(
            _matmul_kernel, in_specs=[x_spec, w_spec], **common,
        )(x_q, w_int)

    out_bsl = thresholds_q.shape[-1]
    t_spec = pl.BlockSpec((block_n, out_bsl), lambda i, j, kk: (j, 0))
    kernel = functools.partial(_matmul_si_kernel, out_bsl=out_bsl)
    return pl.pallas_call(
        kernel, in_specs=[x_spec, w_spec, t_spec], **common,
    )(x_q, w_int, thresholds_q)
