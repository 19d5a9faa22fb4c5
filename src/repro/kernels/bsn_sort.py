"""Pallas TPU kernel: bitonic sorting network over bit vectors.

The circuit-fidelity path of the BSN (DESIGN.md §2): each compare-exchange
level of Batcher's network becomes one VPU min/max over a VMEM-resident
tile — the sort never leaves VMEM.  The compare-exchange at distance j is
expressed as a reshape to (rows, L/2j, 2, j) + elementwise min/max (TPU has
no efficient gather; the reshape form keeps everything lane-aligned).

Grid: rows are tiled by ``block_r``; the full (power-of-two) sort length L
stays resident.  VMEM at defaults: block_r=256 rows x L=4096 lanes x int8
= 1 MiB + the same for the output — comfortable, and the log^2(L) levels
(78 for L=4096) all reuse the same tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from .plan import compiler_params

__all__ = ["bsn_sort_pallas"]


def _sort_kernel(x_ref, o_ref, *, length: int, descending: bool):
    x = x_ref[...]                                   # (block_r, L)
    rows = x.shape[0]
    n_bits = length.bit_length() - 1
    for k_bit in range(1, n_bits + 1):               # merge size k = 2^k_bit
        k = 1 << k_bit
        for j_bit in range(k_bit - 1, -1, -1):       # distance j = 2^j_bit
            j = 1 << j_bit
            blocks = length // (2 * j)
            xr = x.reshape(rows, blocks, 2, j)
            a = xr[:, :, 0, :]
            b = xr[:, :, 1, :]
            # direction per 2j-block: bit k of the block start position
            starts = jnp.arange(blocks, dtype=jnp.int32) * (2 * j)
            up = (starts & k) == 0                   # (blocks,)
            keep_hi = up if descending else ~up
            keep_hi = keep_hi[None, :, None]
            hi = jnp.maximum(a, b)
            lo = jnp.minimum(a, b)
            first = jnp.where(keep_hi, hi, lo)
            second = jnp.where(keep_hi, lo, hi)
            x = jnp.stack([first, second], axis=2).reshape(rows, length)
    o_ref[...] = x


@functools.partial(jax.jit, static_argnames=("descending", "block_r",
                                              "interpret"))
def bsn_sort_pallas(x: jax.Array, *, descending: bool = True,
                    block_r: int = 256, interpret: bool = False) -> jax.Array:
    """Sort each row of ``x`` (R, L). L must be a power of two; R a multiple
    of block_r (ops.py pads both)."""
    r, length = x.shape
    assert length & (length - 1) == 0, f"L={length} must be a power of 2"
    assert r % block_r == 0, (r, block_r)
    kernel = functools.partial(_sort_kernel, length=length,
                               descending=descending)
    return pl.pallas_call(
        kernel,
        grid=(r // block_r,),
        in_specs=[pl.BlockSpec((block_r, length), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_r, length), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, length), x.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(x)
