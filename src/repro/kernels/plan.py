"""Introspectable launch plans for the Pallas kernel fleet.

Every kernel in this package used to build its ``pl.pallas_call``
inline, which made the launch geometry — grid, BlockSpec index maps,
block shapes, scratch, scalar-prefetch operands — invisible to anything
but the Pallas tracer.  The static kernel auditor
(``repro.analysis.kernel_audit``) needs exactly that geometry *without*
tracing, so each kernel now factors its launch into a
:class:`LaunchPlan` built by a pure-Python ``*_plan(...)`` function of
the static shapes.  The same plan object drives the real launch
(:func:`call_plan`) and the audit passes, so the audited geometry can
never drift from the executed one.

A plan records, per operand, the full array shape, the block shape and
the index map (the exact Python callable handed to ``pl.BlockSpec``),
plus — for scalar-prefetch operands — a *worst-case value model*: the
inclusive bound on legal entries (``max_value``) and any extra
adversarial fill values (``values``, e.g. ragged lengths straddling a
page boundary).  The auditor enumerates index maps over the full grid
with scalars pinned to those extremes; because every index map in this
fleet is elementwise monotone in its scalar entries, the extremes are a
proof, not a sample (analysis/README.md "kernel audit").

``accumulate`` declares the write discipline of every output block that
is *revisited* (written from more than one grid step): the revisit pass
cross-checks the declaration against the actual output index maps and
against the kernel body (a revisited block whose kernel never guards a
first write with ``pl.when`` is silent last-write-wins).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["BlockOperand", "ScalarOperand", "LaunchPlan", "call_plan",
           "estimate_vmem", "compiler_params", "kernel_source_fn",
           "DEFAULT_VMEM_BUDGET"]

# ~16 MiB of VMEM per TPU core (v4/v5 class); the audit budget leaves
# headroom for Mosaic's own spills by defaulting to half of it
DEFAULT_VMEM_BUDGET = 8 * 2 ** 20


@dataclass(frozen=True)
class BlockOperand:
    """One blocked (non-scalar-prefetch) input or output operand."""
    name: str
    shape: tuple[int, ...]              # full operand shape
    dtype: Any                          # jnp dtype of the HBM buffer
    block: tuple[int, ...]              # BlockSpec block shape
    index_map: Callable                 # (grid..., *scalar_refs) -> blocks

    def block_bytes(self) -> int:
        return math.prod(self.block) * jnp.dtype(self.dtype).itemsize


@dataclass(frozen=True)
class ScalarOperand:
    """One scalar-prefetch operand plus its worst-case value model.

    ``max_value`` is the inclusive upper bound on legal entries (page
    tables: ``num_pages - 1``; lengths: ``max_len - 1``).  ``values``
    adds adversarial fills beyond the {0, max_value} extremes — e.g.
    lengths whose live prefix straddles a page boundary
    (``plen % page in {0, 1, page-1}``).  ``kernel_only`` marks operands
    read by the kernel body but never by an index map (per-slot lengths
    drive masking, not DMA), so the grid pass does not flag them unused.
    """
    name: str
    shape: tuple[int, ...]
    dtype: Any
    max_value: int
    values: tuple[int, ...] = ()
    kernel_only: bool = False


@dataclass(frozen=True)
class LaunchPlan:
    """Complete static geometry of one ``pl.pallas_call`` launch."""
    name: str
    grid: tuple[int, ...]
    scalars: tuple[ScalarOperand, ...]
    inputs: tuple[BlockOperand, ...]
    outputs: tuple[BlockOperand, ...]
    scratch: tuple[tuple[tuple[int, ...], Any], ...]
    kernel: Callable
    # output name -> declared write discipline for revisited blocks
    # ("online-softmax" | "when-init-accumulate" | "scratch-finalize")
    accumulate: dict[str, str] = field(default_factory=dict)
    dimension_semantics: tuple[str, ...] | None = None
    single_output: bool = True

    def scratch_bytes(self) -> int:
        return sum(math.prod(s) * jnp.dtype(d).itemsize
                   for s, d in self.scratch)


def estimate_vmem(plan: LaunchPlan) -> int:
    """Per-program VMEM estimate in bytes: every input/output block is
    double-buffered by the Pallas pipeline (x2), scratch is resident
    once.  Register-resident temporaries (e.g. the dequantized f32 copy
    of an int8 KV block) are deliberately excluded — the estimate bounds
    the DMA working set, which is what blows up first when a block knob
    (num_splits / block_q / block_r) is oversized."""
    blocks = sum(op.block_bytes() for op in plan.inputs + plan.outputs)
    return 2 * blocks + plan.scratch_bytes()


def compiler_params(semantics: tuple[str, ...]):
    """Mosaic compiler params carrying the grid's dimension_semantics."""
    return pltpu.CompilerParams(dimension_semantics=semantics)


def call_plan(plan: LaunchPlan, operands: tuple, *,
              interpret: bool = False):
    """Execute a plan: scalars first, then blocked inputs, exactly the
    ``pl.pallas_call`` the kernels used to build inline."""
    out_specs = [pl.BlockSpec(op.block, op.index_map)
                 for op in plan.outputs]
    out_shape = [jax.ShapeDtypeStruct(op.shape, op.dtype)
                 for op in plan.outputs]
    if plan.single_output:
        assert len(plan.outputs) == 1, plan.name
        out_specs, out_shape = out_specs[0], out_shape[0]
    in_specs = [pl.BlockSpec(op.block, op.index_map) for op in plan.inputs]
    kw = {}
    if plan.dimension_semantics is not None:
        kw["compiler_params"] = compiler_params(plan.dimension_semantics)
    if plan.scalars or plan.scratch:
        call = pl.pallas_call(
            plan.kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(plan.scalars),
                grid=plan.grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM(s, d) for s, d in plan.scratch],
            ),
            out_shape=out_shape,
            interpret=interpret,
            **kw)
    else:
        call = pl.pallas_call(
            plan.kernel,
            grid=plan.grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            **kw)
    return call(*operands)


def kernel_source_fn(plan: LaunchPlan) -> Callable:
    """The underlying kernel function of a plan (unwrapping partials),
    for source-level discipline checks."""
    fn = plan.kernel
    while isinstance(fn, functools.partial):
        fn = fn.func
    return fn
