"""Kernel dispatch: pick pallas / pallas-interpret / reference per call.

Single policy point for how the approximate-BSN adder AND the paged
attention execute:

* ``"pallas"``            — compiled Mosaic kernel (real TPU).
* ``"pallas-interpret"``  — same kernel through the Pallas interpreter;
  bit-for-bit the compiled semantics, runs anywhere.  This is what the
  differential tests and this CPU container use.
* ``"reference"``         — the pure-JAX oracle (core/bsn.py counts for
  the BSN; the XLA gather/scatter paged attention in kernels/ref.py —
  also the right answer for tiny shapes where a pallas_call is all
  overhead).

Resolution order for every call: explicit ``backend=`` argument, then an
active scope / process default (:func:`backend_scope` for the BSN,
:func:`attn_backend_scope` for paged attention — separate knobs because
an engine may want the BSN circuit pinned while attention autotunes),
then auto (TPU + kernel-worthy row count -> ``pallas``; kernel-worthy
row count elsewhere -> ``pallas-interpret``; otherwise ``reference``).
The decision happens at Python trace time, so a scope must wrap the
*first* (tracing) call of a jitted function — ServeEngine does exactly
that.

``core.bsn.approx_bsn`` forwards here lazily, so library users reach the
kernel without importing repro.kernels themselves.
"""

from __future__ import annotations

import collections
import contextlib
import math
from dataclasses import dataclass
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

from repro.core.bsn import (ApproxBSNSpec, approx_bsn_counts,
                            default_approx_spec, spatial_temporal_counts)

from . import ref
from .approx_bsn import (approx_bsn_pallas, approx_bsn_plan,
                         approx_bsn_temporal_pallas,
                         approx_bsn_temporal_plan)
from .paged_attention import (paged_attn_decode_pallas,
                              paged_attn_decode_plan,
                              paged_attn_prefill_pallas,
                              paged_attn_prefill_plan)
from .plan import DEFAULT_VMEM_BUDGET, estimate_vmem

__all__ = ["BACKENDS", "select_backend", "set_default_backend",
           "get_default_backend", "backend_scope", "approx_bsn",
           "spec_stages", "attn_backend_scope", "set_attn_backend",
           "get_attn_backend", "paged_attn_decode", "paged_attn_prefill",
           "resolved_backends", "clear_resolved", "bsn_block_rows",
           "KernelEntry", "KERNEL_REGISTRY"]

BACKENDS = ("pallas", "pallas-interpret", "reference")

_default_backend: str | None = None

# trace-time log of every resolution, (kernel, backend) -> calls: the
# chip smoke reads it to prove the compiled kernels served
_resolved: collections.Counter = collections.Counter()


def resolved_backends() -> dict[tuple[str, str], int]:
    """(kernel, backend) -> number of traced calls resolved that way
    since the last :func:`clear_resolved`."""
    return dict(_resolved)


def clear_resolved() -> None:
    _resolved.clear()


def set_default_backend(backend: str | None) -> None:
    """Process-wide override; ``None`` restores auto selection."""
    global _default_backend
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}, want one of "
                         f"{BACKENDS} or None")
    _default_backend = backend


def get_default_backend() -> str | None:
    return _default_backend


@contextlib.contextmanager
def backend_scope(backend: str | None) -> Iterator[None]:
    """Temporarily pin the dispatch backend (``None`` scopes are no-ops
    rather than resets, so nested engines compose)."""
    if backend is None:
        yield
        return
    prev = _default_backend
    set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(prev)


def select_backend(rows: int, *, backend: str | None = None,
                   min_rows_for_kernel: int = 8,
                   default: str | None = None) -> str:
    """Resolve the backend for a call over ``rows`` independent codes.

    The row threshold applies on EVERY auto-selected backend: below it a
    pallas_call is all overhead (and ``rows == 0`` is a degenerate grid),
    so tiny shapes take the reference even on TPU.  ``default`` lets a
    subsystem supply its own scope value (attention passes the attn
    scope; the BSN path passes nothing and uses the module default).
    """
    if backend is None:
        backend = _default_backend if default is None else default
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        return backend
    if rows < min_rows_for_kernel:
        return "reference"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "pallas-interpret"


def spec_stages(spec: ApproxBSNSpec) -> tuple[tuple[int, int, int], ...]:
    """ApproxBSNSpec -> the primitive static tuples the kernel takes."""
    return tuple((s.group, s.sub.clip, s.sub.stride) for s in spec.stages)


def bsn_block_rows(rows: int, spec: ApproxBSNSpec, block_r: int,
                   cycles: int = 1) -> int:
    """The BSN kernel's row tile for ``rows`` codes of ``spec.width``:
    ``block_r`` clamped to the (pow2-padded) row count, then halved
    until the plan's own VMEM estimate — the double-buffered
    ``(block_r, width)`` int32 tile — fits ``DEFAULT_VMEM_BUDGET``.  At
    granite's down projection (K=8192) the default 256 rows would need
    16 MiB of tiles, past a v5e core's scoped VMEM."""
    block_r = min(block_r, max(8, 1 << (rows - 1).bit_length()))
    stages = spec_stages(spec)

    def est(br):
        if cycles == 1:
            plan = approx_bsn_plan(rows=br, width=spec.width,
                                   in_bsl=spec.in_bsl, stages=stages,
                                   block_r=br)
        else:
            plan = approx_bsn_temporal_plan(
                rows=br, width=spec.width, in_bsl=spec.in_bsl,
                stages=stages, cycles=cycles, block_r=br)
        return estimate_vmem(plan)

    while block_r > 8 and est(block_r) > DEFAULT_VMEM_BUDGET:
        block_r //= 2
    return block_r


def approx_bsn(counts: jax.Array, spec: ApproxBSNSpec, *, cycles: int = 1,
               backend: str | None = None, block_r: int = 256,
               min_rows_for_kernel: int = 8) -> jax.Array:
    """Approximate-BSN accumulation of ``(..., cycles*width)`` popcounts.

    Returns the output-code popcounts ``(...,)``; represented value is
    ``spec.scale * (out - cycles * spec.out_bsl // 2)``.  Any leading
    batch shape; rows are flattened, padded to ``block_r`` and cropped.
    """
    total = cycles * spec.width
    if counts.shape[-1] != total:
        raise ValueError(f"expected trailing dim {total} "
                         f"(cycles={cycles} x width={spec.width}), "
                         f"got {counts.shape}")
    batch = counts.shape[:-1]
    # static-shape host math: math.prod, not np.prod — this function is
    # reachable from traced code and the host-op lint keeps np out of it
    rows = math.prod(batch) if batch else 1
    chosen = select_backend(rows, backend=backend,
                            min_rows_for_kernel=min_rows_for_kernel)
    if rows == 0:
        # zero-size leading batch dim: a pallas_call over 0 rows is a
        # degenerate grid — the reference returns the empty result with
        # the right trailing shape/dtype regardless of requested backend
        chosen = "reference"
    _resolved[("approx_bsn", chosen)] += 1

    if chosen == "reference":
        if cycles == 1:
            return approx_bsn_counts(counts, spec)
        return spatial_temporal_counts(counts, spec, cycles)

    interpret = chosen == "pallas-interpret"
    block_r = bsn_block_rows(rows, spec, block_r, cycles)
    rp = (rows + block_r - 1) // block_r * block_r
    x2 = counts.reshape(rows, total).astype(jnp.int32)
    x2 = jnp.pad(x2, ((0, rp - rows), (0, 0)))
    kw = dict(in_bsl=spec.in_bsl, stages=spec_stages(spec),
              block_r=block_r, interpret=interpret)
    if cycles == 1:
        out = approx_bsn_pallas(x2, **kw)
    else:
        out = approx_bsn_temporal_pallas(x2, cycles=cycles, **kw)
    out = out[:rows]
    return out.reshape(batch) if batch else out[0]


# ---------------------------------------------------------------------------
# paged attention (serving decode / prefill hot path)
# ---------------------------------------------------------------------------

_attn_backend: str | None = None


def set_attn_backend(backend: str | None) -> None:
    """Process-wide paged-attention override; ``None`` restores auto."""
    global _attn_backend
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}, want one of "
                         f"{BACKENDS} or None")
    _attn_backend = backend


def get_attn_backend() -> str | None:
    return _attn_backend


@contextlib.contextmanager
def attn_backend_scope(backend: str | None) -> Iterator[None]:
    """Pin the paged-attention backend for traced calls (``None`` scopes
    are no-ops rather than resets, so nested engines compose).  Like
    :func:`backend_scope` this must wrap the first (tracing) call —
    ``ServeEngine(attn_backend=...)`` does."""
    if backend is None:
        yield
        return
    prev = _attn_backend
    set_attn_backend(backend)
    try:
        yield
    finally:
        set_attn_backend(prev)


def paged_attn_decode(q: jax.Array, k_pages: jax.Array,
                      v_pages: jax.Array, page_tables: jax.Array,
                      lengths: jax.Array, *, backend: str | None = None,
                      num_splits: int = 1,
                      min_rows_for_kernel: int = 8,
                      kv_format: str = "fp",
                      kv_aux: dict | None = None) -> jax.Array:
    """Batched one-token paged decode: (S, Hkv, G, D) queries against the
    (N, Hkv, page, D) pools through (S, maxp) tables, masked by
    ``lengths``.  Flash-decoding Pallas kernel on the kernel backends,
    XLA gather oracle (kernels/ref.py) on ``"reference"``.  Compressed
    pools (``kv_format`` "int8"/"sc") pass the parallel scale/residual
    pools in ``kv_aux`` (keys ``k_scale``/``v_scale``[/``k_resid``/
    ``v_resid``]); both backends fuse the dequant into the page reads."""
    S, Hkv, G, _ = q.shape
    aux = kv_aux or {}
    chosen = select_backend(S * Hkv * G, backend=backend,
                            min_rows_for_kernel=min_rows_for_kernel,
                            default=_attn_backend)
    _resolved[("paged_attn_decode", chosen)] += 1
    if chosen == "reference":
        return ref.paged_attn_decode_ref(q, k_pages, v_pages,
                                         page_tables, lengths,
                                         kv_format=kv_format, kv_aux=aux)
    return paged_attn_decode_pallas(q, k_pages, v_pages, page_tables,
                                    lengths, num_splits=num_splits,
                                    interpret=chosen == "pallas-interpret",
                                    kv_format=kv_format, **aux)


def paged_attn_prefill(q: jax.Array, k_pages: jax.Array,
                       v_pages: jax.Array, page_tables: jax.Array,
                       start, *, backend: str | None = None,
                       block_q: int = 32,
                       min_rows_for_kernel: int = 8,
                       kv_format: str = "fp",
                       kv_aux: dict | None = None) -> jax.Array:
    """One chunk of paged prefill: (G, C, Hkv, Gq, D) queries at
    positions ``[start, start+C)`` (``start`` may be traced) against
    every page written so far, causal.  Same backend chain (and
    ``kv_format``/``kv_aux`` contract) as :func:`paged_attn_decode`."""
    G, C, Hkv, Gq, _ = q.shape
    aux = kv_aux or {}
    chosen = select_backend(G * C * Hkv * Gq, backend=backend,
                            min_rows_for_kernel=min_rows_for_kernel,
                            default=_attn_backend)
    _resolved[("paged_attn_prefill", chosen)] += 1
    if chosen == "reference":
        return ref.paged_attn_prefill_ref(q, k_pages, v_pages,
                                          page_tables, start,
                                          kv_format=kv_format, kv_aux=aux)
    return paged_attn_prefill_pallas(q, k_pages, v_pages, page_tables,
                                     start=start, block_q=block_q,
                                     interpret=chosen == "pallas-interpret",
                                     kv_format=kv_format, **aux)


# ---------------------------------------------------------------------------
# kernel registry: static-audit metadata for every dispatched kernel
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelEntry:
    """One dispatched Pallas kernel, as the static auditor sees it.

    ``build_plan`` is the kernel's pure-Python launch-plan builder (the
    same one the executing wrapper calls — audited geometry cannot drift
    from executed geometry).  ``kv_formats`` lists the compressed-pool
    variants the kernel compiles per format (empty when kv_format does
    not apply, e.g. the BSN adder).  ``audit_cases()`` returns
    ``(label, plan_kwargs)`` pairs covering the autotune sweep shapes,
    so the auditor can prune/verify exactly the configs the autotuner
    would compile.  New kernels MUST register here before dispatch:
    ``tests/test_kernel_audit.py`` audits every entry x format.
    """
    name: str
    build_plan: Callable
    kv_formats: tuple[str, ...]
    audit_cases: Callable[[], tuple[tuple[str, dict], ...]]


def _bsn_case(rows: int, width: int, block_r: int,
              cycles: int = 1) -> tuple[str, dict]:
    """Mirror dispatch.approx_bsn's fit-then-pad of (rows, block_r)."""
    spec = default_approx_spec(width, 2)
    br = bsn_block_rows(rows, spec, block_r, cycles)
    rp = (rows + br - 1) // br * br
    kw = dict(rows=rp, width=width, in_bsl=spec.in_bsl,
              stages=spec_stages(spec), block_r=br)
    if cycles > 1:
        kw["cycles"] = cycles
    return f"r{rows}_w{width}_b{br}" + (f"_t{cycles}" if cycles > 1
                                        else ""), kw


def _bsn_spatial_cases() -> tuple[tuple[str, dict], ...]:
    # the bench_approx_bsn autotune sweep: (rows, width) x block_r
    cases = {}
    for rows, width in ((64, 128), (64, 512), (256, 1152)):
        for block_r in (64, 128, 256):
            label, kw = _bsn_case(rows, width, block_r)
            cases[label] = kw                        # dedupe clamped ties
    return tuple(cases.items())


def _bsn_temporal_cases() -> tuple[tuple[str, dict], ...]:
    cases = {}
    for rows, width, cycles in ((64, 128, 4), (256, 128, 8)):
        for block_r in (64, 256):
            label, kw = _bsn_case(rows, width, block_r, cycles)
            cases[label] = kw
    return tuple(cases.items())


def _decode_cases() -> tuple[tuple[str, dict], ...]:
    # the bench_serving autotune shapes: the serving-scale decode point
    # and the longer-context split-K point (pools sized like
    # autotune._paged_case: S * maxp pages + the reserved trash page)
    cases = []
    for tag, maxp, splits in (("serving", 4, (1, 2, 4)),
                              ("long", 16, (1, 2, 4, 8))):
        for s in splits:
            if s > maxp:
                continue
            cases.append((f"{tag}_maxp{maxp}_splits{s}",
                          dict(S=8, Hkv=2, G=2, D=16, page=16, maxp=maxp,
                               num_pages=8 * maxp + 1, num_splits=s)))
    return tuple(cases)


def _prefill_cases() -> tuple[tuple[str, dict], ...]:
    # the chunk start is a runtime scalar; the plan's value model sweeps
    # it from 0 to the last chunk the table holds
    return tuple(
        (f"chunk32_table4_bq{bq}",
         dict(G=4, C=32, Hkv=2, Gq=2, D=16, page=16,
              num_pages=4 * 4 + 1, table_width=4, block_q=bq))
        for bq in (8, 16, 32))


KERNEL_REGISTRY: dict[str, KernelEntry] = {
    e.name: e for e in (
        KernelEntry("approx_bsn_spatial", approx_bsn_plan, (),
                    _bsn_spatial_cases),
        KernelEntry("approx_bsn_temporal", approx_bsn_temporal_plan, (),
                    _bsn_temporal_cases),
        KernelEntry("paged_attn_decode", paged_attn_decode_plan,
                    ("fp", "int8", "sc"), _decode_cases),
        KernelEntry("paged_attn_prefill", paged_attn_prefill_plan,
                    ("fp", "int8", "sc"), _prefill_cases),
    )
}
