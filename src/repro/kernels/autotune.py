"""Block-size autotuning for the Pallas kernels.

Tiny deterministic sweeps over the kernels' static tiling knobs —
split-K width (``num_splits``) for paged decode, q-block rows for paged
prefill, row-block size for the approximate BSN — timing each candidate
on synthetic data of the caller's shape and reporting the winner.  The
bench scripts (benchmarks/bench_serving.py, bench_approx_bsn.py) run
these per serving shape and record the winners into the root-level
BENCH JSONs, so successive PRs can compare tile choices, not just
end-to-end numbers.

Timing here is wall-clock over jitted calls with ``block_until_ready``
— on this CPU container that measures the interpret path (dispatch
overhead + interpreter), which is the comparable-correctness trajectory
the bench JSONs track; on a real TPU the same sweep times Mosaic.

Candidates are vetted *before* they are compiled: each sweep builds the
kernel's :class:`~repro.kernels.plan.LaunchPlan` for the candidate knobs
and skips any whose static VMEM estimate exceeds the audit budget — the
same estimate the ``vmem`` pass of ``repro.analysis.kernel_audit``
gates on, so the tuner can never crown a config the auditor would
reject.  Winners carry their ``vmem_est`` in the BENCH JSONs.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kv_quant import check_kv_format, kv_quant

from .approx_bsn import approx_bsn_pallas, approx_bsn_plan
from .paged_attention import (paged_attn_decode_pallas,
                              paged_attn_decode_plan,
                              paged_attn_prefill_pallas,
                              paged_attn_prefill_plan)
from .plan import DEFAULT_VMEM_BUDGET, estimate_vmem

__all__ = ["time_callable", "sweep", "autotune_paged_decode",
           "autotune_paged_prefill", "autotune_approx_bsn"]


def time_callable(fn, iters: int = 10, warmup: int = 2) -> float:
    """Median wall-clock microseconds per call (fn is nullary, jitted)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def sweep(build, candidates: dict, *, iters: int = 10, plan_for=None,
          vmem_budget: int = DEFAULT_VMEM_BUDGET) -> dict:
    """Time ``build(**kwargs)`` for each candidate; pick the fastest.

    candidates: {label: kwargs}.  Returns {"winner": label,
    "us_per_call": {label: us}} — the stable schema the BENCH JSONs
    carry per shape.

    ``plan_for(**kwargs)`` (optional) returns the candidate's
    :class:`~repro.kernels.plan.LaunchPlan`; candidates whose
    ``estimate_vmem`` exceeds ``vmem_budget`` are *pruned* — never
    compiled or timed — and land in the report's ``"pruned"`` map
    instead.  Surviving candidates carry ``"vmem_est"``.  If every
    candidate is over budget the cheapest one runs anyway (flagged as
    ``"all_over_budget"``) so the sweep still returns a winner.
    """
    vmem_est, pruned, all_over = {}, {}, False
    if plan_for is not None:
        for label, kw in candidates.items():
            vmem_est[label] = estimate_vmem(plan_for(**kw))
        pruned = {l: b for l, b in vmem_est.items() if b > vmem_budget}
        if candidates and len(pruned) == len(candidates):
            all_over = True
            del pruned[min(pruned, key=pruned.get)]
    table = {}
    for label, kw in candidates.items():
        if label in pruned:
            continue
        table[label] = round(time_callable(build(**kw), iters=iters), 2)
    winner = min(table, key=table.get)
    out = {"winner": winner, "us_per_call": table}
    if plan_for is not None:
        out["vmem_est"] = {l: vmem_est[l] for l in table}
        if pruned:
            out["pruned"] = pruned
        if all_over:
            out["all_over_budget"] = True
    return out


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _paged_case(seed, S, Hkv, D, page, maxp, kv_format="fp"):
    """Synthetic pools + tables for one paged shape.  For compressed
    formats the float pools are quantized positionwise, yielding the
    code pages and the aux (scale / residual) operand dict the kernels
    take — so the sweep times the fused-dequant kernel, not a float
    stand-in."""
    check_kv_format(kv_format)
    rng = np.random.default_rng(seed)
    n = S * maxp + 1
    kf = jnp.asarray(rng.standard_normal((n, Hkv, page, D)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((n, Hkv, page, D)), jnp.float32)
    aux = {}
    if kv_format == "fp":
        kp, vp = kf, vf
    else:
        kq, vq = kv_quant(kf, kv_format), kv_quant(vf, kv_format)
        kp, vp = kq["q"], vq["q"]
        aux = {"k_scale": kq["scale"], "v_scale": vq["scale"]}
        if kv_format == "sc":
            aux |= {"k_resid": kq["resid"], "v_resid": vq["resid"]}
    tables = np.zeros((S, maxp), np.int32)
    for s in range(S):
        tables[s] = 1 + s * maxp + rng.permutation(maxp)
    return rng, kp, vp, jnp.asarray(tables), aux


def autotune_paged_decode(S: int, Hkv: int, G: int, D: int, page: int,
                          maxp: int, *, splits=(1, 2, 4),
                          kv_format: str = "fp",
                          iters: int = 10) -> dict:
    """Sweep the flash-decoding split-K width for one decode shape."""
    rng, kp, vp, tables, aux = _paged_case(0, S, Hkv, D, page, maxp,
                                           kv_format)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(0, maxp * page, S), jnp.int32)
    interp = _interpret()

    def build(num_splits):
        return lambda: paged_attn_decode_pallas(
            q, kp, vp, tables, lengths, num_splits=num_splits,
            interpret=interp, kv_format=kv_format, **aux)

    def plan_for(num_splits):
        return paged_attn_decode_plan(
            S=S, Hkv=Hkv, G=G, D=D, page=page, maxp=maxp,
            num_pages=kp.shape[0], num_splits=num_splits,
            kv_format=kv_format)

    cands = {f"num_splits={s}": {"num_splits": s}
             for s in splits if s <= maxp}
    out = sweep(build, cands, iters=iters, plan_for=plan_for)
    out["shape"] = dict(S=S, Hkv=Hkv, G=G, D=D, page=page, maxp=maxp,
                        kv_format=kv_format)
    return out


def autotune_paged_prefill(G: int, C: int, Hkv: int, Gq: int, D: int,
                           page: int, start: int, *,
                           block_qs=(8, 16, 32),
                           kv_format: str = "fp",
                           iters: int = 10) -> dict:
    """Sweep the q-block rows for one chunked-prefill shape."""
    maxp = (start + C) // page
    rng, kp, vp, tables, aux = _paged_case(1, G, Hkv, D, page, maxp,
                                           kv_format)
    q = jnp.asarray(rng.standard_normal((G, C, Hkv, Gq, D)), jnp.float32)
    interp = _interpret()

    def build(block_q):
        return lambda: paged_attn_prefill_pallas(
            q, kp, vp, tables, start=start, block_q=block_q,
            interpret=interp, kv_format=kv_format, **aux)

    def plan_for(block_q):
        return paged_attn_prefill_plan(
            G=G, C=C, Hkv=Hkv, Gq=Gq, D=D, page=page,
            num_pages=kp.shape[0], table_width=tables.shape[1],
            block_q=block_q, kv_format=kv_format)

    cands = {f"block_q={b}": {"block_q": b} for b in block_qs if b <= C}
    out = sweep(build, cands, iters=iters, plan_for=plan_for)
    out["shape"] = dict(G=G, C=C, Hkv=Hkv, Gq=Gq, D=D, page=page,
                        start=start, kv_format=kv_format)
    return out


def autotune_approx_bsn(rows: int, spec, *, block_rs=(64, 128, 256),
                        iters: int = 10) -> dict:
    """Sweep the BSN kernel's row-block size for one (rows, spec) shape."""
    from .dispatch import spec_stages                 # lazy: no cycle
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(0, spec.in_bsl + 1, (rows, spec.width)),
                    jnp.int32)
    interp = _interpret()
    stages = spec_stages(spec)

    def build(block_r):
        br = min(block_r, max(8, 1 << (rows - 1).bit_length()))
        rp = (rows + br - 1) // br * br
        xp = jnp.pad(x, ((0, rp - rows), (0, 0)))
        return lambda: approx_bsn_pallas(xp, in_bsl=spec.in_bsl,
                                         stages=stages, block_r=br,
                                         interpret=interp)

    def plan_for(block_r):
        br = min(block_r, max(8, 1 << (rows - 1).bit_length()))
        rp = (rows + br - 1) // br * br
        return approx_bsn_plan(rows=rp, width=spec.width,
                               in_bsl=spec.in_bsl, stages=stages,
                               block_r=br)

    cands = {f"block_r={b}": {"block_r": b} for b in block_rs}
    out = sweep(build, cands, iters=iters, plan_for=plan_for)
    out["shape"] = dict(rows=rows, width=spec.width, in_bsl=spec.in_bsl)
    return out
