"""Fig 10 + Fig 13: parameterized BSN design space / per-layer flexibility.

Fig 10a: reducing the BSN *output* BSL barely hurts SI accuracy (the
SI input-output precision gap).  Fig 10b + Fig 13: a design-space sweep
over (clip, stride, temporal fold) per ResNet18 conv size; the
spatial-temporal BSN right-sizes each layer — paper reports 8.2x..23.3x
ADP reduction vs the max-width baseline BSN with negligible MSE.

``kernel_sweep`` additionally times the execution paths of the adder
itself across BSL/width/stage points: exact bit-level sort kernel
(bsn_sort over the concatenated thermometer codes) vs the fused
approximate-BSN kernel vs the jitted count reference.  On this CPU
container the Pallas numbers are interpret-mode (correctness-path)
timings, not TPU performance — the point is the relative shape: the
approximate kernel touches ``width`` counts instead of sorting
``width * BSL`` bits.

``--smoke`` also runs the ``block_r`` autotune sweep per (rows, width)
shape (repro.kernels.autotune) and writes everything to
``BENCH_approx_bsn.json`` at the repo root.
"""

from __future__ import annotations

import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hwmodel, si
from repro.core.bsn import (ApproxBSNSpec, StageSpec, SubSampleSpec,
                            default_approx_spec)
from repro.kernels import autotune, dispatch, ops
from repro.launch.compile_cache import use_compile_cache

from .bench_bsn_cost import measured_mse

# artifact lands at the repo root regardless of cwd (committable,
# comparable across PRs) — same policy as bench_serving.py
ROOT = pathlib.Path(__file__).resolve().parents[1]

# ResNet18 conv accumulation widths (3x3 kernels x in-channels)
RESNET_LAYERS = {"3x3x64": 576, "3x3x128": 1152,
                 "3x3x256": 2304, "3x3x512": 4608}
IN_BSL = 2
MAX_WIDTH = 4608


def _spec_for(width: int, sigma: float, stride: int = 8) -> ApproxBSNSpec:
    """Two-stage spatial spec with a ~4-sigma clip window."""
    g1 = 64
    m = width // g1
    s1 = StageSpec(g1, SubSampleSpec(clip=48, stride=1))   # 128 -> 32 bits
    sorted2 = m * 32
    window = int(min(4 * sigma, sorted2 // 2))
    window = max(stride * 2, window // (2 * stride) * (2 * stride))
    clip = (sorted2 - 2 * window) // 2
    return ApproxBSNSpec(width=width, in_bsl=IN_BSL,
                         stages=(s1, StageSpec(m, SubSampleSpec(clip, stride))))


def run() -> list[tuple]:
    rows = []
    t0 = time.time()

    # ---- Fig 10a: output-BSL reduction at the SI --------------------------
    # ReLU output is one-sided: use zero_point=0 so the full out_bsl range
    # covers [0, max]; tanh stays symmetric.
    in_max = 512
    xs = np.arange(in_max + 1)
    import jax.numpy as jnp
    for out_bsl in (64, 32, 16, 8):
        for name, fn, zp in (("relu", si.relu_fn, 0.0),
                             ("tanh", si.tanh_fn(8.0), None)):
            v_in = 0.1 * (xs - in_max / 2)
            ideal = fn(v_in)
            span = float(ideal.max() - ideal.min())
            alpha_out = span / out_bsl
            t = si.si_thresholds(fn, in_max, out_bsl, alpha_in=0.1,
                                 alpha_out=alpha_out, zero_point=zp)
            out = np.asarray(si.apply_si_counts(jnp.asarray(xs),
                                                jnp.asarray(t)))
            zp_eff = out_bsl / 2 if zp is None else zp
            approx = alpha_out * (out - zp_eff)
            mse = float(np.mean((approx - ideal) ** 2))
            rows.append((f"fig10a_{name}_outbsl{out_bsl}", 0.0,
                         f"mse={mse:.2e} rel={mse / np.mean(ideal**2):.1e}"))

    # ---- Fig 13: per-layer right-sizing ------------------------------------
    baseline = hwmodel.bsn_cost(MAX_WIDTH * IN_BSL)   # provisioned for max
    for name, width in RESNET_LAYERS.items():
        sigma = (width * 0.32) ** 0.5
        # spatial-temporal: fold onto a 512-wide pipeline when wider
        if width > 512:
            cycles = width // 512
            spec = _spec_for(512, (512 * 0.32) ** 0.5)
            cost = hwmodel.spatial_temporal_cost(spec, cycles)
            adp = cost.area_um2 * cycles * cost.delay_ns
            mse = measured_mse(spec, cycles)
        else:
            cycles = 1
            spec = _spec_for(width, sigma)
            cost = hwmodel.approx_bsn_cost(spec)
            adp = cost.adp
            mse = measured_mse(spec)
        red = baseline.adp / adp
        rows.append((f"fig13_{name}", 0.0,
                     f"cycles={cycles} adp={adp:.3e} "
                     f"adp_red_vs_max_bsn={red:.1f}x mse={mse:.2e}"))

    avg_red = np.mean([float(r[2].split("adp_red_vs_max_bsn=")[1].split("x")[0])
                       for r in rows if r[0].startswith("fig13")])
    rows.append(("fig13_summary", 0.0,
                 f"avg_adp_reduction={avg_red:.1f}x "
                 "(paper: 8.2x..23.3x, avg 8.5x)"))
    us = (time.time() - t0) * 1e6 / len(rows)
    return [(n, us, d) for n, _, d in rows] + kernel_sweep()


# ---------------------------------------------------------------------------
# execution-path sweep: exact-sort kernel vs fused approx kernel vs reference
# ---------------------------------------------------------------------------

def _time_us(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))          # compile + warm
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


# (width, in_bsl, cycles): BSL sweep at fixed width, width sweep at fixed
# BSL, and one temporal fold — at least 3 spec points per the harness.
KERNEL_SWEEP_POINTS = ((128, 2, 1), (128, 4, 1), (512, 2, 1), (128, 2, 4))


def kernel_sweep(rows_batch: int = 256) -> list[tuple]:
    rng = np.random.default_rng(0)
    out = []
    for width, in_bsl, cycles in KERNEL_SWEEP_POINTS:
        spec = default_approx_spec(width, in_bsl)
        total = cycles * width
        counts = jnp.asarray(
            rng.integers(0, in_bsl + 1, (rows_batch, total)), jnp.int32)

        us_ref = _time_us(jax.jit(
            lambda c, s=spec, t=cycles: dispatch.approx_bsn(
                c, s, cycles=t, backend="reference")), counts)
        us_kernel = _time_us(
            lambda c, s=spec, t=cycles: dispatch.approx_bsn(
                c, s, cycles=t, backend="pallas-interpret", block_r=128),
            counts)

        # the exact adder sorts all width*BSL bits of the concatenation
        levels = np.asarray(counts) - in_bsl // 2
        bits = (levels[..., None] + in_bsl // 2
                > np.arange(in_bsl)).astype(np.int8)
        flat = jnp.asarray(bits.reshape(rows_batch, total * in_bsl))
        us_exact = _time_us(
            lambda b: ops.bsn_sort(b, min_rows_for_kernel=0, block_r=128),
            flat)

        ok = bool(jnp.array_equal(
            dispatch.approx_bsn(counts, spec, cycles=cycles,
                                backend="pallas-interpret", block_r=128),
            dispatch.approx_bsn(counts, spec, cycles=cycles,
                                backend="reference")))
        out.append((f"kernel_w{width}L{in_bsl}T{cycles}", us_kernel,
                    f"exact={ok} ref_us={us_ref:.0f} "
                    f"exact_sort_us={us_exact:.0f} "
                    f"fused_vs_exact_sort={us_exact / us_kernel:.1f}x "
                    f"out_bsl={spec.out_bsl} scale={spec.scale}"))
    return out


def autotune_sweep(smoke: bool = False) -> dict:
    """Row-block autotune per (rows, width) shape: the winners land in
    the artifact next to the timing rows, so successive PRs compare
    tile choices, not just end-to-end microseconds."""
    iters = 3 if smoke else 10
    out = {}
    for rows_b, width in ((64, 128), (64, 512), (256, 1152)):
        spec = default_approx_spec(width, IN_BSL)
        out[f"autotune_r{rows_b}_w{width}"] = autotune.autotune_approx_bsn(
            rows_b, spec, block_rs=(64, 128, 256), iters=iters)
    return out


def main() -> None:
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="kernel sweep only (fast); write "
                         "BENCH_approx_bsn.json")
    ap.add_argument("--out", default=str(ROOT / "BENCH_approx_bsn.json"))
    args = ap.parse_args()
    use_compile_cache()
    rows = kernel_sweep(rows_batch=64) if args.smoke else run()
    if args.smoke:
        results = {n: {"us_per_call": us, "derived": d}
                   for n, us, d in rows}
        results.update(autotune_sweep(smoke=True))
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}")
    for r in rows:
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()
