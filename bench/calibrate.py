"""Readings that the check's limits are set from (not run by the cells).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds 15 [--control float8_e4m3fn]

One process, one engine: each seed gets its own weights and traffic,
served through the cell's own programs and window, and prints one JSON
line: the mean, median and widest gap of the served tokens under the
reference and, with ``--control``, of the tokens that the reference
computed in that narrower dtype puts first at the same positions (the
control, which the limit must fail).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--attention", default=None,
                    help="comma-separated reference attention variants "
                         "(bench/reference/decoder.ATTENTION) to read "
                         "the program's tokens under; the first also "
                         "reads the control's")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax sees {dev.platform}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = run.spec.peaks_for(dev.device_kind)
    cell = run.Cell(args.workload)
    eng = None
    for seed in (int(s) for s in args.seeds.split(",")):
        log, eng, _ = run.serve(cell, seed, args.seconds, False, peaks,
                                engine=eng)
        variants = (args.attention or "").split(",") if args.attention \
            else [None]
        picked, prog, ctl = run.readings(cell, log, seed, args.control,
                                         attention=variants[0])
        rec = {"seed": seed, "requests": len(picked),
               "served_tokens": int(len(prog))}
        sides = [(f"program_{variants[0]}", prog), ("control", ctl)]
        for v in variants[1:]:
            sides.append((f"program_{v}", run.readings(
                cell, log, seed, attention=v)[1]))
        for side, g in sides:
            if g is not None and len(g):
                rec |= {f"{side}_mean_gap": float(g.mean()),
                        f"{side}_median_gap": float(np.median(g)),
                        f"{side}_max_gap": float(g.max()),
                        f"{side}_zero": int((g == 0).sum()),
                        f"{side}_over_1": float((g > 1).mean()),
                        f"{side}_over_2": float((g > 2).mean())}
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
