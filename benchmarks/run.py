"""Benchmark harness: one module per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV. Each bench validates a specific
paper claim; the mapping is DESIGN.md §7. Run everything:

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --only fsm_vs_bsn,bsn_cost
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import use_compile_cache

BENCHES = [
    "fsm_vs_bsn",            # Fig 1
    "quant_ablation",        # Table III
    "residual",              # Figs 6/8
    "precision_tradeoff",    # Fig 2 + Table IV
    "ber_fault",             # Fig 5
    "bsn_cost",              # Fig 9 + Table V + Fig 4
    "approx_bsn",            # Figs 10/11/13
    "kernels",               # Pallas datapath kernels
    "serving",               # ServeEngine v2 batched vs per-slot loop
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()
    use_compile_cache()
    names = args.only.split(",") if args.only else BENCHES

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod_name = f"benchmarks.bench_{name}"
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for row in mod.run():
                n, us, derived = row
                print(f"{n},{us if us else 0.0:.1f},{derived}", flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# BENCH {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
