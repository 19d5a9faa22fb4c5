"""Find the highest offered rate a cell's system sustains (run once, on
the chip, to fix the rate in an open-loop mix; the cells never run it).

    python3 bench/sweep.py --workload <name> --rates 2,3,4,5 --seconds 30

One process, one engine; per rate the cell's mix at that Poisson rate,
one JSON line: output tokens/s, TTFT p95, the share of requests due in
the window that had their first token by its end, and how many were
still queued at its end (a queue that grows through the window marks a
rate past capacity).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run, spec  # noqa: E402
from bench.traffic.generator import generate  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax sees {dev.platform}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = spec.peaks_for(dev.device_kind)
    cell = run.Cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    mixes = [dict(cell.mix, arrivals={"process": "poisson",
                                      "rate_per_s": r}) for r in rates]
    every = [q for m in mixes
             for q in generate(m, args.seed, args.seconds, cell.dims.vocab,
                               cell.traffic_dir)]
    eng = None
    for rate, mix in zip(rates, mixes):
        cell.mix = mix
        log, eng, facts = run.serve(cell, args.seed, args.seconds, False,
                                    peaks, engine=eng, warm_reqs=every)
        print(json.dumps(facts), flush=True)
        due = [r for r in log.reqs.values() if r.due < args.seconds]
        first = [r for r in due if r.token_times
                 and r.token_times[0] <= args.seconds]
        rec = {"rate": rate, "due": len(due),
               "first_token_share": len(first) / max(len(due), 1),
               "queued_at_end": sum(r.admit is None for r in due)}
        for m in ("output_tps", "ttft_p95_ms", "itl_p95_ms",
                  "queue_wait_p95_ms", "decode_step_ms",
                  "batch_occupancy"):
            rec[m] = spec.metric_fn(m)(log)
        late = [min(r.admit if r.admit is not None else args.seconds,
                    args.seconds) - r.due
                for r in due if r.due >= args.seconds / 2]
        rec["late_half_queue_wait_ms"] = 1e3 * sum(late) / max(len(late), 1)
        rec["window_compiles"] = log.window_compiles
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
