"""Where one cell's time goes, by the program's own spans, scopes and
counters (not run by cells).

    python3 bench/breakdown.py --workload <name> --seed <n> --seconds <s> \
        [--trace-dir <dir>]

Builds and warms the cell's engine as bench/run.py does, then offers
the traffic for two windows on it: the first with the profiler off, the
second (traffic from ``seed + 1``) under the profiler; a TPU profile
names each operation by its instruction only, so the programs the
window ran are compiled again (cache hits) for their op_names.  Prints
one JSON object: per window the engine's counters (``ServeEngine.stats``,
the window's change; the peaks are the engine's since it was built) and
``decode_step_ms``; for the traced window bench/trace.py's summary and
bench/scopes.py's (idle by innermost span, device time by scope, host
time per step); and the readings they give:

* ``engine_idle_share``: device idle under an ``engine.*`` span / window;
* ``step_host_ms``: median over decode steps of ``engine.step`` less its
  ``*.sync`` children;
* ``prefill_token_use``: ``prefill_tokens / prefill_tokens_padded``;
* ``sc_proj_roofline``: least time of the window's projections
  (bench/cost/sc_proj.py) / device time under ``sc_linear``, percent;
* ``kv_pages_peak_share``: ``pages_in_use_peak / pages_total``.

The profile is kept in ``--trace-dir`` when given.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, scopes, spec, trace  # noqa: E402
from bench.cost import sc_proj  # noqa: E402
from bench.serve import RunLog, run_window, warm_plan  # noqa: E402
from bench.stats import in_window  # noqa: E402
from bench.traffic.generator import generate  # noqa: E402


def _delta(after: dict, before: dict) -> dict:
    """The window's change of each counter; a peak is kept as it is."""
    return {k: v if k.startswith("pages_") or k.endswith("_peak")
            else v - before[k] for k, v in after.items()}


def program_texts(eng, plan, threads: int = 8) -> list:
    """Compiled HLO text of every program in a warm plan, lowered with
    the arguments bench/serve.warm gives them (so the compile is a cache
    hit): a TPU profile names an operation by its instruction, and the
    text holds the instruction's op_name (bench/scopes.hlo_op_names)."""
    from concurrent.futures import ThreadPoolExecutor

    import jax.numpy as jnp
    from repro.serving.sampling import SamplingParams, pack_sampling
    trash, scratch = 0, eng.config.max_slots

    def lanes(n, fill):
        return jnp.full((n,), fill, jnp.int32)

    def samp(n):
        return pack_sampling([SamplingParams()] * n, pad_to=n)
    lowered = []
    with eng._scope():
        for g, L, w, chunk in plan["prefill"]:
            lowered.append(eng._prefill_batched.lower(
                eng.params, eng.cache, jnp.zeros((g, L), jnp.int32),
                jnp.full((g, w), trash, jnp.int32), lanes(g, 0),
                lanes(g, scratch), samp(g), chunk=chunk,
                do_sample=False, lp_k=0))
        for n, m in plan["decode"]:
            lowered.append(eng._decode.lower(
                eng.params, eng.cache, lanes(n, 0), lanes(n, scratch),
                jnp.full((n, m), trash, jnp.int32), lanes(n, 0), samp(n),
                do_sample=False, lp_k=0))
    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(lambda lo: lo.compile().as_text(), lowered))


def readings(log: RunLog, counters: dict, prog: dict) -> dict:
    """The five readings of a traced window (module docstring)."""
    idle = sum(v for k, v in prog["idle_by_span"].items()
               if k.startswith("engine."))
    least = sum(sc_proj.step_least_seconds(log.dims, log.peaks, s.admitted,
                                           len(s.decode_lens))
                for s in in_window(log))
    proj = prog["device_by_scope"].get("sc_linear")
    return {
        "engine_idle_share": idle / prog["window_s"],
        "step_host_ms": prog["step_host_ms"],
        "prefill_token_use": (counters["prefill_tokens"]
                              / counters["prefill_tokens_padded"]
                              if counters["prefill_tokens_padded"]
                              else None),
        "sc_proj_roofline": 100.0 * least / proj if proj else None,
        "kv_pages_peak_share": (counters["pages_in_use_peak"]
                                / counters["pages_total"]),
    }


def breakdown(workload: str, seed: int, seconds: float, peaks: dict,
              trace_dir: str | None = None, root: str = _ROOT) -> dict:
    import jax
    cell = run.Cell(workload, root)
    step_ms = spec.metric_fn("decode_step_ms", os.path.join(root, "bench"))
    log, eng, facts = run.serve(cell, seed, seconds, False, peaks)
    before = eng.stats
    out = {"workload": workload, "seed": seed, **facts,
           "plain": {"counters": before, "decode_step_ms": step_ms(log)}}
    run._drain(eng)
    reqs = generate(cell.mix, seed + 1, seconds, cell.dims.vocab,
                    cell.traffic_dir)
    log = RunLog(seconds=seconds, max_slots=cell.ec.max_slots,
                 page_size=cell.ec.page_size, chunk=cell.ec.prefill_chunk,
                 kv_format=cell.ec.kv_format, dims=cell.dims, peaks=peaks)
    tdir = trace_dir or tempfile.mkdtemp(prefix="bench_breakdown_")
    before = eng.stats
    jax.profiler.start_trace(tdir)
    try:
        run_window(eng, reqs, seconds, log)
    finally:
        jax.profiler.stop_trace()
    counters = _delta(eng.stats, before)
    ec = cell.ec
    texts = program_texts(eng, warm_plan(reqs, ec.max_slots, ec.page_size,
                                         ec.prefill_chunk))
    prog = scopes.summarize(tdir, texts)
    out["traced"] = {"counters": counters, "decode_step_ms": step_ms(log),
                     "trace": trace.summarize(tdir, run.KERNELS,
                                              run.MODULES),
                     "program": prog,
                     "readings": readings(log, counters, prog)}
    if trace_dir is None:
        shutil.rmtree(tdir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-dir")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = spec.peaks_for(jax.devices()[0].device_kind)
    print(json.dumps(breakdown(args.workload, args.seed, args.seconds,
                               peaks, args.trace_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
