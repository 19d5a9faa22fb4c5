"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

In order: make the weights on the device from the seed (one jitted
call); build ``ServeEngine`` from the configuration file; compile every
program the cell's traffic can reach without running it (a checkout's
first run compiles them, later runs load them from JAX's persistent
cache, in ``<checkout>/.jax_cache`` unless JAX_COMPILATION_CACHE_DIR
says otherwise); offer the traffic for
``--seconds``; free the engine and compare a sample of what it served
with the plain reference (bench/check.py).  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are printed
instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (, ``breakdown``),
and last ``check``, each compared number beside its limit; the same
numbers close standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

_T_PROC = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, spec  # noqa: E402
from bench.serve import RunLog, run_window, warm, warm_plan  # noqa: E402
from bench.traffic.generator import generate  # noqa: E402
from bench.weights import Dims, program_params  # noqa: E402

KERNELS = ("paged_attn_decode", "paged_attn_prefill")
MODULES = ("prefill", "decode")


class _Compiles:
    """Backend compiles and their seconds (jax.monitoring), one listener
    per process."""
    count = 0
    seconds = 0.0
    _on = False

    @classmethod
    def listen(cls):
        if cls._on:
            return
        import jax

        def on(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1
                cls.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(on)
        cls._on = True


def model_config(cf: dict):
    """The program's ModelConfig for a configuration file: the registered
    arch with every size the file states."""
    from repro.configs import get_arch
    c = cf["config"]
    arch = get_arch(cf["arch"])
    cfg = arch.scaled(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        rope_fraction=c.get("partial_rotary_factor", 1.0),
        dtype=c["torch_dtype"])
    norm = "layernorm" if "layer_norm_eps" in c else "rmsnorm"
    if cfg.norm != norm or cfg.tie_embeddings or cfg.padded_vocab \
            != cf["padded_vocab"] or not cfg.ffn_gated \
            or cfg.ffn_act != c["hidden_act"]:
        raise ValueError(f"{cf['name']}: the file does not describe arch "
                         f"{cf['arch']} (norm, tying, vocab padding or MLP)")
    return cfg


def _memory(devices) -> dict:
    out = {"peak_bytes_in_use": [], "peak_bytes_reserved": []}
    for d in devices:
        st = d.memory_stats() or {}
        for k in out:
            out[k].append(st.get(k))
    return out


class Cell:
    """A cell's files, read once: BENCHMARK.json's entry, configuration,
    traffic mix, the program's ModelConfig and EngineConfig."""

    def __init__(self, workload: str, root: str = _ROOT):
        from repro.serving import EngineConfig
        self.root, self.workload = root, workload
        self.bench = spec.load_benchmark(root)
        self.wl = spec.workload(self.bench, workload)
        self.cf = spec.config_of(self.bench, self.wl["config"], root)
        self.mix = spec.traffic_of(self.wl["traffic"],
                                   os.path.join(root, "bench"))
        self.traffic_dir = os.path.join(root, "bench", "traffic")
        self.cfg = model_config(self.cf)
        self.dims = Dims(self.cf["config"], self.cf["padded_vocab"])
        self.ec = EngineConfig(**self.cf["engine"]).validate()
        self.alpha_a = self.cf["weights"]["alpha_a"]
        self.wdtype = self.cf["weights"]["dtype"]

    def reference(self, seed: int, act_dtype: str | None = None,
                  attention: str | None = None):
        """The plain reference, rounding activations to the
        configuration's dtype, or to ``act_dtype`` (the control)."""
        from bench.reference.decoder import Reference
        return Reference(self.dims, self.alpha_a, seed, self.wdtype,
                         act_dtype or self.cf["config"]["torch_dtype"],
                         kv_int8=self.ec.kv_format == "int8",
                         attention=attention
                         or self.cf["check"]["attention"],
                         page=self.ec.page_size)


def _drain(eng):
    """Withdraw what is queued and end what is in a slot at its next
    token (one step)."""
    eng.queue.clear()
    for r in eng.slots:
        if r is not None:
            r.max_new_tokens = len(r.generated) + 1
    while any(s is not None for s in eng.slots):
        eng.step()


def serve(cell: Cell, seed: int, seconds: float, trace: bool,
          peaks: dict, fault=None, engine=None, warm_reqs=None):
    """Weights, engine, warm-up and the window.  Returns (RunLog, engine,
    set-up facts).  ``fault``, a context manager, wraps the timed path
    (engine build, warm-up, window): the tests plant faults with it.
    ``engine``, one this process built for the cell before, is drained
    and given this seed's weights; its programs are already warm.
    ``warm_reqs`` (default: this run's traffic) are the requests whose
    buckets the warm-up compiles."""
    import jax
    from repro.models import init_params
    from repro.serving import ServeEngine

    from bench import trace as trace_mod
    _Compiles.listen()
    ec, dims = cell.ec, cell.dims
    reqs = generate(cell.mix, seed, seconds, dims.vocab, cell.traffic_dir)
    log = RunLog(seconds=seconds, max_slots=ec.max_slots,
                 page_size=ec.page_size, chunk=ec.prefill_chunk,
                 kv_format=ec.kv_format, dims=dims, peaks=peaks)
    c_start = (_Compiles.count, _Compiles.seconds)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    n_warm = 0
    with (fault or contextlib.nullcontext)():
        abstract = jax.eval_shape(lambda k: init_params(k, cell.cfg),
                                  jax.random.key(0))
        if engine is not None:
            _drain(engine)
            engine.params = None
        params = program_params(seed, dims, cell.alpha_a, cell.wdtype,
                                abstract)
        if engine is None:
            engine = ServeEngine.from_config(params, cell.cfg, ec)
            jax.block_until_ready((params, engine.cache))
            n_warm = warm(engine, warm_plan(warm_reqs or reqs,
                                            ec.max_slots, ec.page_size,
                                            ec.prefill_chunk))
        else:
            engine.params = params
        del params
        facts = {"programs_warmed": n_warm,
                 "setup_compiles": _Compiles.count - c_start[0],
                 "setup_compile_s": _Compiles.seconds - c_start[1]}
        log.setup_s = time.perf_counter() - _T_PROC
        if trace:
            jax.profiler.start_trace(tdir)
        c0 = _Compiles.count
        run_window(engine, reqs, seconds, log)
        log.window_compiles = _Compiles.count - c0
        if trace:
            jax.profiler.stop_trace()
    if trace:
        log.trace = trace_mod.summarize(tdir, KERNELS, MODULES)
        shutil.rmtree(tdir, ignore_errors=True)
    return log, engine, facts


def readings(cell: Cell, log: RunLog, seed: int, control=None,
             attention=None):
    """The served sample's gaps under the reference, and with ``control``
    (a dtype narrower than the configuration's) the control's gaps on
    the same tokens.  ``attention`` picks another of the reference's
    attention variants (calibration only)."""
    picked = check.sample(log, seed)
    if not picked:
        return picked, np.zeros((0,)), None
    ref = cell.reference(seed, attention=attention)
    if control is None:
        return picked, check.gap_readings(ref, picked, cell.ec.max_len), \
            None
    prog, ctl = check.control_readings(
        ref, cell.reference(seed, control, attention), picked,
        cell.ec.max_len)
    return picked, prog, ctl


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             peaks: dict, root: str = _ROOT, fault=None,
             log_to=sys.stdout) -> dict:
    """Everything after the look for a chip: the contract's result."""
    import jax
    from repro.kernels import dispatch

    cell = Cell(workload, root)
    devices = jax.devices()[:cell.wl["chips"]]
    dispatch.clear_resolved()
    log, eng, facts = serve(cell, seed, seconds, trace, peaks, fault)
    mem = _memory(devices)
    resolved = {f"{k}:{b}": n for (k, b), n in
                sorted(dispatch.resolved_backends().items())}
    # the engine's state goes before the reference runs: the device's
    # peak has been read, and the reference needs the memory
    eng.cache = eng.params = None
    del eng
    gc.collect()

    metrics = {}
    for m in spec.metrics_for(cell.bench, workload, per_layer=trace):
        v = spec.metric_fn(m["name"], os.path.join(root, "bench"))(log)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    picked, gaps, _ = readings(cell, log, seed)
    verdict = check.judge(gaps, cell.cf["check"])
    correct = all(v["value"] <= v["limit"] for v in verdict.values())

    served = [r for r in log.reqs.values() if r.done is not None
              and r.done <= seconds]
    info = {
        "resolved_backends": resolved,
        "peak_bytes_in_use": mem["peak_bytes_in_use"],
        "peak_bytes_reserved": mem["peak_bytes_reserved"],
        **facts,
        "requests": {"sent": len(log.reqs) - log.failed,
                     "completed": len(served),
                     "truncated": sum(len(r.served) < r.max_new
                                      for r in served),
                     "refused": log.failed},
        "steps": len(log.steps),
        "sampled_requests": len(picked),
        "served_tokens_compared": len(gaps),
        "mean_logit_gap": float(np.mean(gaps)) if len(gaps) else None,
        "max_logit_gap": float(np.max(gaps)) if len(gaps) else None,
    }
    print(json.dumps(info), file=log_to, flush=True)
    # buffers' high-water mark on the fullest chip; program temporaries
    # sit in the reserved region (peak_bytes_reserved, printed above)
    peak = max((a or 0 for a in mem["peak_bytes_in_use"]), default=0)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct),
              "attempted": len(log.reqs),
              "failed": log.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = log.trace["busy_s"]
        device["window_s"] = log.trace["window_s"]
        result["breakdown"] = {"device_ops": log.trace["device_ops"],
                               "idle_gaps": log.trace["idle_gaps"]}
    result["check"] = verdict
    return result


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < wl["chips"]:
        print(f"{args.workload} needs {wl['chips']} chips, jax sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = spec.peaks_for(devices[0].device_kind)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # every program of the cell, however quick to compile, is read back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), peaks)
    for name, v in result["check"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
