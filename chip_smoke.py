"""Smoke run of the SC serving engine on one TPU chip, at full width.

    python chip_smoke.py               # one chip: three datapath phases
    python chip_smoke.py --four-chips  # tp=4 mesh vs one chip, only that

The default run builds granite-3-2b at its published widths (40 layers,
d_model 2048, 32 query / 8 KV heads of 64, d_ff 8192, bf16) with random
weights from ``--seed`` and serves it through the engine's normal API
(``EngineConfig`` / ``ServeEngine.from_config``) three times, one phase
per datapath, covering every KV pool format:

    sc_int_approx / sc,  sc_int / int8,  qat / fp

Each phase submits four requests (prompts of 24, 130, 512 and 1000
tokens, 32 new tokens each, one of them seeded-sampled) to an engine of
8 slots x 2048 tokens and runs them to completion.  The integer
datapaths must reproduce ``sequential_generate`` token for token; qat
reports its match count only.  Every paged-attention and BSN call the
phase traced must have resolved to the compiled Pallas kernel.

Per phase it prints one JSON line (compile and wall seconds, tokens,
device bytes, resolved backends); the last line of stdout is
``{"ok": true, "device": {...}}``.  Without a TPU it exits non-zero
before doing anything else; a failed phase raises, and nothing is
caught.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PROMPT_LENS = (24, 130, 512, 1000)
SAMPLED = 1                      # index of the seeded-sampled request
# largest expected program temp first: the device's peak counters
# never reset, so only the first phase's peaks are surely its own
PHASES = (("sc_int_approx", "sc"), ("sc_int", "int8"), ("qat", "fp"))
GATED = ("sc_int", "sc_int_approx")
# process-wide high-water marks of device 0, never reset: the buffers
# held (arguments, outputs, live arrays), and the reserved region that
# holds a TPU program's temporaries
PEAKS = ("peak_bytes_in_use", "peak_bytes_reserved")


def make_prompts(seed: int, vocab: int, lens=PROMPT_LENS) -> list[list[int]]:
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).tolist() for n in lens]


def _sampling(seed: int, n: int) -> list:
    from repro.serving import SamplingParams
    return [SamplingParams(temperature=0.8, top_p=0.9, seed=seed)
            if i == SAMPLED else SamplingParams() for i in range(n)]


def _compile_seconds():
    """A running total of backend compile time, read by calling it."""
    import jax
    total = [0.0]

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += secs
    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def serve(params, cfg, config, prompts, sampling, max_new_tokens):
    """Submit every prompt, run to completion, return tokens by rid."""
    from repro.serving import ServeEngine
    eng = ServeEngine.from_config(params, cfg, config)
    for p, sp in zip(prompts, sampling):
        eng.submit(p, max_new_tokens=max_new_tokens, sampling=sp)
    done = eng.run_to_completion()
    assert len(done) == len(prompts), (len(done), len(prompts))
    # free the pools now: the engine's jitted bound methods hold it in a
    # reference cycle, and the next phase needs the HBM
    eng.cache = None
    return [r.generated for r in sorted(done, key=lambda r: r.rid)]


def run_phase(params, cfg, *, datapath: str, kv_format: str,
              prompts: list[list[int]], seed: int, max_new_tokens: int = 32,
              max_slots: int = 8, max_len: int = 2048, page_size: int = 16,
              prefill_chunk: int = 64, kernel_backend: str = "pallas",
              compile_seconds=None) -> dict:
    """Serve ``prompts`` on one datapath and check the result.

    Raises when an integer datapath's tokens differ from
    ``sequential_generate`` or when a paged-attention / BSN call resolved
    to anything but ``kernel_backend`` (``"pallas"`` on the chip,
    ``"pallas-interpret"`` in a CPU test).  Returns the phase record.
    """
    from repro.kernels import dispatch
    from repro.serving import EngineConfig, sequential_generate

    config = EngineConfig(max_slots=max_slots, max_len=max_len,
                          page_size=page_size, prefill_chunk=prefill_chunk,
                          datapath=datapath, kv_format=kv_format)
    sampling = _sampling(seed, len(prompts))
    dispatch.clear_resolved()
    mem0 = _memory()
    c0 = compile_seconds() if compile_seconds else 0.0
    t0 = time.perf_counter()
    got = serve(params, cfg, config, prompts, sampling, max_new_tokens)
    wall = time.perf_counter() - t0
    compile_s = (compile_seconds() - c0) if compile_seconds else None
    want = sequential_generate(params, cfg, prompts,
                               max_new_tokens=max_new_tokens,
                               max_len=max_len, datapath=datapath,
                               sampling=sampling, kv_format=kv_format,
                               page_size=page_size)
    mem1 = _memory()
    resolved = dispatch.resolved_backends()
    matched = sum(a == b for g, w in zip(got, want) for a, b in zip(g, w))
    # per request: how many leading tokens agree with the oracle
    prefix = [next((i for i, (a, b) in enumerate(zip(g, w)) if a != b),
                   min(len(g), len(w))) for g, w in zip(got, want)]
    record = {
        "phase": f"{datapath}/{kv_format}",
        "compile_s": compile_s,
        "wall_s": wall,
        "tokens": sum(len(g) for g in got),
        "oracle_matched_tokens": matched,
        "oracle_agreeing_prefix": prefix,
        # bytes held at the phase's start and end, each PEAKS mark after
        # the phase, and how far the phase raised it (0: it stayed under
        # an earlier phase's or the parameter init's)
        "bytes_in_use": [mem0.get("bytes_in_use"), mem1.get("bytes_in_use")],
        **{k: mem1.get(k) for k in PEAKS},
        **{k + "_rise": mem1[k] - mem0[k] for k in PEAKS if k in mem0},
        "backends": {f"{k}:{b}": n for (k, b), n in sorted(resolved.items())},
    }
    stray = sorted(f"{k}:{b}" for (k, b) in resolved if b != kernel_backend)
    if stray or not resolved:
        raise RuntimeError(f"{record['phase']}: calls not served by the "
                           f"{kernel_backend} kernels: {stray or 'none seen'}")
    if datapath in GATED and got != want:
        raise RuntimeError(f"{record['phase']}: tokens differ from "
                           f"sequential_generate: {json.dumps(record)}")
    return record


def _memory() -> dict:
    """Device 0's allocator counters ({} where the backend keeps none)."""
    import jax
    return jax.devices()[0].memory_stats() or {}


def _granite():
    from repro.configs import get_arch
    return get_arch("granite-3-2b")


def one_chip(seed: int) -> None:
    import jax
    from repro.models import init_params

    compile_seconds = _compile_seconds()
    cfg = _granite()
    params = init_params(jax.random.key(seed), cfg)
    prompts = make_prompts(seed, cfg.vocab_size)
    for datapath, kv_format in PHASES:
        rec = run_phase(params, cfg, datapath=datapath, kv_format=kv_format,
                        prompts=prompts, seed=seed,
                        compile_seconds=compile_seconds)
        print(json.dumps(rec), flush=True)


def four_chips(seed: int) -> None:
    """granite-3-2b sc_int on a tp=4 serving mesh, then the same requests
    on one chip in this process.  The mesh path serves the reference
    attention (the Pallas kernel is single-device), so the one-chip run
    pins it too: the comparison isolates the sharding.  Passes when the
    tokens are identical and every device holds a share of the
    parameters and pools."""
    import jax
    from repro.launch.mesh import make_serving_mesh, serving_rules
    from repro.models import init_params
    from repro.serving import EngineConfig

    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(devices)}")
    cfg = _granite()
    params = init_params(jax.random.key(seed), cfg)
    prompts = make_prompts(seed, cfg.vocab_size)
    sampling = _sampling(seed, len(prompts))
    base = EngineConfig(max_slots=8, max_len=2048, page_size=16,
                        prefill_chunk=64, datapath="sc_int",
                        kv_format="int8", attn_backend="reference")
    before = [d.memory_stats()["bytes_in_use"] for d in devices]
    rules = serving_rules(make_serving_mesh(model_parallel=4))
    t0 = time.perf_counter()
    mesh_tokens = serve(params, cfg, base.replace(mesh_rules=rules),
                        prompts, sampling, 32)
    mesh_s = time.perf_counter() - t0
    peak = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    added = [p - b for p, b in zip(peak, before)]
    t0 = time.perf_counter()
    one_tokens = serve(params, cfg, base, prompts, sampling, 32)
    one_s = time.perf_counter() - t0
    rec = {"phase": "sc_int/int8 tp=4 vs one chip", "mesh_wall_s": mesh_s,
           "one_chip_wall_s": one_s,
           "tokens": sum(len(t) for t in mesh_tokens),
           "tokens_identical": mesh_tokens == one_tokens,
           "bytes_in_use_before": before, "peak_bytes_in_use": peak}
    print(json.dumps(rec), flush=True)
    if mesh_tokens != one_tokens:
        raise RuntimeError("tp=4 tokens differ from one chip")
    # device 0 also holds the unsharded params; the others must each
    # have taken a real share of the sharded ones
    if min(added[1:]) < 0.15 * max(added):
        raise RuntimeError(f"the mesh did not spread the model over the "
                           f"devices: peak bytes added {added}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 mesh path and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax sees {devices[0].platform} devices",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
