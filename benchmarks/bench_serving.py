"""ServeEngine v2 throughput: batched paged decode vs the per-slot loop.

Measures end-to-end tokens/sec of the continuous-batching engine against
the seed execution model (per-request prefill + one-token-at-a-time
batch-1 decode — exactly what ``serving.sequential_generate`` encodes)
across concurrency levels and prompt-length mixes.  Both sides are
jit-warmed before timing; the sequential baseline reuses its compiled
steps across requests, so the speedup is batching, not caching.

CLI:
    PYTHONPATH=src python benchmarks/bench_serving.py            # full
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke    # CI job
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python benchmarks/bench_serving.py --sharded --smoke
The smoke run writes ``BENCH_serving.json`` (tokens/sec per point +
the 8-way speedup, plus seeded-sampled vs greedy decode throughput —
the cost of the in-jit top-k/top-p filter and categorical draw — plus
recurrent prefill tokens/sec: mamba/rwkv6 through the batched chunked
paged path vs the retired exact-length per-request fallback;
``--recurrent`` runs just that slice, the CI matrix smoke — plus the
paged-attention kernel differential: decode tokens/sec with the
attention backend pinned to the Pallas kernel vs the XLA gather
reference, and per-shape autotune winners from repro.kernels.autotune;
``--paged-kernel`` runs just that slice — plus the compressed KV pool
slice: decode tokens/sec and analytic slots-per-GiB per ``kv_format``
(fp / int8 / sc), with batched==sequential token-identity and the
int8 >= 2x-capacity gate asserted inline; ``--kv-format`` runs just
that slice — plus the speculative-decoding slice: draft on
sc_int_approx, verify on qat / sc_int, recording wall tokens/sec,
acceptance rate and tokens-per-round per pair (token identity
asserted before timing) and the coupled-ceiling cells whose >=1.5x
verifier-step reduction is gated; ``--spec-decode`` runs just that
slice).  The artifact is written to
the REPO ROOT so it is committable.  ``--sharded``
additionally measures the mesh-sharded engine against the unsharded one
on the same prompts and writes ``BENCH_serving_sharded.json``.  On
forced host devices the sharded path is expected to be SLOWER (every
collective is a host copy) — the artifact tracks the overhead trend,
it is not gated.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import LayerSpec, get_arch
from repro.kernels import autotune
from repro.launch.compile_cache import use_compile_cache
from repro.models import decode_step, init_params, prefill
from repro.serving import SamplingParams, ServeEngine
from repro.serving.engine import _pad_prefill_cache

# bench artifacts land at the REPO ROOT regardless of cwd, so the smoke
# JSONs are stable, committable and comparable across PRs (they used to
# exist only as CI artifacts — the perf trajectory was empty)
ROOT = pathlib.Path(__file__).resolve().parents[1]

MAX_LEN = 64
PAGE = 16

CFG = get_arch("granite-3-2b").scaled(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=64, vocab_pad_multiple=32, dtype="float32", attn_q_chunk=8)

# recurrent mixers: chunked state-carrying paged prefill vs the retired
# exact-length per-request fallback (prefill_mode="exact" debug oracle)
_RSCALE = dict(d_model=64, n_heads=4, d_ff=128, vocab_size=64,
               vocab_pad_multiple=32, dtype="float32")
RECURRENT_CFGS = {
    "mamba": get_arch("jamba-1.5-large-398b").scaled(
        period=(LayerSpec("mamba", "dense"),), n_layers=2,
        n_kv_heads=2, mamba_d_state=8, **_RSCALE),
    "rwkv6": get_arch("rwkv6-7b").scaled(
        n_layers=2, n_kv_heads=4, rwkv_head_dim=16, **_RSCALE),
}

MIXES = {
    "uniform8": lambda n: [[(7 * i + j) % 64 for j in range(8)]
                           for i in range(n)],
    "mixed4to24": lambda n: [[(5 * i + j) % 64
                              for j in range(4 + (i * 5) % 21)]
                             for i in range(n)],
}


def _engine_tps(params, n_req, prompts_fn, max_new, cfg=None,
                rules=None, sampled=False, attn_backend=None,
                datapath="qat", kv_format="fp") -> float:
    eng = ServeEngine(params, cfg if cfg is not None else CFG,
                      max_slots=min(n_req, 8), max_len=MAX_LEN,
                      page_size=PAGE, mesh_rules=rules,
                      attn_backend=attn_backend, datapath=datapath,
                      kv_format=kv_format)
    # seeded stochastic decode (vs the default greedy): same jitted step,
    # plus the in-jit filter + categorical draw per token
    sps = [SamplingParams(temperature=0.8, top_p=0.9, top_k=32, seed=i)
           for i in range(n_req)] if sampled else [None] * n_req

    def wave():
        for p, sp in zip(prompts_fn(n_req), sps):
            eng.submit(p, max_new_tokens=max_new, sampling=sp)
        done = eng.run_to_completion()
        return sum(len(r.generated) for r in done)

    wave()                                    # compile every bucket
    t0 = time.time()
    toks = wave()
    return toks / (time.time() - t0)


def _sequential_tps(params, n_req, prompts_fn, max_new) -> float:
    """The seed per-slot loop, jitted once and warmed (see module doc)."""
    prefill_fn = jax.jit(lambda b: prefill(params, b, CFG))
    decode_fn = jax.jit(lambda c, t: decode_step(params, c, t, CFG))

    def wave():
        total = 0
        for prompt in prompts_fn(n_req):
            toks = jnp.asarray(prompt, jnp.int32)[None, :]
            logits, cache = prefill_fn({"tokens": toks})
            cache = _pad_prefill_cache(cache, MAX_LEN)
            gen = [int(jnp.argmax(logits[0, -1, :CFG.vocab_size]))]
            while len(gen) < max_new:
                tok = jnp.asarray([[gen[-1]]], jnp.int32)
                logits, cache = decode_fn(cache, tok)
                gen.append(int(jnp.argmax(logits[0, 0, :CFG.vocab_size])))
            total += len(gen)
        return total

    wave()
    t0 = time.time()
    toks = wave()
    return toks / (time.time() - t0)


def _recurrent_prefill_tps(params, cfg, prefill_mode, n_req) -> float:
    """PREFILL tokens/sec for a recurrent arch (max_new_tokens=1 so the
    wave is prefill-dominated).  The mixed-length prompt set makes the
    exact path pay its real cost: one compiled variant per distinct
    prompt length vs the chunked path's pow2 buckets."""
    eng = ServeEngine(params, cfg, max_slots=8, max_len=MAX_LEN,
                      page_size=PAGE, prefill_mode=prefill_mode)
    prompts = MIXES["mixed4to24"](n_req)

    def wave():
        for p in prompts:
            eng.submit(p, max_new_tokens=1)
        done = eng.run_to_completion()
        return sum(len(r.prompt) for r in done)

    wave()                                    # compile every variant
    t0 = time.time()
    toks = wave()
    return toks / (time.time() - t0)


def run_recurrent(smoke: bool = False):
    """Recurrent prefill: batched chunked-paged vs the old exact-length
    per-request fallback, prompt tokens/sec (recorded, not gated)."""
    n_req = 8 if smoke else 16
    rows, results = [], {}
    for name, cfg in RECURRENT_CFGS.items():
        params = init_params(jax.random.key(0), cfg)
        tps_c = _recurrent_prefill_tps(params, cfg, "chunked", n_req)
        tps_e = _recurrent_prefill_tps(params, cfg, "exact", n_req)
        key = f"recurrent_prefill_{name}"
        results[key] = {"chunked_tps": tps_c, "exact_tps": tps_e,
                        "chunked_vs_exact": tps_c / tps_e}
        rows.append((key, 1e6 / tps_c,
                     f"chunked_tps={tps_c:.1f} exact_tps={tps_e:.1f} "
                     f"chunked_vs_exact={tps_c / tps_e:.2f}x"))
    return rows, results


def run_paged(smoke: bool = False):
    """Paged-attention kernel vs the XLA gather/scatter reference:
    engine decode tokens/sec with the attention backend pinned each way,
    plus the per-shape autotune winners (split-K width for decode,
    q-block rows for chunked prefill).  On this CPU container the
    kernel leg runs the Pallas interpreter, so kernel_vs_xla tracks
    dispatch + interpreter overhead (expected << 1); on a TPU the same
    rows time Mosaic.  The schema is stable either way — that is what
    the root-level artifact is for."""
    params = init_params(jax.random.key(0), CFG)
    n_req, max_new = 8, (8 if smoke else 16)
    rows, results = [], {}
    tps_k = _engine_tps(params, n_req, MIXES["uniform8"], max_new,
                        attn_backend="pallas-interpret")
    tps_r = _engine_tps(params, n_req, MIXES["uniform8"], max_new,
                        attn_backend="reference")
    key = "paged_attn_decode_uniform8_n8"
    results[key] = {"kernel_tps": tps_k, "xla_gather_tps": tps_r,
                    "kernel_vs_xla": tps_k / tps_r,
                    "kernel_backend": "pallas-interpret"}
    rows.append((key, 1e6 / tps_k,
                 f"kernel_tps={tps_k:.1f} xla_gather_tps={tps_r:.1f} "
                 f"kernel_vs_xla={tps_k / tps_r:.2f}x"))
    # autotune sweeps at the serving shapes (and one longer-context
    # decode shape where split-K has room to matter)
    iters = 3 if smoke else 10
    hkv, gq = CFG.n_kv_heads, CFG.n_heads // CFG.n_kv_heads
    dh = CFG.d_model // CFG.n_heads
    tune = {
        "decode_serving": autotune.autotune_paged_decode(
            8, hkv, gq, dh, PAGE, MAX_LEN // PAGE, iters=iters),
        "decode_long": autotune.autotune_paged_decode(
            8, hkv, gq, dh, PAGE, 16, splits=(1, 2, 4, 8), iters=iters),
        "prefill_chunk": autotune.autotune_paged_prefill(
            4, 32, hkv, gq, dh, PAGE, 32, block_qs=(8, 16, 32),
            iters=iters),
    }
    results["paged_attn_autotune"] = tune
    for name, t in tune.items():
        rows.append((f"paged_autotune_{name}",
                     t["us_per_call"][t["winner"]],
                     f"winner={t['winner']}"))
    return rows, results


def run_kv_formats(smoke: bool = False):
    """Compressed KV pools: decode tokens/sec + slots-per-GiB per
    ``kv_format``.  Every format runs datapath="sc_int" so "sc" is a
    legal pairing and the comparison isolates the cache format.  Before
    timing, each format's engine is checked token-identical to its
    same-format B=1 sequential oracle, and the sc round-trip error is
    checked against its analytic bound — a perf number can never ship
    for a wrong-token configuration.  The capacity gate (int8 >= 2x fp
    slots at unchanged page_size) is asserted here as in the tests."""
    from repro.core.kv_quant import (KV_FORMATS, kv_dequant,
                                     kv_error_bound, kv_quant)
    from repro.serving import sequential_generate, slots_per_gib
    params = init_params(jax.random.key(0), CFG)
    n_req, max_new = 8, (8 if smoke else 16)
    hkv, dh = CFG.n_kv_heads, CFG.d_model // CFG.n_heads
    # sc accuracy: the cache round-trip honors |err| <= alpha_r / 2
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (64, hkv, dh)), jnp.float32)
    qd = kv_quant(x, "sc")
    err = jnp.abs(kv_dequant(qd["q"], qd["scale"], qd["resid"],
                             fmt="sc") - x)
    bound = kv_error_bound(qd["scale"], "sc")[..., None]
    assert bool(jnp.all(err <= bound * (1 + 1e-6))), "sc bound violated"
    rows, results = [], {}
    spg = {f: slots_per_gib(MAX_LEN, PAGE, hkv, dh, f,
                            n_layers=CFG.n_layers) for f in KV_FORMATS}
    assert spg["int8"] >= 2.0 * spg["fp"], \
        f"int8 capacity gate: {spg['int8'] / spg['fp']:.2f}x < 2x"
    prompts = MIXES["uniform8"](3)
    for fmt in KV_FORMATS:
        eng = ServeEngine(params, CFG, max_slots=2, max_len=MAX_LEN,
                          page_size=PAGE, datapath="sc_int",
                          kv_format=fmt)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        got = [r.generated for r in sorted(eng.run_to_completion(),
                                           key=lambda r: r.rid)]
        want = sequential_generate(params, CFG, prompts,
                                   max_new_tokens=4, max_len=MAX_LEN,
                                   datapath="sc_int", kv_format=fmt)
        assert got == want, f"{fmt}: batched != sequential"
        tps = _engine_tps(params, n_req, MIXES["uniform8"], max_new,
                          datapath="sc_int", kv_format=fmt)
        key = f"serving_kv_{fmt}_uniform8_n8"
        results[key] = {"decode_tps": tps,
                        "slots_per_gib": spg[fmt],
                        "slots_vs_fp": spg[fmt] / spg["fp"]}
        rows.append((key, 1e6 / tps,
                     f"decode_tps={tps:.1f} "
                     f"slots_per_gib={spg[fmt]:.0f} "
                     f"slots_vs_fp={spg[fmt] / spg['fp']:.2f}x"))
    return rows, results


def _spec_tps(params, n_req, prompts_fn, max_new, datapath,
              spec: bool, draft_len: int = 4, perfect: bool = False):
    """Wall tokens/sec + spec_stats for one engine configuration.
    ``perfect=True`` points the drafter at the target datapath (the
    coupled ceiling: acceptance is 1.0 by construction)."""
    eng = ServeEngine(params, CFG, max_slots=min(n_req, 8),
                      max_len=MAX_LEN, page_size=PAGE, datapath=datapath,
                      spec_decode=spec, draft_len=draft_len)
    if perfect:
        eng.cfg_draft = eng.cfg

    def wave():
        for p in prompts_fn(n_req):
            eng.submit(p, max_new_tokens=max_new)
        done = eng.run_to_completion()
        return sum(len(r.generated) for r in done)

    wave()
    t0 = time.time()
    toks = wave()
    return toks / (time.time() - t0), dict(eng.spec_stats) if spec else {}


def run_spec_decode(smoke: bool = False):
    """Cross-datapath speculative decoding: draft on sc_int_approx,
    verify on the target datapath in ONE batched multi-token step.

    Two families of cells:

    * ``spec_approx_to_{qat,sc_int}`` — the paper's pairing, recorded
      honestly.  Before timing, spec-on is asserted token-identical to
      spec-off (greedy) — a perf number can never ship for a
      wrong-token configuration.  NOTE the simulation-vs-silicon cost
      inversion: on real SC hardware the approximate-BSN drafter is the
      cheap path (that is the paper's whole premise), but this repo
      SIMULATES the approximate adder with extra integer ops, so here
      the drafter costs MORE wall-clock per step than the target
      (jaxpr op counts: qat 396 / sc_int 418 / sc_int_approx 562 on
      the bench config).  Wall speedup < 1 on this box is therefore
      expected and NOT gated; the hardware-relevant number is the
      verifier-side step reduction below.
    * ``spec_coupled_ceiling_*`` — drafter == target (the acceptance
      ceiling the shared-Gumbel coupling guarantees): acceptance rate
      is exactly 1.0 and the engine takes ``ceil((max_new-1)/(k+1))``
      verify rounds instead of ``max_new-1`` decode ticks.  The
      ``verifier_step_reduction`` cell is gated >= 1.5x — on silicon,
      where drafting is nearly free, this bounds the decode speedup.
    """
    params = init_params(jax.random.key(0), CFG)
    n_req, max_new, k = 8, (8 if smoke else 16), 4
    prompts = MIXES["uniform8"]
    rows, results = [], {}
    for target in ("qat", "sc_int"):
        # token identity first (greedy): spec must change nothing
        outs = []
        for spec in (True, False):
            eng = ServeEngine(params, CFG, max_slots=4, max_len=MAX_LEN,
                              page_size=PAGE, datapath=target,
                              spec_decode=spec, draft_len=k)
            for p in prompts(4):
                eng.submit(p, max_new_tokens=max_new)
            outs.append([r.generated for r in
                         sorted(eng.run_to_completion(),
                                key=lambda r: r.rid)])
        assert outs[0] == outs[1], f"{target}: spec-on != spec-off"

        base_tps, _ = _spec_tps(params, n_req, prompts, max_new, target,
                                spec=False)
        spec_tps, st = _spec_tps(params, n_req, prompts, max_new, target,
                                 spec=True, draft_len=k)
        key = f"spec_approx_to_{target}_uniform8_n8"
        results[key] = {
            "spec_decode_tps": spec_tps, "baseline_tps": base_tps,
            "wall_speedup": spec_tps / base_tps,
            "acceptance_rate": st["acceptance_rate"],
            "tokens_per_round": st["tokens_per_round"],
            "draft_len": k, "drafter": "sc_int_approx",
        }
        rows.append((key, 1e6 / spec_tps,
                     f"spec_tps={spec_tps:.1f} base_tps={base_tps:.1f} "
                     f"wall_speedup={spec_tps / base_tps:.2f}x "
                     f"accept={st['acceptance_rate']:.2f}"))

        # the coupled ceiling: drafter == target, acceptance 1.0
        ctps, cst = _spec_tps(params, n_req, prompts, max_new, target,
                              spec=True, draft_len=k, perfect=True)
        # stats accumulate over the warm + timed wave (2 waves); a plain
        # engine spends max_new-1 decode ticks per wave (prefill emits
        # token 1), the spec engine cst["rounds"]/2 verify rounds
        plain_steps = max_new - 1
        reduction = 2 * plain_steps / cst["rounds"]
        ckey = f"spec_coupled_ceiling_{target}_uniform8_n8"
        results[ckey] = {
            "spec_decode_tps": ctps,
            "acceptance_rate": cst["acceptance_rate"],
            "tokens_per_round": cst["tokens_per_round"],
            "verifier_steps_plain": plain_steps,
            "verifier_rounds_spec": cst["rounds"] / 2,
            "verifier_step_reduction": reduction,
            "draft_len": k, "drafter": target,
        }
        rows.append((ckey, 1e6 / ctps,
                     f"accept={cst['acceptance_rate']:.2f} "
                     f"rounds={cst['rounds'] / 2:.0f} vs {plain_steps} "
                     f"ticks step_reduction={reduction:.2f}x"))
        assert cst["acceptance_rate"] == 1.0, \
            f"{target}: coupled ceiling acceptance {cst['acceptance_rate']}"
        assert reduction >= 1.5, \
            f"{target}: verifier step reduction {reduction:.2f}x < 1.5x"
    return rows, results


def run(smoke: bool = False) -> list[tuple]:
    params = init_params(jax.random.key(0), CFG)
    max_new = 8 if smoke else 16
    slot_counts = (8,) if smoke else (1, 4, 8)
    mixes = ("uniform8",) if smoke else tuple(MIXES)
    rows, results = [], {}
    for mix in mixes:
        for n in slot_counts:
            tps_b = _engine_tps(params, n, MIXES[mix], max_new)
            tps_s = _sequential_tps(params, n, MIXES[mix], max_new)
            # sampled decode (temperature/top-k/top-p inside the jit) vs
            # greedy: tracks what the filter + categorical draw cost per
            # decoded token — recorded, not gated
            tps_smp = _engine_tps(params, n, MIXES[mix], max_new,
                                  sampled=True)
            speedup = tps_b / tps_s
            key = f"serving_{mix}_n{n}"
            results[key] = {"batched_tps": tps_b, "sequential_tps": tps_s,
                            "speedup": speedup, "sampled_tps": tps_smp,
                            "sampled_vs_greedy": tps_smp / tps_b}
            rows.append((key, 1e6 / tps_b,
                         f"batched_tps={tps_b:.1f} seq_tps={tps_s:.1f} "
                         f"speedup={speedup:.2f}x "
                         f"sampled_tps={tps_smp:.1f} "
                         f"sampled_vs_greedy={tps_smp / tps_b:.2f}x"))
    # recurrent prefill trajectory rides in the same artifact
    rrows, rresults = run_recurrent(smoke=smoke)
    rows += rrows
    results.update(rresults)
    # ...and so do the paged-kernel differential + autotune winners
    prows, presults = run_paged(smoke=smoke)
    rows += prows
    results.update(presults)
    # ...and the per-kv_format decode throughput + capacity accounting
    krows, kresults = run_kv_formats(smoke=smoke)
    rows += krows
    results.update(kresults)
    # ...and the speculative-decoding slice (honest cross-datapath
    # pairs + the gated coupled-ceiling step reduction)
    srows, sresults = run_spec_decode(smoke=smoke)
    rows += srows
    results.update(sresults)
    return rows if not smoke else (rows, results)


def run_sharded(smoke: bool = False):
    """Mesh-sharded engine vs the same engine unsharded, same prompts.

    Needs a multi-device jax (CI forces 8 host devices).  The sharded
    engine must produce the same token count — token identity is the
    test suite's job (tests/test_sharded_serving.py); here we track the
    collective overhead on the forced-host mesh.
    """
    ndev = jax.device_count()
    if ndev < 2:
        raise SystemExit(
            "--sharded needs a multi-device jax; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.launch.mesh import make_serving_mesh, serving_rules
    # tp must divide n_heads=4 (GQA grouping) AND equal the KV head
    # count so the pools shard: 4-way when possible, else 2-way
    tp = 4 if ndev >= 4 else 2
    dp = 2 if ndev >= 2 * tp else 1
    rules = serving_rules(make_serving_mesh(model_parallel=tp,
                                            data_parallel=dp))
    cfg = CFG.scaled(n_kv_heads=tp)
    params = init_params(jax.random.key(0), cfg)
    max_new = 8 if smoke else 16
    mixes = ("uniform8",) if smoke else tuple(MIXES)
    rows, results = [], {}
    for mix in mixes:
        tps_sh = _engine_tps(params, 8, MIXES[mix], max_new, cfg=cfg,
                             rules=rules)
        tps_un = _engine_tps(params, 8, MIXES[mix], max_new, cfg=cfg)
        key = f"serving_sharded_{mix}_n8"
        results[key] = {"sharded_tps": tps_sh, "unsharded_tps": tps_un,
                        "ratio": tps_sh / tps_un, "devices": ndev,
                        "mesh": f"{dp}x{tp}"}
        rows.append((key, 1e6 / tps_sh,
                     f"sharded_tps={tps_sh:.1f} unsharded_tps={tps_un:.1f} "
                     f"ratio={tps_sh / tps_un:.2f}x mesh={dp}x{tp}"))
    return rows, results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one fast point; write BENCH_serving.json")
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-sharded engine vs unsharded (needs "
                         "multi-device jax); writes "
                         "BENCH_serving_sharded.json")
    ap.add_argument("--recurrent", action="store_true",
                    help="recurrent prefill only: mamba + rwkv6 through "
                         "the engine, chunked-paged vs the exact "
                         "fallback (the CI matrix smoke)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged-attention kernel slice only: kernel vs "
                         "XLA-gather decode tokens/sec + autotune "
                         "sweeps (the CI matrix smoke)")
    ap.add_argument("--kv-format", action="store_true",
                    help="compressed KV pool slice only: per-kv_format "
                         "decode tokens/sec + slots-per-GiB, with the "
                         "batched==sequential and int8>=2x capacity "
                         "asserts (the CI matrix smoke)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding slice only: draft on "
                         "sc_int_approx / verify on qat and sc_int, "
                         "with spec-on==spec-off token identity and "
                         "the coupled-ceiling >=1.5x verifier step "
                         "reduction asserted (the CI matrix smoke)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="fail unless batched/sequential >= this at every "
                         "measured point (CI gate; local bar is 3x at 8 "
                         "slots, CI uses margin for runner noise)")
    args = ap.parse_args()
    use_compile_cache()
    if sum((args.sharded, args.recurrent, args.paged_kernel,
            args.kv_format, args.spec_decode)) > 1:
        ap.error("--sharded / --recurrent / --paged-kernel / --kv-format "
                 "/ --spec-decode are mutually exclusive")
    if (args.recurrent or args.paged_kernel or args.kv_format
            or args.spec_decode) and (args.out or args.min_speedup):
        ap.error("--recurrent/--paged-kernel/--kv-format/--spec-decode "
                 "ignore --out/--min-speedup; run the full --smoke to "
                 "record/gate")
    if args.out is None:
        name = "BENCH_serving_sharded.json" if args.sharded \
            else "BENCH_serving.json"
        args.out = str(ROOT / name)
    if args.sharded:
        rows, results = run_sharded(smoke=args.smoke)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}")
        print("name,us_per_call,derived")
        for n, us, d in rows:
            print(f"{n},{us:.1f},{d}")
        return
    if args.recurrent or args.paged_kernel or args.kv_format \
            or args.spec_decode:
        # standalone CI-matrix smokes (exercised on pinned AND latest
        # jax); the full --smoke run is what records these numbers into
        # BENCH_serving.json
        runner = (run_paged if args.paged_kernel else
                  run_kv_formats if args.kv_format else
                  run_spec_decode if args.spec_decode else run_recurrent)
        rows, _ = runner(smoke=args.smoke)
        print("name,us_per_call,derived")
        for n, us, d in rows:
            print(f"{n},{us:.1f},{d}")
        return
    if args.smoke:
        rows, results = run(smoke=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}")
    else:
        rows = run()
        results = None
    print("name,us_per_call,derived")
    for n, us, d in rows:
        print(f"{n},{us:.1f},{d}")
    if args.min_speedup and results:
        # the gate covers batched-vs-sequential decode only; recurrent
        # prefill entries are a recorded trajectory, not a bar
        worst = min(r["speedup"] for r in results.values()
                    if "speedup" in r)
        if worst < args.min_speedup:
            raise SystemExit(f"speedup {worst:.2f}x below the "
                             f"{args.min_speedup}x gate")


if __name__ == "__main__":
    main()
