"""Drive ``ServeEngine`` through its public API and log what happens.

Warm-up compiles every program the cell's traffic can reach, without
running it; the window then offers the traffic on its
schedule (an open loop: a request is submitted once it is due, whatever
the engine is doing) and steps the engine, recording per request its due,
submit, admission and token times, and per ``step()`` its host start and
end, the prompt lengths it admitted and the cache lengths of the lanes
it decoded.  Host spans (``jax.profiler.TraceAnnotation``) mark every
submit, step and idle wait, so a trace can say what the host was doing
in each gap of the device.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation
from repro.serving.paging import pad_pow2, pages_needed

__all__ = ["ReqLog", "StepLog", "RunLog", "prefill_bucket", "decode_bucket",
           "warm_plan", "warm", "run_window"]


@dataclass
class ReqLog:
    due: float
    prompt: list
    max_new: int
    submit: float | None = None
    admit: float | None = None           # start of the admitting step
    token_times: list = field(default_factory=list)
    done: float | None = None
    served: list | None = None           # the tokens, once finished


@dataclass
class StepLog:
    t0: float
    t1: float
    admitted: list = field(default_factory=list)     # prompt lengths
    decode_lens: list = field(default_factory=list)  # cached tokens/lane


@dataclass
class RunLog:
    """Everything a metric reads.  Times are seconds from the window's
    start; ``trace`` is bench/trace.py's summary of a traced run."""
    seconds: float
    setup_s: float = 0.0
    reqs: dict = field(default_factory=dict)
    steps: list = field(default_factory=list)
    failed: int = 0
    window_compiles: int = 0
    max_slots: int = 0
    page_size: int = 16
    chunk: int = 64
    kv_format: str = "int8"
    dims: object = None                  # the family's Dims
    family: object = None                # its module (bench/families)
    peaks: dict = field(default_factory=dict)
    trace: dict | None = None


def prefill_bucket(plens, page: int, max_slots: int, chunk: int):
    """(group, padded length, table width, chunk) of the engine's
    prefill of one admission group with prompt lengths ``plens``
    (``ServeEngine._prefill_group``; each lane's table holds its prompt
    and the first decode write).  The benchmark's one copy of the
    engine's prefill buckets, built on the engine's ``pad_pow2``."""
    L = pad_pow2(max(plens), lo=page)
    pages = pages_needed(max(plens) + 1, page)
    return (pad_pow2(len(plens), hi=max_slots), L,
            pad_pow2(max(L // page, pages)),
            min(pad_pow2(max(chunk, page)), L))


def decode_bucket(lens, page: int, max_slots: int):
    """(lanes, table width) of the engine's decode step over lanes with
    ``lens`` tokens cached (``ServeEngine._step_batch``; a lane with n
    cached holds the pages of n + 1 tokens)."""
    return (pad_pow2(len(lens), hi=max_slots),
            pad_pow2(max(pages_needed(n + 1, page) for n in lens)))


def warm_plan(reqs, max_slots: int, page: int, chunk: int):
    """Every program the traffic can reach: prefill programs keyed by
    ``prefill_bucket`` for every group size and prompt length (a group's
    bucket is its longest prompt's), decode programs by
    ``decode_bucket`` for every lane count and cached length."""
    pl = sorted({len(r.prompt) for r in reqs})
    outs = [r.max_new_tokens for r in reqs]
    groups = sorted({pad_pow2(g, hi=max_slots)
                     for g in range(1, max_slots + 1)})
    prefill = sorted({prefill_bucket([p] * g, page, max_slots, chunk)
                      for g in groups for p in pl})
    # a backlog keeps every slot busy: decode always runs max_slots lanes
    backlog = all(r.due_s == 0 for r in reqs) and len(reqs) > max_slots \
        and min(outs) >= 2
    lanes = [max_slots] if backlog else groups
    # a lane decodes with n tokens cached, n running from its prompt to
    # prompt + output - 2; table widths are monotone in n
    lo = decode_bucket([pl[0]], page, max_slots)[1]
    hi = decode_bucket([pl[-1] + max(outs) - 2], page, max_slots)[1]
    widths = [1 << i for i in range(lo.bit_length() - 1, hi.bit_length())]
    return {"prefill": prefill,
            "decode": [(n, m) for n in lanes for m in widths]}


def warm(eng, plan, threads: int = 8) -> int:
    """Compile (or load from the persistent cache) every program in the
    plan without running it: each is lowered with the arguments the
    engine's own step builds for that bucket, which puts it in the
    jit's cache for the window.  Running each bucket instead would cost
    minutes of device time.  Lowering runs here; compiles run on
    ``threads`` threads.  Returns the number of programs."""
    from concurrent.futures import ThreadPoolExecutor

    import jax.numpy as jnp
    from repro.serving.sampling import SamplingParams, pack_sampling

    ec = eng.config
    trash, scratch = 0, ec.max_slots
    lowered = []

    def lanes(n, fill):
        return jnp.full((n,), fill, jnp.int32)

    def samp(n):
        return pack_sampling([SamplingParams()] * n, pad_to=n)
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
        for f in [pool.submit(lo.compile) for lo in lowered]:
            f.result()
    # the engine reads each admitted lane's first token as ``int(nxt[g])``:
    # one small indexing program per group size
    import jax
    for g in sorted({g for g, _, _, _ in plan["prefill"]}):
        int(jax.jit(lambda n=g: jnp.zeros((n,), jnp.int32))()[0])
    return len(lowered)


def run_window(eng, reqs, seconds: float, log: RunLog,
               clock=time.perf_counter) -> RunLog:
    live = {}                       # rid -> (engine Request, ReqLog)
    i, n = 0, len(reqs)
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if i < n and reqs[i].due_s <= now:
            with TraceAnnotation("bench.submit"):
                while i < n and reqs[i].due_s <= now:
                    r = reqs[i]
                    rl = ReqLog(r.due_s, r.prompt, r.max_new_tokens)
                    try:
                        rid = eng.submit(r.prompt,
                                         max_new_tokens=r.max_new_tokens)
                    except ValueError:
                        # refused: it never gets a token, so it counts as
                        # missing every latency limit
                        log.failed += 1
                        log.reqs[("refused", i)] = rl
                    else:
                        rl.submit = clock() - t0
                        live[rid] = (eng.queue[-1], rl)
                        log.reqs[rid] = rl
                    i += 1
        if eng.queue or any(s is not None for s in eng.slots):
            before = {rid: len(r.generated) for rid, (r, _) in live.items()}
            ts = clock() - t0
            with TraceAnnotation("bench.step"):
                done = eng.step()
            te = clock() - t0
            st = StepLog(ts, te)
            for rid, (r, rl) in live.items():
                g0, g1 = before[rid], len(r.generated)
                if g1 == g0:
                    continue
                if g0 == 0:
                    rl.admit = ts
                    st.admitted.append(len(r.prompt))
                if g1 - g0 - (g0 == 0) > 0:
                    st.decode_lens.append(len(r.prompt) + g1 - 2)
                rl.token_times += [te] * (g1 - g0)
            for r in done:
                _, rl = live.pop(r.rid)
                rl.done, rl.served = te, list(r.generated)
            log.steps.append(st)
        else:
            nxt = reqs[i].due_s if i < n else seconds
            wait = min(nxt, seconds) - (clock() - t0)
            if wait > 0:
                with TraceAnnotation("bench.wait"):
                    time.sleep(wait)
    return log
