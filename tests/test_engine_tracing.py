"""What the engine tells about itself: counters, host spans, scopes.

* ``ServeEngine.stats`` counts what a scripted mix makes the engine do,
  equal to a count by hand of its buckets (prefill ``G x L``, decode
  ``Sb`` lanes) on both prefill paths, with tokens unchanged.
* The ``engine.*`` host spans land in a profiler trace, nested as the
  calls nest, inside a caller's own span, with the request ids of the
  admission as metadata.
* The model's name scopes (``sc_linear``, ``kv_write``, ``paged_attn``,
  ``sampler``, the ``layers`` loop) are in the op metadata of the
  lowered decode program.
"""

import glob
import tempfile

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_arch
from repro.models import init_params
from repro.serving import ServeEngine, sequential_generate
from repro.serving.sampling import SamplingParams, pack_sampling

CFG = get_arch("granite-3-2b").scaled(
    n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
    vocab_size=32, vocab_pad_multiple=32, dtype="float32")
KW = dict(max_slots=4, max_len=32, page_size=4, datapath="sc_int",
          kv_format="int8", attn_backend="pallas-interpret")
# prompt, max_new_tokens
MIX = [([1, 2, 3], 4), ([4, 5, 6, 7, 8], 2), ([9] * 6, 3)]


def _engine(**kw):
    return ServeEngine(init_params(jax.random.key(0), CFG), CFG,
                       **{**KW, **kw})


@pytest.mark.parametrize("prefill_mode", ["chunked", "exact"])
def test_counters_equal_a_count_by_hand(prefill_mode):
    eng = _engine(prefill_mode=prefill_mode)
    for p, n in MIX:
        eng.submit(p, max_new_tokens=n)
    done = eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    ref = [sequential_generate(eng.params, CFG, [p], max_new_tokens=n,
                               max_len=32, datapath="sc_int",
                               kv_format="int8", page_size=4)[0]
           for p, n in MIX]
    assert got == ref
    # step 1 admits all three (one group of 4 lanes by 8 positions, or
    # three exact prefills) and decodes 3 lanes in a bucket of 4; the
    # 2-token request ends there.  Step 2 decodes 2 lanes, step 3 one.
    padded = 4 * 8 if prefill_mode == "chunked" else 3 + 5 + 6
    groups = 1 if prefill_mode == "chunked" else 3
    # pages of 4 held at once after step 1's admission: 1 + 2 + 2
    assert eng.stats == {
        "steps": 3, "decode_steps": 3, "decode_lanes": 3 + 2 + 1,
        "decode_lanes_padded": 4 + 2 + 1, "prefill_groups": groups,
        "prefill_tokens": 3 + 5 + 6, "prefill_tokens_padded": padded,
        "admitted": 3, "preempted": 0, "truncated": 0, "finished": 3,
        "queue_depth_peak": 3, "spec_rounds": 0, "spec_draft_tokens": 0,
        "spec_accepted_tokens": 0, "spec_emitted_tokens": 0,
        "pages_in_use_peak": 5, "pages_total": 4 * 8}


def _host_spans(trace_dir):
    """(name, start, end, metadata as strings) of the bench.* and
    engine.* spans."""
    pd = ProfileData.from_file(glob.glob(
        f"{trace_dir}/**/*.xplane.pb", recursive=True)[0])
    return [(ev.name, ev.start_ns, ev.end_ns,
             {k: str(v) for k, v in ev.stats})
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(("bench.", "engine."))]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_spans_nest_inside_the_callers_span():
    eng = _engine()
    for p, n in MIX:
        eng.submit(p, max_new_tokens=n)
    eng.step()                      # compile outside the trace
    eng.submit([5, 6, 7], max_new_tokens=2)
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    try:
        while eng.queue or any(eng.slots):
            with TraceAnnotation("bench.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tdir)
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert len(by["bench.step"]) == len(by["engine.step"]) >= 2
    for step in by["engine.step"]:
        assert any(_inside(step, b) for b in by["bench.step"])
    for name in ("engine.admit", "engine.grow", "engine.sweep",
                 "engine.decode.prepare", "engine.decode.dispatch",
                 "engine.decode.sync", "engine.decode.commit"):
        assert by[name], name
        for s in by[name]:
            assert any(_inside(s, st) for st in by["engine.step"]), name
    # the admission of request 3, and its prefill, carry its id
    (pre,) = by["engine.prefill"]
    (sync,) = by["engine.prefill.sync"]
    assert pre[3] == sync[3] == {"rids": "3"}
    admits = [a for a in by["engine.admit"] if a[3]]
    assert [a[3] for a in admits] == [{"rids": "3"}]
    assert _inside(pre, admits[0]) and _inside(sync, admits[0])


def test_model_scopes_in_the_lowered_decode_program():
    eng = _engine()
    n, width = 2, 4
    with eng._scope():
        lowered = eng._decode.lower(
            eng.params, eng.cache, jnp.zeros((n,), jnp.int32),
            jnp.full((n,), 4, jnp.int32), jnp.zeros((n, width), jnp.int32),
            jnp.zeros((n,), jnp.int32),
            pack_sampling([SamplingParams()] * n, pad_to=n),
            do_sample=False, lp_k=0)
    text = lowered.as_text(debug_info=True)
    for scope in ("sc_linear/", "kv_write/", "paged_attn/", "sampler",
                  "layers/"):
        assert scope in text, scope
