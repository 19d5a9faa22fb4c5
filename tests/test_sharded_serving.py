"""Mesh-sharded paged serving: the tensor-parallel differential theorem.

The sharded engine (`ServeEngine(mesh_rules=...)`) must be TOKEN-
IDENTICAL to the unsharded engine and to `sequential_generate` — not
approximately equal.  That holds because the serving layout shards
output channels only (column-parallel projections, whole experts per
device, KV pools over KV heads): every norm / quantizer / accumulator
reduction stays device-local, so mesh-on decode produces bit-equal
logits on the qat path and bit-equal integer sums on the sc_int /
sc_int_approx paths (the approximate BSN adder is a per-output-channel
unit — splitting its inputs across chips would change the answer, which
is exactly why no contraction dim is ever sharded).

These tests need a multi-device jax, which must be forced BEFORE jax
initializes: run under ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` (the CI sharded job does; so does the tier-1 subprocess
wrapper ``test_paged_kv.py::test_sharded_serving_subprocess``).  With
fewer devices everything here skips.
"""

import jax
import pytest

from repro.configs import LayerSpec, get_arch
from repro.launch.mesh import make_serving_mesh, serving_rules
from repro.models import init_params
from repro.serving import (SamplingParams, ServeEngine,
                           sequential_generate)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices — set "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

# n_kv_heads=4 so the KV page pools actually shard over the 4-way
# "model" axis (2 data x 4 model = the forced 8 devices)
SCALE = dict(d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
             vocab_size=64, vocab_pad_multiple=32, dtype="float32",
             attn_q_chunk=8)
ATTN_CFG = get_arch("granite-3-2b").scaled(n_layers=2, **SCALE)
MOE_CFG = get_arch("dbrx-132b").scaled(
    n_layers=2, **SCALE, n_experts=4, n_experts_per_tok=2,
    moe_capacity_factor=2.0)
# the hybrid: mamba (d_inner=128 shards 4-way) + attn + MoE in one
# period — the union of everything the chunked prefill has to carry
JAMBA_CFG = get_arch("jamba-1.5-large-398b").scaled(
    n_layers=8, **SCALE, mamba_d_state=8, n_experts=4,
    n_experts_per_tok=2, moe_capacity_factor=2.0)
RWKV_CFG = get_arch("rwkv6-7b").scaled(n_layers=2, **SCALE,
                                       rwkv_head_dim=16)
PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]


def _rules():
    return serving_rules(make_serving_mesh(model_parallel=4,
                                           data_parallel=2))


def _engine_tokens(params, cfg, datapath, rules, max_new=4,
                   sampling=None, prefill_mode="chunked", **kw):
    eng = ServeEngine(params, cfg, max_slots=2, max_len=32, page_size=8,
                      datapath=datapath, mesh_rules=rules,
                      prefill_mode=prefill_mode, **kw)
    sps = sampling or [None] * len(PROMPTS)
    for p, sp in zip(PROMPTS, sps):
        eng.submit(p, max_new_tokens=max_new, sampling=sp)
    done = eng.run_to_completion()
    assert len(done) == len(PROMPTS)
    return [r.generated for r in sorted(done, key=lambda r: r.rid)]


@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
@pytest.mark.parametrize("cfg", [ATTN_CFG, MOE_CFG], ids=["attn", "moe"])
def test_mesh_on_equals_mesh_off_equals_sequential(cfg, datapath):
    """The acceptance differential: sharded == unsharded == oracle,
    token for token, on an attention config and an MoE config across
    all three datapaths."""
    params = init_params(jax.random.key(0), cfg)
    sharded = _engine_tokens(params, cfg, datapath, _rules())
    local = _engine_tokens(params, cfg, datapath, None)
    ref = sequential_generate(params, cfg, PROMPTS, max_new_tokens=4,
                              max_len=32, datapath=datapath)
    assert sharded == local, (cfg.name, datapath)
    assert local == ref, (cfg.name, datapath)


SAMPLED = [SamplingParams(temperature=0.8, top_p=0.9, seed=11 + i)
           for i in range(len(PROMPTS))]


@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
def test_sampled_mesh_on_equals_mesh_off_equals_sequential(datapath):
    """The seeded third of the acceptance differential: nontrivial
    temperature/top-p draws are token-identical across the mesh-sharded
    engine, the unsharded engine, and the sequential oracle on every
    datapath.  Holds because the sampler's PRNG streams are keyed by
    (seed, position) only and the logit/sample tensors are pinned
    replicated before the categorical draw — the mesh can change neither
    the kept set nor the Gumbel bits."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    sharded = _engine_tokens(params, ATTN_CFG, datapath, _rules(),
                             sampling=SAMPLED)
    local = _engine_tokens(params, ATTN_CFG, datapath, None,
                           sampling=SAMPLED)
    ref = sequential_generate(params, ATTN_CFG, PROMPTS,
                              max_new_tokens=4, max_len=32,
                              datapath=datapath, sampling=SAMPLED)
    assert sharded == local == ref, datapath
    greedy = sequential_generate(params, ATTN_CFG, PROMPTS,
                                 max_new_tokens=4, max_len=32,
                                 datapath=datapath)
    assert sharded != greedy, "sampling degenerated to greedy"


@pytest.mark.parametrize("fmt,datapath", [("int8", "qat"),
                                          ("int8", "sc_int"),
                                          ("sc", "sc_int")])
def test_mesh_on_equals_mesh_off_compressed(fmt, datapath):
    """The compressed pools under the mesh: quantize-on-scatter and the
    dequant-fused reference attention are elementwise per (position,
    head), so sharding the KV-head axis changes nothing — mesh-on ==
    mesh-off == same-format sequential oracle, token for token."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    sharded = _engine_tokens(params, ATTN_CFG, datapath, _rules(),
                             kv_format=fmt)
    local = _engine_tokens(params, ATTN_CFG, datapath, None,
                           kv_format=fmt)
    seq = sequential_generate(params, ATTN_CFG, PROMPTS, max_new_tokens=4,
                              max_len=32, datapath=datapath,
                              kv_format=fmt)
    assert sharded == local, (fmt, datapath)
    assert local == seq, (fmt, datapath)


def test_kv_scale_and_residual_pools_shard_with_the_code_pages():
    """The parallel scale / residual pools carry the SAME KV-head "model"
    axis as the code pages (a scale must live with its head's codes, or
    the fused dequant would gather cross-device)."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    eng = ServeEngine(params, ATTN_CFG, max_slots=2, max_len=32,
                      page_size=8, mesh_rules=_rules(), datapath="sc_int",
                      kv_format="sc")
    entry = eng.cache["periods"]["p0"]
    # codes / residuals: (n_periods, num_pages, Hkv, page, Dh)
    assert entry["k_pages"].sharding.spec[2] == "model"
    assert entry["k_resid"].sharding.spec[2] == "model"
    # scales: (n_periods, num_pages, Hkv, page)
    assert entry["k_scale"].sharding.spec[2] == "model"
    assert entry["v_scale"].sharding.spec[2] == "model"


def test_kv_pools_sharded_over_model_axis():
    """The page pools really shard their KV-head axis (weights-resident
    layout), while host bookkeeping stays device-count-agnostic."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    eng = ServeEngine(params, ATTN_CFG, max_slots=2, max_len=32,
                      page_size=8, mesh_rules=_rules())
    kp = eng.cache["periods"]["p0"]["k_pages"]
    # (n_periods, num_pages, Hkv, page, Dh): Hkv carries "model"
    assert kp.sharding.spec[2] == "model"
    wq = eng.params["periods"]["p0"]["mixer"]["wq"]["w"]
    # (n_periods, d_model, hq*dh): column-parallel -> out dim on "model"
    assert wq.sharding.spec[2] == "model"
    # the allocator never saw the mesh
    assert eng.allocator.num_pages == eng.max_slots * eng.max_pages + 1


def test_uneven_heads_degrade_to_replicated():
    """A KV-head count that doesn't divide the model axis must degrade
    that leaf to replicated (fit_spec), not error."""
    cfg = get_arch("granite-3-2b").scaled(
        n_layers=2, **{**SCALE, "n_kv_heads": 2, "n_heads": 4})
    params = init_params(jax.random.key(0), cfg)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=32, page_size=8,
                      mesh_rules=_rules())          # model axis = 4, Hkv = 2
    kp = eng.cache["periods"]["p0"]["k_pages"]
    assert kp.sharding.spec[2] is None
    for p in PROMPTS[:2]:
        eng.submit(p, max_new_tokens=3)
    done = eng.run_to_completion()
    ref = sequential_generate(params, cfg, PROMPTS[:2], max_new_tokens=3,
                              max_len=32)
    assert [r.generated for r in
            sorted(done, key=lambda r: r.rid)] == ref


@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
def test_recurrent_chunked_mesh_on_equals_mesh_off(datapath):
    """The tentpole's mesh third: the jamba hybrid (mamba + attn + MoE)
    prefills through the batched chunked paged path UNDER the mesh —
    the carried chunk state keeps the paged_cache_specs pins (channel
    axes over "model", constrain_tree), so sharded == unsharded ==
    sequential, token for token, on every datapath."""
    params = init_params(jax.random.key(0), JAMBA_CFG)
    sharded = _engine_tokens(params, JAMBA_CFG, datapath, _rules())
    local = _engine_tokens(params, JAMBA_CFG, datapath, None)
    ref = sequential_generate(params, JAMBA_CFG, PROMPTS,
                              max_new_tokens=4, max_len=32,
                              datapath=datapath)
    assert sharded == local, datapath
    assert local == ref, datapath


def test_recurrent_sampled_mesh_on_equals_mesh_off():
    """Seeded stochastic decode over the chunked recurrent prefill,
    mesh-on vs mesh-off vs oracle (rwkv6: tmix + cmix state rows)."""
    params = init_params(jax.random.key(0), RWKV_CFG)
    sharded = _engine_tokens(params, RWKV_CFG, "qat", _rules(),
                             sampling=SAMPLED)
    local = _engine_tokens(params, RWKV_CFG, "qat", None,
                           sampling=SAMPLED)
    ref = sequential_generate(params, RWKV_CFG, PROMPTS,
                              max_new_tokens=4, max_len=32,
                              sampling=SAMPLED)
    greedy = sequential_generate(params, RWKV_CFG, PROMPTS,
                                 max_new_tokens=4, max_len=32)
    assert sharded == local == ref
    assert sharded != greedy, "sampling degenerated to greedy"


def test_recurrent_exact_oracle_sharded_matches_sequential():
    """prefill_mode="exact" (debug oracle): the per-request exact-length
    prefill's eager scatter runs OUTSIDE the jit — under a mesh its
    output must be re-pinned to the init-time cache layout (or the next
    decode step loses donation and copies the whole cache).  Kept on
    the retired path so the oracle stays trustworthy."""
    params = init_params(jax.random.key(0), RWKV_CFG)
    got = _engine_tokens(params, RWKV_CFG, "qat", _rules(),
                         prefill_mode="exact")
    ref = sequential_generate(params, RWKV_CFG, PROMPTS, max_new_tokens=4,
                              max_len=32)
    assert got == ref


@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
def test_kernel_attention_mesh_on_equals_mesh_off(datapath):
    """The paged-attention kernel third: mesh-on decode (which always
    serves the constrained XLA reference — the kernel is a single-device
    program) is token-identical to the mesh-off engine pinned to the
    interpret-mode Pallas kernel.  This is the cross-arithmetic leg of
    the differential: flash-decoding online-softmax vs gathered full
    softmax, same tokens."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    sharded = _engine_tokens(params, ATTN_CFG, datapath, _rules())
    eng = ServeEngine(params, ATTN_CFG, max_slots=2, max_len=32,
                      page_size=8, datapath=datapath,
                      attn_backend="pallas-interpret")
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=4)
    done = eng.run_to_completion()
    kernel = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    assert sharded == kernel, datapath
    ref = sequential_generate(params, ATTN_CFG, PROMPTS, max_new_tokens=4,
                              max_len=32, datapath=datapath)
    assert kernel == ref, datapath


def test_mesh_engine_rejects_pinned_pallas_attention():
    """Pinning a pallas attention backend under mesh rules is a
    contradiction (the kernel is single-device) and must fail loudly,
    not silently serve something else."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    with pytest.raises(ValueError):
        ServeEngine(params, ATTN_CFG, max_slots=2, max_len=32,
                    page_size=8, mesh_rules=_rules(),
                    attn_backend="pallas-interpret")


def test_degenerate_mesh_equals_no_mesh():
    """A (1, 1) mesh is behaviorally identical to mesh_rules=None."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    rules = serving_rules(make_serving_mesh(model_parallel=1,
                                            data_parallel=1))
    assert _engine_tokens(params, ATTN_CFG, "qat", rules) \
        == _engine_tokens(params, ATTN_CFG, "qat", None)


@pytest.mark.parametrize("datapath", ["qat", "sc_int"])
def test_spec_decode_mesh_on_equals_mesh_off(datapath):
    """The speculative fourth of the differential: drafting on
    sc_int_approx and verifying on the sharded target datapath emits
    the same tokens as the mesh-off spec engine AND the mesh-off
    plain engine — the draft scan, the multi-token verify window, and
    the state-snapshot rollback all preserve the layout pins, so GSPMD
    partitioning cannot perturb a single accept/reject decision."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    sharded = _engine_tokens(params, ATTN_CFG, datapath, _rules(),
                             max_new=6, spec_decode=True, draft_len=3)
    local = _engine_tokens(params, ATTN_CFG, datapath, None,
                           max_new=6, spec_decode=True, draft_len=3)
    plain = _engine_tokens(params, ATTN_CFG, datapath, None, max_new=6)
    assert sharded == local == plain, datapath


def test_spec_decode_sampled_mesh_on_equals_mesh_off():
    """Seeded-sampled speculation under the mesh: the shared
    (seed, position) Gumbel streams are replicated-pinned before every
    draw, so the coupled draft/target draws — and hence the accepted
    prefixes — are bit-identical with and without the mesh."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    sharded = _engine_tokens(params, ATTN_CFG, "sc_int", _rules(),
                             max_new=6, sampling=SAMPLED,
                             spec_decode=True, draft_len=3)
    plain = _engine_tokens(params, ATTN_CFG, "sc_int", None,
                           max_new=6, sampling=SAMPLED)
    assert sharded == plain


def test_logprobs_mesh_on_equals_mesh_off():
    """Logprob records (chosen + top-k) are computed from replicated-
    pinned logits, so the mesh changes neither tokens nor scores —
    including through speculative verify steps."""
    params = init_params(jax.random.key(0), ATTN_CFG)
    sps = [SamplingParams(logprobs=2),
           SamplingParams(temperature=0.8, top_p=0.9, seed=11,
                          logprobs=2),
           SamplingParams(logprobs=2)]
    runs = []
    for rules in (_rules(), None):
        eng = ServeEngine(params, ATTN_CFG, max_slots=2, max_len=32,
                          page_size=8, datapath="qat", mesh_rules=rules,
                          spec_decode=True, draft_len=3)
        for p, sp in zip(PROMPTS, sps):
            eng.submit(p, max_new_tokens=5, sampling=sp)
        done = sorted(eng.run_to_completion(), key=lambda r: r.rid)
        runs.append([(r.generated, r.logprobs) for r in done])
    for (g_a, lp_a), (g_b, lp_b) in zip(*runs):
        assert g_a == g_b
        assert len(lp_a) == len(lp_b) == len(g_a)
        for a, b in zip(lp_a, lp_b):
            assert a["logprob"] == pytest.approx(b["logprob"], abs=1e-6)
            assert [t for t, _ in a["top"]] == [t for t, _ in b["top"]]
