"""Compressed KV pools + EngineConfig: the format contracts end to end.

Bottom-up, matching the PR's layering:

1. ``core.kv_quant`` round-trip contracts: per-format error bounds, the
   sc residual's pow2 re-scale identity (``alpha_r * 2**SC_SHIFT ==
   alpha_c``, residual never clips), exact-zero round-trips (the trash
   page / unwritten tail must dequantize to 0), format inference from
   pool keys.
2. ``kernels/ref.py``: gather commutes with dequant (bit-exact), and the
   dequant-fused reference equals running the fp reference over
   materialized dequantized pools — bit-exact, so every downstream
   theorem about the fp path transfers to the compressed paths.
3. ``kernels/paged_attention.py``: the fused-dequant Pallas kernels
   (interpret mode) match the reference within the same float tolerance
   as the fp kernels, decode and prefill, int8 and sc.
4. Accuracy vs fp: the attention output of a compressed cache stays
   within the softmax-Lipschitz bound derived from the per-value
   round-trip bounds.
5. ``EngineConfig``: every ``validate()`` rule raises (parametrized over
   the full rule list), ``from_config`` == the kwargs shim token for
   token, and the engine rejects invalid configs through both paths.
6. The serving differential: batched engine(kv_format=X) == B=1 paged
   sequential oracle, BIT-exact within each format — int8 under qat,
   sc under sc_int.
7. Capacity accounting: ``kv_page_bytes`` / ``slots_per_gib`` per
   format, including the acceptance gate int8 >= 2x fp slots at the
   bench shape and unchanged page_size.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.kv_quant import (INT8_BSL, KV_FORMATS, SC_COARSE_BSL,
                                 SC_SHIFT, check_kv_format, kv_dequant,
                                 kv_error_bound, kv_format_of, kv_quant)
from repro.core.residual import pow2_exponent
from repro.kernels import dispatch, ref
from repro.kernels.paged_attention import (paged_attn_decode_pallas,
                                           paged_attn_prefill_pallas)
from repro.models import init_params
from repro.serving import (EngineConfig, ServeEngine, kv_page_bytes,
                           sequential_generate, slots_per_gib)
from repro.serving.paging import pages_needed

COMPRESSED = [f for f in KV_FORMATS if f != "fp"]


def _rand(seed, shape, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


# ---------------------------------------------------------------------------
# 1. core round-trip contracts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", COMPRESSED)
def test_roundtrip_within_error_bound(fmt):
    x = _rand(0, (5, 7, 3, 16), scale=2.5)
    qd = kv_quant(x, fmt)
    back = kv_dequant(qd["q"], qd.get("scale"), qd.get("resid"), fmt=fmt)
    bound = kv_error_bound(qd["scale"], fmt)[..., None]
    err = jnp.abs(back - x)
    assert np.all(np.asarray(err) <= np.asarray(bound) * (1 + 1e-6)), \
        float(jnp.max(err - bound))


def test_fp_roundtrip_is_identity():
    x = _rand(1, (3, 4, 8))
    qd = kv_quant(x, "fp")
    assert qd.keys() == {"q"}
    np.testing.assert_array_equal(np.asarray(kv_dequant(qd["q"], fmt="fp")),
                                  np.asarray(x))
    assert float(jnp.max(kv_error_bound(jnp.ones((3,)), "fp"))) == 0.0


def test_sc_residual_pow2_contract():
    """The residual scale is EXACTLY alpha_c * 2**-SC_SHIFT (the pow2
    re-scaling block's contract), and the residual never clips: the
    coarse quantizer leaves |r| <= alpha_c/2 == (BSL/2) * alpha_r."""
    x = _rand(2, (4, 6, 2, 16), scale=3.0)
    qd = kv_quant(x, "sc")
    alpha_c = np.asarray(qd["scale"])
    alpha_r = alpha_c * 2.0 ** -SC_SHIFT
    # every (position, head) scale pair sits at the exact pow2 ratio
    exps = {pow2_exponent(ar, ac)
            for ar, ac in zip(alpha_r.ravel(), alpha_c.ravel())}
    assert exps == {SC_SHIFT}
    # residual levels use the full +-BSL/2 range but never exceed it
    resid = np.asarray(qd["resid"])
    assert np.abs(resid).max() <= SC_COARSE_BSL // 2
    r = np.asarray(x) - alpha_c[..., None] * np.asarray(qd["q"],
                                                        np.float32)
    assert np.all(np.abs(r) <= alpha_c[..., None] / 2 * (1 + 1e-6))


@pytest.mark.parametrize("fmt", KV_FORMATS)
def test_zero_roundtrips_exactly(fmt):
    """All-zero vectors (trash page, unwritten positions) must quantize
    to all-zero codes AND scales and dequantize back to exact 0 — this
    is what makes zero-initialized compressed pools safe."""
    x = jnp.zeros((2, 4, 3, 8), jnp.float32)
    qd = kv_quant(x, fmt)
    assert float(jnp.max(jnp.abs(qd["q"].astype(jnp.float32)))) == 0.0
    back = kv_dequant(qd["q"], qd.get("scale"), qd.get("resid"), fmt=fmt)
    np.testing.assert_array_equal(np.asarray(back), np.zeros_like(x))
    # and the pool-initialization path: zero codes + zero scales
    if fmt != "fp":
        z = kv_dequant(jnp.zeros((4, 8), jnp.int8), jnp.zeros((4,)),
                       jnp.zeros((4, 8), jnp.int8) if fmt == "sc" else None,
                       fmt=fmt)
        np.testing.assert_array_equal(np.asarray(z), np.zeros((4, 8)))


def test_format_inference_and_checks():
    assert kv_format_of({"k_pages": 0}) == "fp"
    assert kv_format_of({"k_pages": 0, "k_scale": 0}) == "int8"
    assert kv_format_of({"k_pages": 0, "k_scale": 0, "k_resid": 0}) == "sc"
    for fmt in KV_FORMATS:
        assert check_kv_format(fmt) == fmt
    with pytest.raises(ValueError, match="kv_format"):
        check_kv_format("fp16")
    with pytest.raises(ValueError):
        kv_quant(jnp.zeros((2, 4)), "nf4")


# ---------------------------------------------------------------------------
# 2. reference layer: dequant commutes with gather
# ---------------------------------------------------------------------------

def _pools(seed, S, Hkv, D, page, maxp, fmt):
    """Quantized pools + tables, allocator-style (page 0 = trash)."""
    rng = np.random.default_rng(seed)
    n = S * maxp + 1
    kf = jnp.asarray(rng.standard_normal((n, Hkv, page, D)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((n, Hkv, page, D)), jnp.float32)
    kq, vq = kv_quant(kf, fmt), kv_quant(vf, fmt)
    aux = {}
    if fmt != "fp":
        aux = {"k_scale": kq["scale"], "v_scale": vq["scale"]}
        if fmt == "sc":
            aux |= {"k_resid": kq["resid"], "v_resid": vq["resid"]}
    tables = np.zeros((S, maxp), np.int32)
    for s in range(S):
        tables[s] = 1 + s * maxp + rng.permutation(maxp)
    return rng, kq["q"], vq["q"], jnp.asarray(tables), aux


def _dequant_pool(pages, aux, side, fmt):
    return kv_dequant(pages, aux.get(f"{side}_scale"),
                      aux.get(f"{side}_resid"), fmt=fmt)


@pytest.mark.parametrize("fmt", COMPRESSED)
def test_gather_dequant_commutes(fmt):
    _, kp, _, tables, aux = _pools(3, 3, 2, 16, 8, 4, fmt)
    fused = ref.gather_pages_dequant(kp, tables, kv_format=fmt,
                                     scale=aux["k_scale"],
                                     resid=aux.get("k_resid"))
    first = ref.gather_pages(_dequant_pool(kp, aux, "k", fmt), tables)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(first))


@pytest.mark.parametrize("fmt", COMPRESSED)
def test_ref_fused_dequant_bitexact_decode(fmt):
    """The in-gather dequant is BIT-identical to materializing fp pools
    and running the fp reference — the fp differential theorems transfer
    wholesale to the compressed formats."""
    rng, kp, vp, tables, aux = _pools(5, 3, 2, 16, 8, 4, fmt)
    q = jnp.asarray(rng.standard_normal((3, 2, 2, 16)), jnp.float32)
    lengths = jnp.asarray([5, 17, 31], jnp.int32)
    fused = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths,
                                      kv_format=fmt, kv_aux=aux)
    first = ref.paged_attn_decode_ref(q, _dequant_pool(kp, aux, "k", fmt),
                                      _dequant_pool(vp, aux, "v", fmt),
                                      tables, lengths)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(first))


@pytest.mark.parametrize("fmt", COMPRESSED)
def test_ref_fused_dequant_bitexact_prefill(fmt):
    rng, kp, vp, tables, aux = _pools(7, 2, 2, 16, 8, 5, fmt)
    q = jnp.asarray(rng.standard_normal((2, 16, 2, 2, 16)), jnp.float32)
    fused = ref.paged_attn_prefill_ref(q, kp, vp, tables, 16,
                                       kv_format=fmt, kv_aux=aux)
    first = ref.paged_attn_prefill_ref(q, _dequant_pool(kp, aux, "k", fmt),
                                       _dequant_pool(vp, aux, "v", fmt),
                                       tables, 16)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(first))


# ---------------------------------------------------------------------------
# 3. fused-dequant Pallas kernels vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", COMPRESSED)
@pytest.mark.parametrize("num_splits", [1, 2])
def test_decode_kernel_vs_reference_compressed(fmt, num_splits):
    S, Hkv, G, D, page, maxp = 3, 2, 2, 16, 8, 4
    rng, kp, vp, tables, aux = _pools(S * D, S, Hkv, D, page, maxp, fmt)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(0, maxp * page, S), jnp.int32)
    got = paged_attn_decode_pallas(q, kp, vp, tables, lengths,
                                   num_splits=num_splits, interpret=True,
                                   kv_format=fmt, **aux)
    want = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths,
                                     kv_format=fmt, kv_aux=aux)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("fmt", COMPRESSED)
@pytest.mark.parametrize("block_q", [8, 5])
def test_prefill_kernel_vs_reference_compressed(fmt, block_q):
    G, C, Hkv, Gq, D, page, start = 2, 16, 2, 2, 16, 8, 16
    maxp = (start + C) // page + 1
    rng, kp, vp, tables, aux = _pools(G * C, G, Hkv, D, page, maxp, fmt)
    q = jnp.asarray(rng.standard_normal((G, C, Hkv, Gq, D)), jnp.float32)
    got = paged_attn_prefill_pallas(q, kp, vp, tables, start=start,
                                    block_q=block_q, interpret=True,
                                    kv_format=fmt, **aux)
    want = ref.paged_attn_prefill_ref(q, kp, vp, tables, start,
                                      kv_format=fmt, kv_aux=aux)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("fmt", COMPRESSED)
def test_dispatch_threads_kv_aux(fmt):
    """dispatch.paged_attn_decode forwards kv_format/kv_aux to both
    backends; kernel path == its own direct call, bit for bit."""
    S, Hkv, G, D, page, maxp = 3, 2, 2, 16, 8, 3
    rng, kp, vp, tables, aux = _pools(23, S, Hkv, D, page, maxp, fmt)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray([3, 11, 20], jnp.int32)
    via = dispatch.paged_attn_decode(q, kp, vp, tables, lengths,
                                     backend="pallas-interpret",
                                     kv_format=fmt, kv_aux=aux)
    direct = paged_attn_decode_pallas(q, kp, vp, tables, lengths,
                                      interpret=True, kv_format=fmt, **aux)
    np.testing.assert_array_equal(np.asarray(via), np.asarray(direct))


# ---------------------------------------------------------------------------
# 4. accuracy vs fp: the softmax-Lipschitz bound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", COMPRESSED)
def test_attention_output_within_lipschitz_bound(fmt):
    """|out_fmt - out_fp| <= eps_v + vmax * (e^{2d} - 1) with
    d = ||q||_1 * max(eps_k) / sqrt(D): perturbing every key by at most
    eps_k moves each logit by at most ||q||_1 * eps_k / sqrt(D), the
    softmax weights by a factor in [e^{-2d}, e^{2d}], and the convex
    V-combination by at most vmax * (e^{2d} - 1); the value round-trip
    adds eps_v directly."""
    S, Hkv, G, D, page, maxp = 2, 2, 2, 16, 8, 3
    rng = np.random.default_rng(31)
    n = S * maxp + 1
    kf = jnp.asarray(rng.standard_normal((n, Hkv, page, D)) * 0.5,
                     jnp.float32)
    vf = jnp.asarray(rng.standard_normal((n, Hkv, page, D)) * 0.5,
                     jnp.float32)
    tables = np.zeros((S, maxp), np.int32)
    for s in range(S):
        tables[s] = 1 + s * maxp + rng.permutation(maxp)
    tables = jnp.asarray(tables)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)) * 0.5, jnp.float32)
    lengths = jnp.asarray([11, 23], jnp.int32)

    out_fp = ref.paged_attn_decode_ref(q, kf, vf, tables, lengths)
    kq, vq = kv_quant(kf, fmt), kv_quant(vf, fmt)
    aux = {"k_scale": kq["scale"], "v_scale": vq["scale"]}
    if fmt == "sc":
        aux |= {"k_resid": kq["resid"], "v_resid": vq["resid"]}
    out_q = ref.paged_attn_decode_ref(q, kq["q"], vq["q"], tables,
                                      lengths, kv_format=fmt, kv_aux=aux)

    eps_k = float(jnp.max(kv_error_bound(kq["scale"], fmt)))
    eps_v = float(jnp.max(kv_error_bound(vq["scale"], fmt)))
    vmax = float(jnp.max(jnp.abs(vf)))
    q1 = float(jnp.max(jnp.sum(jnp.abs(q), axis=-1)))
    d = q1 * eps_k / math.sqrt(D)
    bound = eps_v + vmax * (math.exp(2 * d) - 1)
    diff = float(jnp.max(jnp.abs(out_q - out_fp)))
    assert diff <= bound, (diff, bound)
    # the bound is meaningfully tight: the sc path (8 extra code bits)
    # must beat int8's worst case
    if fmt == "sc":
        assert eps_k < 2.0 / INT8_BSL


# ---------------------------------------------------------------------------
# 5. EngineConfig: the single construction path
# ---------------------------------------------------------------------------

SCALE = dict(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
             vocab_pad_multiple=32, dtype="float32", attn_q_chunk=8)
CFG = get_arch("granite-3-2b").scaled(n_layers=2, **SCALE)
PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]


def test_engine_config_defaults_validate():
    c = EngineConfig()
    assert c.validate() is c
    assert c.kv_format == "fp" and c.datapath == "qat"


@pytest.mark.parametrize("changes,match", [
    (dict(max_slots=0), "max_slots"),
    (dict(max_len=1), "max_len"),
    (dict(page_size=7), "power of two"),
    (dict(page_size=0), "power of two"),
    (dict(num_pages=1), "trash page"),
    (dict(prefill_chunk=0), "prefill_chunk"),
    (dict(datapath="fp8"), "datapath"),
    (dict(kv_format="nf4"), "kv_format"),
    (dict(kv_format="sc", datapath="qat"), "SC"),
    (dict(bsn_backend="verilog"), "bsn_backend"),
    (dict(attn_backend="verilog"), "attn_backend"),
    (dict(prefill_mode="streaming"), "prefill_mode"),
])
def test_engine_config_rejects(changes, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**changes).validate()


def test_engine_config_mesh_needs_reference_attention():
    from repro.launch.mesh import make_serving_mesh, serving_rules
    rules = serving_rules(make_serving_mesh(model_parallel=1,
                                            data_parallel=1))
    EngineConfig(mesh_rules=rules).validate()                 # auto: fine
    EngineConfig(mesh_rules=rules,
                 attn_backend="reference").validate()         # pinned ref
    with pytest.raises(ValueError, match="mesh"):
        EngineConfig(mesh_rules=rules,
                     attn_backend="pallas-interpret").validate()


def test_engine_config_replace():
    c = EngineConfig().replace(kv_format="int8", page_size=8)
    assert (c.kv_format, c.page_size) == ("int8", 8)
    assert EngineConfig().kv_format == "fp"                   # frozen


def test_engine_validates_through_both_paths():
    params = init_params(jax.random.key(0), CFG)
    with pytest.raises(ValueError, match="power of two"):
        ServeEngine(params, CFG, page_size=7)
    with pytest.raises(ValueError, match="kv_format"):
        ServeEngine(params, CFG, kv_format="nf4")
    with pytest.raises(ValueError, match="SC"):
        ServeEngine.from_config(params, CFG,
                                EngineConfig(kv_format="sc"))


def test_from_config_equals_kwarg_shim():
    """The kwargs shim and from_config are the same engine: identical
    tokens and identical resolved EngineConfig."""
    params = init_params(jax.random.key(0), CFG)

    def run(eng):
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=4)
        done = eng.run_to_completion()
        return [r.generated for r in sorted(done, key=lambda r: r.rid)]

    kw = dict(max_slots=2, max_len=32, page_size=8, kv_format="int8")
    a = ServeEngine(params, CFG, **kw)
    b = ServeEngine.from_config(params, CFG, EngineConfig(**kw))
    assert a.config == b.config
    assert run(a) == run(b)


def test_submit_rejects_nonpositive_max_new_tokens():
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=8)
    for bad in (0, -3):
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2], max_new_tokens=bad)
    eng.submit([1, 2], max_new_tokens=1)                      # boundary ok


# ---------------------------------------------------------------------------
# 6. the serving differential per format
# ---------------------------------------------------------------------------

def _engine_tokens(params, config, max_new=5):
    eng = ServeEngine.from_config(params, CFG, config)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=max_new)
    done = eng.run_to_completion()
    assert len(done) == len(PROMPTS)
    return [r.generated for r in sorted(done, key=lambda r: r.rid)]


@pytest.mark.parametrize("fmt,datapath", [("int8", "qat"),
                                          ("int8", "sc_int"),
                                          ("sc", "sc_int")])
def test_engine_batched_equals_sequential_compressed(fmt, datapath):
    """The acceptance differential for the compressed pools: the batched
    continuous-batching engine produces EXACTLY the tokens of the B=1
    paged sequential oracle in the same format (per-position scales make
    quantization order-independent), at a DIFFERENT oracle page size —
    the codes are page-layout-invariant."""
    params = init_params(jax.random.key(0), CFG)
    got = _engine_tokens(params, EngineConfig(
        max_slots=2, max_len=64, page_size=16, prefill_chunk=8,
        datapath=datapath, kv_format=fmt))
    want = sequential_generate(params, CFG, PROMPTS, max_new_tokens=5,
                               max_len=64, datapath=datapath,
                               kv_format=fmt, page_size=8)
    assert got == want, (fmt, datapath)


def test_compressed_formats_actually_change_tokens():
    """Sanity that the differential above isn't vacuous: at this tiny
    scale the int8 cache round-trip perturbs logits enough to move some
    argmax — if all formats agreed everywhere, the format tests would
    not be exercising distinct numerics."""
    params = init_params(jax.random.key(0), CFG)
    fp = sequential_generate(params, CFG, PROMPTS, max_new_tokens=5,
                             max_len=64)
    i8 = sequential_generate(params, CFG, PROMPTS, max_new_tokens=5,
                             max_len=64, kv_format="int8")
    assert fp != i8


# ---------------------------------------------------------------------------
# 7. capacity accounting
# ---------------------------------------------------------------------------

def test_kv_page_bytes_per_format():
    # bench shape: page=16, Hkv=2, Dh=16, f32
    assert kv_page_bytes(16, 2, 16, "fp") == 4096
    assert kv_page_bytes(16, 2, 16, "int8") == 1280
    assert kv_page_bytes(16, 2, 16, "sc") == 2304
    with pytest.raises(ValueError):
        kv_page_bytes(16, 2, 16, "nf4")


def test_int8_at_least_doubles_slots_per_gib():
    """The acceptance gate: >= 2x full-length request slots per GiB for
    int8 vs fp at unchanged page_size."""
    args = (256, 16, 2, 16)
    ratio = slots_per_gib(*args, "int8") / slots_per_gib(*args, "fp")
    assert ratio >= 2.0, ratio
    # sc trades some of that back for the residual pool but still wins
    assert slots_per_gib(*args, "sc") > slots_per_gib(*args, "fp")


def test_slots_per_gib_accounting():
    got = slots_per_gib(256, 16, 2, 16, "fp", n_layers=2)
    want = (1 << 30) / (pages_needed(256, 16) * 4096 * 2)
    assert got == pytest.approx(want)
