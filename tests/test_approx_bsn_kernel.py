"""Three-way differential harness for the approximate BSN.

The chain proven here, per spec:

    approx_bsn_bits (circuit)  ==  approx_bsn_counts (oracle)
                               ==  fused Pallas kernel (interpret mode)

plus the dispatch layer's selection policy, the temporal-reuse kernel
against the chunked reference, the sc_layers integration, and the
paper_tnn spatial-temporal chunking regression.  Randomized specs come
from hypothesis (or the deterministic conftest fallback); degenerate
specs (no clip, stride 1, single stage) are pinned explicitly.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import coding
from repro.core.bsn import (ApproxBSNSpec, StageSpec, SubSampleSpec,
                            approx_bsn, approx_bsn_bits, approx_bsn_counts,
                            default_approx_spec, spatial_temporal_counts)
from repro.kernels import dispatch
from repro.kernels.approx_bsn import (approx_bsn_pallas,
                                      approx_bsn_temporal_pallas,
                                      validate_stages)

KERNEL = "pallas-interpret"       # compiled semantics, runs on CPU


# ---------------------------------------------------------------------------
# spec generation
# ---------------------------------------------------------------------------

def _random_spec(rng: np.random.Generator) -> ApproxBSNSpec:
    """A random VALID spec: 1-3 stages, pow2 groups/strides, legal clips."""
    n_stages = int(rng.integers(1, 4))
    groups = [int(2 ** rng.integers(1, 3)) for _ in range(n_stages)]
    in_bsl = int(2 ** rng.integers(1, 4))             # 2, 4, 8
    bsl, stages = in_bsl, []
    for g in groups:
        sorted_len = bsl * g
        # 1, 2, 4, never past the sorted length (a stage keeps >= 1 bit)
        stride = min(int(2 ** rng.integers(0, 3)), sorted_len)
        max_out = sorted_len // stride
        out_bsl = int(rng.integers(1, max_out + 1))
        if (sorted_len - out_bsl * stride) % 2:       # clip must be symmetric
            out_bsl += -1 if out_bsl > 1 else 1
        kept = out_bsl * stride
        stages.append(StageSpec(g, SubSampleSpec((sorted_len - kept) // 2,
                                                 stride)))
        bsl = out_bsl
    return ApproxBSNSpec(width=math.prod(groups), in_bsl=in_bsl,
                         stages=tuple(stages))


def _three_way(spec: ApproxBSNSpec, seed: int, rows: int = 3):
    key = jax.random.key(seed)
    half = spec.in_bsl // 2
    levels = jax.random.randint(key, (rows, spec.width), -half, half + 1)
    bits = coding.encode_thermometer(levels, spec.in_bsl)
    counts = coding.counts_from_bits(bits)

    from_bits = coding.counts_from_bits(approx_bsn_bits(bits, spec))
    from_counts = approx_bsn_counts(counts, spec)
    from_kernel = dispatch.approx_bsn(counts, spec, backend=KERNEL,
                                      min_rows_for_kernel=0)
    return (np.asarray(from_bits), np.asarray(from_counts),
            np.asarray(from_kernel))


# ---------------------------------------------------------------------------
# three-way differential: randomized + degenerate specs
# ---------------------------------------------------------------------------

@given(st.integers(0, 10 ** 6))
@settings(max_examples=10, deadline=None)
def test_three_way_random_specs(seed):
    rng = np.random.default_rng(seed)
    spec = _random_spec(rng)
    b, c, k = _three_way(spec, seed)
    np.testing.assert_array_equal(b, c)
    np.testing.assert_array_equal(c, k)


DEGENERATE = [
    # no clip, stride 1, single stage: the exact adder
    ApproxBSNSpec(8, 4, (StageSpec(8, SubSampleSpec(0, 1)),)),
    # single stage, clip only
    ApproxBSNSpec(8, 4, (StageSpec(8, SubSampleSpec(4, 1)),)),
    # single stage, stride only
    ApproxBSNSpec(8, 4, (StageSpec(8, SubSampleSpec(0, 4)),)),
    # multi-stage, all degenerate sub-samplers
    ApproxBSNSpec(16, 2, (StageSpec(4, SubSampleSpec(0, 1)),
                          StageSpec(4, SubSampleSpec(0, 1)))),
    # group=1 stages are legal plumbing (sort of a single code)
    ApproxBSNSpec(4, 4, (StageSpec(1, SubSampleSpec(1, 1)),
                         StageSpec(4, SubSampleSpec(0, 2)))),
]


@pytest.mark.parametrize("spec", DEGENERATE, ids=lambda s: str(s.stages))
def test_three_way_degenerate_specs(spec):
    b, c, k = _three_way(spec, seed=7, rows=4)
    np.testing.assert_array_equal(b, c)
    np.testing.assert_array_equal(c, k)


def test_fully_degenerate_is_exact_sum():
    spec = DEGENERATE[0]
    rng = np.random.default_rng(0)
    counts = jnp.asarray(rng.integers(0, spec.in_bsl + 1, (16, spec.width)))
    out = dispatch.approx_bsn(counts, spec, backend=KERNEL,
                              min_rows_for_kernel=0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(counts.sum(-1)))


# ---------------------------------------------------------------------------
# temporal-reuse kernel vs chunked reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cycles", [2, 4, 9])
def test_temporal_kernel_matches_reference(cycles):
    spec = ApproxBSNSpec(8, 4, (StageSpec(8, SubSampleSpec(clip=2,
                                                           stride=2)),))
    rng = np.random.default_rng(cycles)
    counts = jnp.asarray(
        rng.integers(0, spec.in_bsl + 1, (12, cycles * spec.width)),
        jnp.int32)
    got = dispatch.approx_bsn(counts, spec, cycles=cycles, backend=KERNEL,
                              min_rows_for_kernel=0)
    ref = spatial_temporal_counts(counts, spec, cycles)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_temporal_kernel_raw_grid_accumulation():
    """Raw kernel call (no dispatch): grid-revisited accumulation."""
    spec = ApproxBSNSpec(4, 2, (StageSpec(4, SubSampleSpec(0, 2)),))
    rng = np.random.default_rng(1)
    counts = jnp.asarray(rng.integers(0, 3, (8, 6 * 4)), jnp.int32)
    got = approx_bsn_temporal_pallas(
        counts, in_bsl=2, stages=dispatch.spec_stages(spec), cycles=6,
        block_r=8, interpret=True)
    ref = spatial_temporal_counts(counts, spec, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

def test_select_backend_policy(monkeypatch):
    # explicit argument always wins
    assert dispatch.select_backend(1, backend="pallas") == "pallas"
    # auto off-TPU: kernel for big row counts, reference for tiny
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert dispatch.select_backend(64) == "pallas-interpret"
    assert dispatch.select_backend(2) == "reference"
    # auto on TPU: compiled kernel for kernel-worthy row counts, but the
    # row threshold holds there too — a tiny pallas_call is all overhead
    # (regression: this used to return "pallas" unconditionally)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dispatch.select_backend(64) == "pallas"
    assert dispatch.select_backend(2) == "reference"
    assert dispatch.select_backend(2, min_rows_for_kernel=1) == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    # scope override + restoration
    with dispatch.backend_scope("reference"):
        assert dispatch.select_backend(64) == "reference"
        with dispatch.backend_scope(None):      # None scope is a no-op
            assert dispatch.select_backend(64) == "reference"
    assert dispatch.select_backend(64) == "pallas-interpret"
    with pytest.raises(ValueError):
        dispatch.select_backend(1, backend="verilog")
    with pytest.raises(ValueError):
        dispatch.set_default_backend("verilog")


def test_dispatch_batched_and_1d_shapes():
    spec = default_approx_spec(16, 4)
    rng = np.random.default_rng(2)
    c3 = jnp.asarray(rng.integers(0, 5, (2, 5, 16)), jnp.int32)
    got = dispatch.approx_bsn(c3, spec, backend=KERNEL,
                              min_rows_for_kernel=0)
    assert got.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(approx_bsn_counts(c3, spec)))
    c1 = c3[0, 0]
    got1 = dispatch.approx_bsn(c1, spec, backend=KERNEL,
                               min_rows_for_kernel=0)
    assert got1.shape == ()
    assert int(got1) == int(approx_bsn_counts(c1, spec))


def test_core_front_door_routes_to_kernel():
    """core.bsn.approx_bsn is the same computation via dispatch."""
    spec = default_approx_spec(32, 2)
    rng = np.random.default_rng(3)
    c = jnp.asarray(rng.integers(0, 3, (16, 32)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(approx_bsn(c, spec, backend=KERNEL)),
        np.asarray(approx_bsn_counts(c, spec)))


def test_kernel_saturates_out_of_range_like_oracle():
    """Even with clip=0 the oracle saturates counts into [0, kept]; the
    kernel must clamp identically or backends diverge on garbage input."""
    spec = ApproxBSNSpec(8, 4, (StageSpec(8, SubSampleSpec(0, 2)),))
    bad = jnp.full((16, 8), 99, jnp.int32)          # far above in_bsl
    a = dispatch.approx_bsn(bad, spec, backend=KERNEL, min_rows_for_kernel=0)
    b = dispatch.approx_bsn(bad, spec, backend="reference")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("width,in_bsl", [(15, 5), (25, 3), (9, 9), (5, 2),
                                          (8, 2), (576, 2), (100, 8)])
def test_default_approx_spec_always_valid(width, in_bsl):
    """The designer must produce a constructible spec for ANY geometry,
    including odd sorted lengths (which admit no symmetric clip with an
    even stride)."""
    spec = default_approx_spec(width, in_bsl)       # would raise if invalid
    assert spec.out_bsl >= 1
    assert spec.scale & (spec.scale - 1) == 0       # pow2, re-alignable
    rng = np.random.default_rng(width)
    c = jnp.asarray(rng.integers(0, in_bsl + 1, (16, width)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(dispatch.approx_bsn(c, spec, backend=KERNEL,
                                       min_rows_for_kernel=0)),
        np.asarray(approx_bsn_counts(c, spec)))


def test_validate_stages_rejects_bad_specs():
    with pytest.raises(ValueError):
        validate_stages(8, 4, ((3, 0, 1),))          # group doesn't divide
    with pytest.raises(ValueError):
        validate_stages(8, 4, ((8, 16, 1),))         # clip eats everything
    with pytest.raises(ValueError):
        validate_stages(8, 4, ((8, 1, 4),))          # stride doesn't divide
    with pytest.raises(ValueError):
        validate_stages(8, 4, ((4, 0, 1),))          # prod(groups) != width


# ---------------------------------------------------------------------------
# sc_layers integration: the approximate adder in the integer datapath
# ---------------------------------------------------------------------------

def _int_layer(seed, k, n):
    rng = np.random.default_rng(seed)
    x_q = jnp.asarray(rng.integers(-4, 5, (6, k)), jnp.int8)
    w_int = rng.integers(-1, 2, (k, n)).astype(np.int8)
    return x_q, {"w_int": w_int, "thresholds": None}


def test_sc_linear_int_approx_degenerate_is_exact():
    from repro.core.sc_layers import sc_linear_int, sc_linear_int_approx
    k, act_bsl = 32, 8
    x_q, ip = _int_layer(0, k, 8)
    spec = ApproxBSNSpec(k, act_bsl, (StageSpec(k, SubSampleSpec(0, 1)),))
    got = sc_linear_int_approx(ip, x_q, act_bsl, spec, backend=KERNEL)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(sc_linear_int(ip, x_q)))


def test_sc_linear_int_approx_kernel_equals_reference():
    from repro.core.sc_layers import sc_linear_int_approx
    k, act_bsl = 64, 8
    x_q, ip = _int_layer(1, k, 4)
    spec = default_approx_spec(16, act_bsl)
    a = sc_linear_int_approx(ip, x_q, act_bsl, spec, cycles=4,
                             backend=KERNEL)
    b = sc_linear_int_approx(ip, x_q, act_bsl, spec, cycles=4,
                             backend="reference")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_engine_pins_dispatch_backend():
    """ServeEngine(bsn_backend=...) scopes dispatch during traced calls
    and greedy generations are identical across backends (the adder is
    deterministic, only its executor changes)."""
    from repro.configs import get_arch
    from repro.models import init_params
    from repro.serving import ServeEngine
    cfg = get_arch("granite-3-2b").scaled(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
        vocab_size=32, vocab_pad_multiple=32, dtype="float32")
    params = init_params(jax.random.key(0), cfg)

    with pytest.raises(ValueError):
        ServeEngine(params, cfg, bsn_backend="verilog")

    outs = {}
    for backend in (None, "reference"):
        eng = ServeEngine(params, cfg, max_slots=2, max_len=16,
                          bsn_backend=backend)
        eng.submit([1, 2, 3], max_new_tokens=3)
        done = eng.run_to_completion()
        assert len(done) == 1
        outs[backend] = done[0].generated
    assert outs[None] == outs["reference"]


# ---------------------------------------------------------------------------
# paper_tnn spatial-temporal chunking regression (Fig 12 on the chip's
# layer sizes)
# ---------------------------------------------------------------------------

def _tnn_folds():
    """(spec, cycles) combinations folding the TNN layer accumulations."""
    from repro.configs.paper_tnn import TNN_ACT_BSL, TNN_LAYERS
    folds = []
    for width, fold in ((TNN_LAYERS[0], 7), (TNN_LAYERS[1], 4),
                        (TNN_LAYERS[2], 4)):
        w = width // fold
        folds.append((default_approx_spec(w, TNN_ACT_BSL), fold))
        # exact (degenerate) fold of the same geometry
        folds.append((ApproxBSNSpec(
            w, TNN_ACT_BSL, (StageSpec(w, SubSampleSpec(0, 1)),)), fold))
    return folds


@pytest.mark.parametrize("spec,cycles", _tnn_folds(),
                         ids=lambda v: str(v))
def test_tnn_temporal_chunking_regression(spec, cycles):
    """Temporal path over T cycles == spatial pipeline per chunk, summed —
    and for degenerate specs == the exact sum of the concatenated input."""
    rng = np.random.default_rng(spec.width * cycles)
    total = cycles * spec.width
    counts = jnp.asarray(rng.integers(0, spec.in_bsl + 1, (4, total)),
                         jnp.int32)
    got = spatial_temporal_counts(counts, spec, cycles)
    chunks = counts.reshape(4, cycles, spec.width)
    expect = approx_bsn_counts(chunks, spec).sum(-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
    # kernel agrees with the chunked reference
    kern = dispatch.approx_bsn(counts, spec, cycles=cycles, backend=KERNEL,
                               min_rows_for_kernel=0)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(got))
    if spec.scale == 1 and spec.out_bsl == spec.width * spec.in_bsl:
        # degenerate: temporal fold == spatial exact sum on the concat input
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(counts.sum(-1)))
