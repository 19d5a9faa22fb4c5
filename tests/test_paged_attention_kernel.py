"""Three-way differential harness for the paged-attention kernels.

The chain proven here, per the dispatch discipline:

    flash-decoding Pallas kernel (interpret mode)
        ==  XLA gather/scatter reference (kernels/ref.py)
        ==  sequential_generate token identity (dense-cache oracle)

on all three datapaths, plus: lengths straddling page boundaries
(``plen % page`` in {0, 1, page-1}), split-K widths, trash-page poison
invisibility under the kernel path, the attention backend scope /
``ServeEngine(attn_backend=...)`` pinning, and the dispatch-layer
regressions this PR fixes (TPU row threshold, zero-row approx_bsn).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.bsn import default_approx_spec
from repro.core.kv_quant import kv_quant
from repro.kernels import dispatch, ref
from repro.kernels.paged_attention import (paged_attn_decode_pallas,
                                           paged_attn_prefill_pallas)
from repro.models import init_params
from repro.serving import ServeEngine, sequential_generate

KERNEL = "pallas-interpret"       # compiled semantics, runs on CPU
POISON = 3.0e4


# ---------------------------------------------------------------------------
# kernel-level differential vs the XLA gather reference
# ---------------------------------------------------------------------------

def _paged_case(seed, S, Hkv, D, page, maxp):
    """Pools + per-slot page tables the way the allocator hands them out:
    page 0 reserved (trash), distinct physical pages per slot."""
    rng = np.random.default_rng(seed)
    n = S * maxp + 1
    kp = jnp.asarray(rng.standard_normal((n, Hkv, page, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n, Hkv, page, D)), jnp.float32)
    tables = np.zeros((S, maxp), np.int32)
    for s in range(S):
        tables[s] = 1 + s * maxp + rng.permutation(maxp)
    return rng, kp, vp, jnp.asarray(tables)


@pytest.mark.parametrize("S,Hkv,G,D,page,maxp", [
    (3, 2, 2, 16, 8, 4),
    (1, 1, 1, 8, 4, 2),          # degenerate single-slot MHA
    (4, 2, 3, 32, 16, 3),        # non-pow2 GQA group
])
@pytest.mark.parametrize("num_splits", [1, 2, 3])
def test_decode_kernel_vs_reference(S, Hkv, G, D, page, maxp, num_splits):
    rng, kp, vp, tables = _paged_case(S * D, S, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(0, maxp * page, S), jnp.int32)
    got = paged_attn_decode_pallas(q, kp, vp, tables, lengths,
                                   num_splits=num_splits, interpret=True)
    want = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("rem", [0, 1, -1])
def test_decode_lengths_straddle_page_boundaries(rem):
    """plen % page in {0, 1, page-1}: the mask must cut exactly at the
    boundary whether the live window ends a page, just enters one, or
    stops one short."""
    S, Hkv, G, D, page, maxp = 3, 2, 2, 16, 8, 4
    rng, kp, vp, tables = _paged_case(7 + rem, S, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    # one slot per page multiple, offset by rem (mod page)
    lengths = jnp.asarray([(k * page + rem) % (maxp * page)
                           for k in (1, 2, 3)], jnp.int32)
    for num_splits in (1, 2):
        got = paged_attn_decode_pallas(q, kp, vp, tables, lengths,
                                       num_splits=num_splits,
                                       interpret=True)
        want = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6, err_msg=str(rem))


def test_decode_kernel_trash_page_poison_invisible():
    """Poison the trash page AND every page not referenced below the live
    length: the kernel output must be bit-identical to the clean run."""
    S, Hkv, G, D, page, maxp = 3, 2, 2, 16, 8, 4
    rng, kp, vp, tables = _paged_case(11, S, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray([5, page, 2 * page - 1], jnp.int32)
    clean = paged_attn_decode_pallas(q, kp, vp, tables, lengths,
                                     num_splits=2, interpret=True)
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp2[0] = POISON                                 # the trash page
    vp2[0] = POISON
    t = np.asarray(tables)
    for s in range(S):                              # pages past the length
        for j in range(int(lengths[s]) // page + 1, maxp):
            kp2[t[s, j]] = POISON
            vp2[t[s, j]] = POISON
    pois = paged_attn_decode_pallas(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                    tables, lengths, num_splits=2,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(pois))


def _stacked_case(seed, fmt, L, S, Hkv, D, page, maxp):
    """Pools of ``L`` layers stacked (L, N, Hkv, page, D), the layout the
    decode step carries, quantized positionwise for compressed formats;
    the aux dict holds the stacked scale / residual pools."""
    rng = np.random.default_rng(seed)
    n = S * maxp + 1
    shape = (L, n, Hkv, page, D)
    kf = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vf = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    aux = {}
    if fmt == "fp":
        kp, vp = kf, vf
    else:
        kq, vq = kv_quant(kf, fmt), kv_quant(vf, fmt)
        kp, vp = kq["q"], vq["q"]
        aux = {"k_scale": kq["scale"], "v_scale": vq["scale"]}
        if fmt == "sc":
            aux |= {"k_resid": kq["resid"], "v_resid": vq["resid"]}
    tables = np.zeros((S, maxp), np.int32)
    for s in range(S):
        tables[s] = 1 + s * maxp + rng.permutation(maxp)
    q = jnp.asarray(rng.standard_normal((S, Hkv, 2, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(0, maxp * page, S), jnp.int32)
    return q, kp, vp, jnp.asarray(tables), lengths, aux


@pytest.mark.parametrize("page", [8, 128])      # 128: the (D, page) view
@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("fmt", ["fp", "int8", "sc"])
def test_decode_kernel_layer_index_equals_sliced_layer(fmt, layer, page):
    """The layer-indexed kernel on a stack of pools reads exactly layer
    ``layer``: bit for bit the 4-D kernel on ``pools[layer]``, and the
    reference with ``layer`` is bit for bit the reference on the slice."""
    L = 3
    li = 0 if layer == "first" else L - 1
    q, kp, vp, tables, lengths, aux = _stacked_case(
        li + page, fmt, L, S=3, Hkv=2, D=16, page=page, maxp=3)
    one = {k: v[li] for k, v in aux.items()}
    got = paged_attn_decode_pallas(q, kp, vp, tables, lengths,
                                   layer=jnp.int32(li), num_splits=2,
                                   interpret=True, kv_format=fmt, **aux)
    want = paged_attn_decode_pallas(q, kp[li], vp[li], tables, lengths,
                                    num_splits=2, interpret=True,
                                    kv_format=fmt, **one)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_ref = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths,
                                        layer=jnp.int32(li), kv_format=fmt,
                                        kv_aux=aux)
    want_ref = ref.paged_attn_decode_ref(q, kp[li], vp[li], tables,
                                         lengths, kv_format=fmt,
                                         kv_aux=one)
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want_ref))
    # and the kernel agrees with the reference, as on one layer
    np.testing.assert_allclose(np.asarray(got), np.asarray(got_ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("page", [8, 128])
@pytest.mark.parametrize("fmt", ["fp", "int8", "sc"])
def test_attn_decode_on_stacks_equals_one_layer(fmt, page):
    """One decode attention on the layer loop's pool stacks: the new K/V
    land in layer ``li`` of the stack in place (every other layer kept
    bit for bit) and the output is bit for bit the same call on a stack
    of that layer alone; the padded lane writes the trash page."""
    from repro.models.attention import attn_decode_paged, attn_init
    cfg = CFG.scaled(dtype="float32")
    p = attn_init(jax.random.key(3), cfg)
    L, li, S, maxp = 3, 1, 3, 2
    Hkv, D = cfg.n_kv_heads, cfg.head_dim
    _, kp, vp, tables, _, aux = _stacked_case(
        page + 1, fmt, L, S=S, Hkv=Hkv, D=D, page=page, maxp=maxp)
    tables = tables.at[S - 1].set(0)                # a padded lane
    lengths = jnp.asarray([page - 1, page + 3, 0], jnp.int32)
    x = jax.random.normal(jax.random.key(4), (S, 1, cfg.d_model))
    stack = dict(aux, k_pages=kp, v_pages=vp)
    one = {k: v[li:li + 1] for k, v in stack.items()}
    with dispatch.attn_backend_scope(KERNEL):
        y, new = attn_decode_paged(
            p, x, cfg, dict(stack, page_tables=tables, layer=jnp.int32(li)),
            lengths)
        y1, new1 = attn_decode_paged(
            p, x, cfg, dict(one, page_tables=tables, layer=jnp.int32(0)),
            lengths)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
    assert set(new) == set(stack)
    for k in stack:
        got = np.asarray(new[k])
        np.testing.assert_array_equal(got[li], np.asarray(new1[k])[0], k)
        keep = [i for i in range(L) if i != li]
        np.testing.assert_array_equal(got[keep], np.asarray(stack[k])[keep],
                                      k)


@pytest.mark.parametrize("G,Hkv,Gq,D,page,C,start", [
    (2, 2, 2, 16, 8, 16, 0),
    (2, 2, 2, 16, 8, 16, 16),     # later chunk sees earlier pages
    (3, 1, 4, 8, 4, 8, 24),
    (1, 2, 1, 32, 8, 8, 8),
])
@pytest.mark.parametrize("block_q", [4, 16, 5])
def test_prefill_kernel_vs_reference(G, Hkv, Gq, D, page, C, start,
                                     block_q):
    maxp = (start + C) // page + 1
    rng, kp, vp, tables = _paged_case(G * C, G, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((G, C, Hkv, Gq, D)), jnp.float32)
    got = paged_attn_prefill_pallas(q, kp, vp, tables, start=start,
                                    block_q=block_q, interpret=True)
    want = ref.paged_attn_prefill_ref(q, kp, vp, tables, start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_prefill_kernel_future_pages_poison_invisible():
    """Pages past the chunk's causal window never load: poisoning them
    (and the trash page) leaves the chunk output bit-identical."""
    G, Hkv, Gq, D, page, C, start = 2, 2, 2, 16, 8, 16, 8
    maxp = 6
    rng, kp, vp, tables = _paged_case(13, G, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((G, C, Hkv, Gq, D)), jnp.float32)
    clean = paged_attn_prefill_pallas(q, kp, vp, tables, start=start,
                                      block_q=8, interpret=True)
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    kp2[0] = POISON
    vp2[0] = POISON
    t = np.asarray(tables)
    seen = (start + C) // page
    for s in range(G):
        for j in range(seen, maxp):
            kp2[t[s, j]] = POISON
            vp2[t[s, j]] = POISON
    pois = paged_attn_prefill_pallas(q, jnp.asarray(kp2),
                                     jnp.asarray(vp2), tables,
                                     start=start, block_q=8,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(pois))


# ---------------------------------------------------------------------------
# engine-level: kernel path == reference path == sequential oracle
# ---------------------------------------------------------------------------

SCALE = dict(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64,
             vocab_pad_multiple=32, dtype="float32", attn_q_chunk=8)
CFG = get_arch("granite-3-2b").scaled(n_layers=2, **SCALE)
PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]


def _engine_tokens(params, datapath, attn_backend, max_new=4, **kw):
    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=8,
                      datapath=datapath, attn_backend=attn_backend, **kw)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=max_new)
    done = eng.run_to_completion()
    assert len(done) == len(PROMPTS)
    return [r.generated for r in sorted(done, key=lambda r: r.rid)]


@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
def test_engine_kernel_three_way_token_identity(datapath):
    """The acceptance differential: decode AND chunked prefill through
    the interpret-mode Pallas kernels produce exactly the tokens of the
    XLA reference engine and of the dense-cache sequential oracle."""
    params = init_params(jax.random.key(0), CFG)
    kern = _engine_tokens(params, datapath, KERNEL)
    refe = _engine_tokens(params, datapath, "reference")
    seq = sequential_generate(params, CFG, PROMPTS, max_new_tokens=4,
                              max_len=32, datapath=datapath)
    assert kern == refe, datapath
    assert refe == seq, datapath


@pytest.mark.parametrize("fmt", ["int8", "sc"])
def test_engine_kernel_three_way_token_identity_compressed(fmt):
    """The compressed-pool third of the acceptance differential: the
    fused-dequant kernels (decode AND prefill, with the scale/residual
    pools riding the scalar-prefetch machinery) serve exactly the tokens
    of the dequant-fused XLA reference engine and of the same-format B=1
    paged sequential oracle."""
    datapath = "sc_int" if fmt == "sc" else "qat"
    params = init_params(jax.random.key(0), CFG)
    kern = _engine_tokens(params, datapath, KERNEL, kv_format=fmt)
    refe = _engine_tokens(params, datapath, "reference", kv_format=fmt)
    seq = sequential_generate(params, CFG, PROMPTS, max_new_tokens=4,
                              max_len=32, datapath=datapath,
                              kv_format=fmt)
    assert kern == refe, fmt
    assert refe == seq, fmt


def test_engine_auto_serves_the_kernel_off_tpu():
    """auto (attn_backend=None) routes this CPU container's serving
    shapes through the interpret kernel — and still matches the oracle."""
    params = init_params(jax.random.key(1), CFG)
    auto = _engine_tokens(params, "qat", None)
    seq = sequential_generate(params, CFG, PROMPTS, max_new_tokens=4,
                              max_len=32)
    assert auto == seq


def test_engine_kernel_path_poisoned_pools_never_attend():
    """The trash-page poison theorem under the kernel path: poison every
    pool position OUTSIDE the pages the requests legitimately own and
    the generated tokens must not move."""
    params = init_params(jax.random.key(0), CFG)
    want = _engine_tokens(params, "qat", KERNEL)

    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=8,
                      datapath="qat", attn_backend=KERNEL)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=4)
    # poison the whole pool (trash page included) before any prefill —
    # every live position gets overwritten by real K/V scatters, and
    # everything else must be masked by lengths/causality
    for per in eng.cache["periods"].values():
        for k in ("k_pages", "v_pages"):
            if k in per:
                per[k] = jnp.full_like(per[k], POISON)
    done = eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    assert got == want


def test_engine_rejects_unknown_attn_backend():
    params = init_params(jax.random.key(0), CFG)
    with pytest.raises(ValueError):
        ServeEngine(params, CFG, attn_backend="verilog")


# ---------------------------------------------------------------------------
# dispatch layer: scope, thresholds, regressions
# ---------------------------------------------------------------------------

def test_attn_backend_scope_pins_and_restores():
    S, Hkv, G, D, page, maxp = 2, 2, 2, 16, 8, 2
    rng, kp, vp, tables = _paged_case(17, S, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray([3, 9], jnp.int32)
    want = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths)
    with dispatch.attn_backend_scope("reference"):
        assert dispatch.get_attn_backend() == "reference"
        with dispatch.attn_backend_scope(None):     # no-op, not a reset
            assert dispatch.get_attn_backend() == "reference"
        got = dispatch.paged_attn_decode(q, kp, vp, tables, lengths)
    assert dispatch.get_attn_backend() is None
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the attention scope never leaks into the BSN chain and vice versa
    with dispatch.attn_backend_scope("reference"):
        assert dispatch.get_default_backend() is None
    with dispatch.backend_scope("reference"):
        assert dispatch.get_attn_backend() is None
    with pytest.raises(ValueError):
        dispatch.set_attn_backend("verilog")


def test_paged_dispatch_row_threshold():
    """Tiny paged shapes take the reference under auto — same policy as
    the BSN chain, including on (monkeypatched) TPU."""
    S, Hkv, G, D, page, maxp = 1, 1, 1, 8, 4, 2
    rng, kp, vp, tables = _paged_case(19, S, Hkv, D, page, maxp)
    q = jnp.asarray(rng.standard_normal((S, Hkv, G, D)), jnp.float32)
    lengths = jnp.asarray([2], jnp.int32)
    # rows = S*Hkv*G = 1 < 8 -> reference; result must equal the oracle
    got = dispatch.paged_attn_decode(q, kp, vp, tables, lengths)
    want = ref.paged_attn_decode_ref(q, kp, vp, tables, lengths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_approx_bsn_zero_rows_short_circuits_to_reference():
    """Regression: a zero-size leading batch dim used to reach the
    pallas path as a degenerate 0-row pallas_call.  Now it returns the
    empty reference result on EVERY backend."""
    spec = default_approx_spec(width=16, in_bsl=4)
    empty = jnp.zeros((0, spec.width), jnp.int32)
    for backend in (None, "pallas-interpret", "reference"):
        out = dispatch.approx_bsn(empty, spec, backend=backend)
        assert out.shape == (0,), backend
    # zero rows hiding under a nonzero leading dim
    empty3 = jnp.zeros((2, 0, spec.width), jnp.int32)
    out = dispatch.approx_bsn(empty3, spec, backend="pallas-interpret")
    assert out.shape == (2, 0)
    # temporal variant too
    empty_t = jnp.zeros((0, 2 * spec.width), jnp.int32)
    out = dispatch.approx_bsn(empty_t, spec, cycles=2,
                              backend="pallas-interpret")
    assert out.shape == (0,)
