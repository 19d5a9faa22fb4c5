"""Paged KV cache: allocator properties + batched-vs-sequential decode.

Three layers of guarantees, bottom-up:

1. ``PageAllocator``/``PageTable`` host bookkeeping: alloc/free
   round-trips, all-or-nothing allocation, the trash page is never
   handed out (property tests via hypothesis or the conftest fallback).
2. No cross-request leakage: after requests finish and their pages are
   recycled to *new* requests, the new requests' tokens are identical
   to a fresh engine's — stale page contents are dead by construction
   (length-masked reads).
3. The differential theorem the engine stands on: batched paged decode
   == per-request sequential decode (the seed execution model),
   token for token, across the zoo's layer types and datapaths —
   recurrent mixers included, through the chunked state-carrying paged
   prefill (prefill runs the per-token recurrence, so any chunk split
   is bit-identical to the exact-length call; ``sc_int`` is bit-exact
   by integer accumulation on every arch).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import LayerSpec, get_arch
from repro.kernels.ref import from_pages, to_pages
from repro.models import init_params
from repro.serving import (PageAllocator, PageTable, SamplingParams,
                           ServeEngine, kv_page_bytes, sequential_generate)
from repro.serving.paging import TRASH_PAGE, pad_pow2, pages_needed

SCALE = dict(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
             vocab_size=64, vocab_pad_multiple=32, dtype="float32",
             attn_q_chunk=8)
CFG = get_arch("granite-3-2b").scaled(n_layers=2, **SCALE)
PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12, 13, 14]]

# the recurrent zoo, quantized (sc_int is bit-exact on these too now
# that prefill is order-exact at every chunk split)
RECURRENT = {
    "mamba": get_arch("jamba-1.5-large-398b").scaled(
        period=(LayerSpec("mamba", "dense"),), n_layers=2, **SCALE,
        mamba_d_state=8),
    "rwkv6": get_arch("rwkv6-7b").scaled(
        n_layers=2, **{**SCALE, "n_kv_heads": 4}),
    "jamba": get_arch("jamba-1.5-large-398b").scaled(
        n_layers=8, **SCALE, mamba_d_state=8, n_experts=4,
        n_experts_per_tok=2, moe_capacity_factor=2.0),
}


def _run_engine(params, cfg, prompts, max_new=5, sampling=None, **kw):
    eng = ServeEngine(params, cfg, **kw)
    sps = sampling if sampling is not None else [None] * len(prompts)
    for p, sp in zip(prompts, sps):
        eng.submit(p, max_new_tokens=max_new, sampling=sp)
    done = eng.run_to_completion()
    assert len(done) == len(prompts)
    return [r.generated for r in sorted(done, key=lambda r: r.rid)]


# ---------------------------------------------------------------------------
# 1. allocator properties
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(1, 5), min_size=1, max_size=8),
       st.integers(8, 64))
@settings(max_examples=20, deadline=None)
def test_alloc_free_roundtrip(sizes, num_pages):
    a = PageAllocator(num_pages)
    start_free = a.free_count
    assert start_free == num_pages - 1          # page 0 reserved
    held = []
    for n in sizes:
        got = a.alloc(n)
        if got is None:
            assert n > a.free_count             # only fails when short
            continue
        assert len(got) == n
        assert TRASH_PAGE not in got            # trash never handed out
        held.append(got)
    flat = [p for g in held for p in g]
    assert len(set(flat)) == len(flat)          # no page owned twice
    for g in held:
        a.free(g)
    assert a.free_count == start_free           # round-trip restores all


def test_double_free_rejected():
    a = PageAllocator(8)
    g = a.alloc(2)
    a.free(g)
    with pytest.raises(ValueError):
        a.free(g)
    with pytest.raises(ValueError):
        a.free([TRASH_PAGE])


def test_fragmentation_interleaved_alloc_free_to_exhaustion():
    """Long interleaved alloc/free churn fragments the LIFO free list;
    the invariants must hold at every step — no page owned twice, the
    trash page never escapes, free_count + owned == capacity — and a
    full drain after driving the pool to exhaustion restores the exact
    starting capacity (no page leaked, none minted)."""
    cap = 16
    a = PageAllocator(cap + 1)
    held = []
    rng = np.random.default_rng(5)
    for _ in range(200):
        if held and rng.integers(3) == 0:
            a.free(held.pop(int(rng.integers(len(held)))))
        n = int(rng.integers(1, 5))
        got = a.alloc(n)
        if got is None:
            assert n > a.free_count         # all-or-nothing, only short
        else:
            held.append(got)
        owned = [p for g in held for p in g]
        assert len(set(owned)) == len(owned)
        assert TRASH_PAGE not in owned
        assert a.free_count + len(owned) == cap
    while (got := a.alloc(1)) is not None:   # exhaust
        held.append(got)
    assert a.free_count == 0 and a.alloc(1) is None
    for g in held:
        a.free(g)
    assert a.free_count == cap


def test_alloc_fail_leaves_pool_intact():
    """A failing alloc must return None WITHOUT leaking partially
    grabbed pages: the free count is untouched and a smaller request
    still succeeds."""
    a = PageAllocator(6)                     # 5 usable
    g = a.alloc(3)
    before = a.free_count
    assert a.alloc(3) is None                # only 2 free
    assert a.free_count == before
    g2 = a.alloc(2)
    assert g2 is not None and not set(g2) & set(g)
    a.free(g)
    a.free(g2)
    assert a.free_count == 5


@pytest.mark.parametrize("fmt,datapath", [("fp", "qat"), ("int8", "qat"),
                                          ("sc", "sc_int")])
def test_pool_device_bytes_match_page_accounting(fmt, datapath):
    """The allocator's page count times ``kv_page_bytes`` equals the
    actual device bytes of the attention pools (codes + scales +
    residuals) per layer — the analytic capacity model the bench
    records is exact, not an estimate."""
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=8,
                      datapath=datapath, kv_format=fmt)
    per_page = kv_page_bytes(8, CFG.n_kv_heads,
                             CFG.d_model // CFG.n_heads, fmt)
    pool_keys = ("k_pages", "v_pages", "k_scale", "v_scale",
                 "k_resid", "v_resid")
    for entry in eng.cache["periods"].values():
        if "k_pages" not in entry:
            continue
        n_periods, num_pages = entry["k_pages"].shape[:2]
        assert num_pages == eng.allocator.num_pages
        got = sum(entry[k].nbytes for k in pool_keys if k in entry)
        assert got == n_periods * num_pages * per_page, fmt


@given(st.integers(0, 40), st.integers(0, 40))
@settings(max_examples=20, deadline=None)
def test_page_table_ensure_monotonic(l1, l2):
    a = PageAllocator(64)
    t = PageTable(page_size=4)
    assert t.ensure(l1, a) and t.ensure(l2, a)
    # table covers the running max, exactly (never shrinks, never over-
    # allocates), and releases everything it took
    assert len(t.pages) == pages_needed(max(l1, l2), 4)
    t.release(a)
    assert a.free_count == 63


def test_padded_table_is_trash_padded():
    a = PageAllocator(16)
    t = PageTable(page_size=4)
    t.ensure(6, a)                              # 2 pages
    padded = t.padded(8)
    assert list(padded[:2]) == t.pages
    assert all(p == TRASH_PAGE for p in padded[2:])
    with pytest.raises(ValueError):
        t.padded(1)


def test_pad_pow2_buckets():
    assert [pad_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert pad_pow2(1, lo=16) == 16


def test_pad_pow2_always_pow2():
    """The pow2-bucket contract: whatever the bounds, the bucket is a
    power of two >= n (a non-pow2 bucket would mint a fresh jit trace
    per odd size; a bucket < n would under-allocate the lane buffers)."""
    for n in range(1, 20):
        for lo in (1, 3, 4, 16):
            for hi in (None, 3, 4, 6, 8, 31):
                b = pad_pow2(n, lo=lo, hi=hi)
                assert b & (b - 1) == 0, (n, lo, hi, b)
                assert b >= n, (n, lo, hi, b)
    # hi is clamped DOWN to a pow2 (6 -> 4), lo rounded up (3 -> 4)
    assert pad_pow2(3, hi=6) == 4
    assert pad_pow2(4, hi=6) == 4
    assert pad_pow2(2, hi=3) == 2
    assert pad_pow2(1, lo=3) == 4
    # the old bug: min(b, hi) returned a non-pow2 hi verbatim
    assert pad_pow2(3, hi=3) == 4
    # soft cap: no pow2 <= hi can hold n -> next pow2 above n anyway
    assert pad_pow2(5, hi=6) == 8
    assert pad_pow2(6, hi=6) == 8


# ---------------------------------------------------------------------------
# 2. recycling: no cross-request leakage
# ---------------------------------------------------------------------------

def test_page_recycling_no_leakage():
    """Run a wave of requests to completion, then a second wave through
    the SAME engine — its pages are recycled physical pages.  The second
    wave must match a fresh engine serving it alone."""
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=8)
    wave1 = PROMPTS[:2]
    wave2 = [[9, 8, 7, 6, 5], [3, 1], [2, 2, 2]]
    for p in wave1:
        eng.submit(p, max_new_tokens=6)
    eng.run_to_completion()
    used_before = eng.allocator.free_count
    for p in wave2:
        eng.submit(p, max_new_tokens=6)
    done = eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    assert eng.allocator.free_count == used_before   # all pages returned
    fresh = _run_engine(init_params(jax.random.key(0), CFG), CFG, wave2,
                        max_new=6, max_slots=2, max_len=32, page_size=8)
    assert got == fresh


def test_unservable_prompt_rejected_at_submit():
    """A prompt that could never fit the pool (even empty) must fail
    loudly at submit, not spin forever in the admission queue."""
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=2, max_len=31, page_size=4,
                      num_pages=8)                 # 7 usable pages
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(30)))                # needs 8 pages
    eng.submit(list(range(20)))                    # 6 pages: fine


def test_empty_prompt_rejected_at_submit():
    """An empty prompt would reach prefill as a (1, 0) token batch and
    blow up deep inside the model; it must fail at the API boundary."""
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=8)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([])
    eng.submit([1])                                # 1 token: fine


def test_boundary_prompts_match_sequential():
    """Prompts of length max_len-2 and max_len-1: the done-logic boundary
    (`_len >= max_len - 1`, consolidated in `_check_done`) must agree
    with sequential_generate's `length < max_len - 1` loop condition —
    exactly 2 and 1 generated tokens respectively."""
    params = init_params(jax.random.key(0), CFG)
    max_len = 16
    prompts = [list(range(1, max_len - 1)),        # max_len - 2 tokens
               list(range(1, max_len))]            # max_len - 1 tokens
    got = _run_engine(params, CFG, prompts, max_new=8, max_slots=2,
                      max_len=max_len, page_size=4)
    ref = sequential_generate(params, CFG, prompts, max_new_tokens=8,
                              max_len=max_len)
    assert got == ref
    assert [len(g) for g in got] == [2, 1]
    with pytest.raises(ValueError, match="exceeds"):
        ServeEngine(params, CFG, max_slots=2, max_len=max_len,
                    page_size=4).submit(list(range(max_len)))


def test_non_pow2_max_slots_matches_sequential():
    """max_slots=3 (non-pow2): slot buckets must still be powers of two
    (the pad_pow2 fix) and tokens must match the oracle."""
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=3, max_len=32, page_size=8)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=5)
    done = eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    ref = sequential_generate(params, CFG, PROMPTS, max_new_tokens=5,
                              max_len=32)
    assert got == ref


def test_preemption_under_page_pressure():
    """A pool too small for all admitted requests forces preemption
    (free + requeue + re-prefill); greedy decode is deterministic so the
    final tokens still match the sequential oracle."""
    params = init_params(jax.random.key(0), CFG)
    # 2 slots x up to 24 tokens needs 6 pages of 8; give it 4 + trash
    eng = ServeEngine(params, CFG, max_slots=2, max_len=24, page_size=8,
                      num_pages=5)
    prompts = [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13]]
    for p in prompts:
        eng.submit(p, max_new_tokens=12)
    done = eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    ref = sequential_generate(params, CFG, prompts, max_new_tokens=12,
                              max_len=24)
    assert got == ref


# ---------------------------------------------------------------------------
# 3. differential: batched paged == sequential, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
def test_batched_equals_sequential_sc_datapaths(datapath):
    params = init_params(jax.random.key(0), CFG)
    got = _run_engine(params, CFG, PROMPTS, max_new=5, max_slots=3,
                      max_len=32, page_size=8, datapath=datapath)
    ref = sequential_generate(params, CFG, PROMPTS, max_new_tokens=5,
                              max_len=32, datapath=datapath)
    assert got == ref, datapath


def test_batched_equals_sequential_mixed_lengths_and_buckets():
    """Length mix spanning several page/slot buckets + late admissions."""
    params = init_params(jax.random.key(1), CFG)
    prompts = [[1], [2, 3, 4, 5, 6, 7, 8, 9, 10],
               [11, 12], [13, 14, 15, 16, 17], [18] * 12]
    got = _run_engine(params, CFG, prompts, max_new=8, max_slots=2,
                      max_len=32, page_size=4)
    ref = sequential_generate(params, CFG, prompts, max_new_tokens=8,
                              max_len=32)
    assert got == ref


@pytest.mark.parametrize("datapath", ["qat", "sc_int", "sc_int_approx"])
@pytest.mark.parametrize("arch", sorted(RECURRENT))
def test_chunked_recurrent_batched_equals_sequential(arch, datapath):
    """The tentpole differential: mamba, rwkv6 and the jamba hybrid now
    prefill through the SAME batched chunked paged path as attention
    (no exact-length fallback), and stay token-identical to the
    sequential oracle on every datapath.  Holds because prefill runs
    the per-token recurrence with carried state — any chunk split
    replays the identical op sequence, so even sc_int's lattice ties
    break the same way on both sides."""
    cfg = RECURRENT[arch]
    params = init_params(jax.random.key(0), cfg)
    got = _run_engine(params, cfg, PROMPTS[:3], max_new=4, max_slots=2,
                      max_len=32, page_size=8, datapath=datapath)
    ref = sequential_generate(params, cfg, PROMPTS[:3], max_new_tokens=4,
                              max_len=32, datapath=datapath)
    assert got == ref, (arch, datapath)


def test_chunked_recurrent_sampled_matches_sequential():
    """Seeded stochastic decode over the chunked recurrent prefill: the
    (seed, position) streams don't care how the prompt was chunked."""
    sampling = [SamplingParams(temperature=0.9, top_p=0.9, seed=3 + i)
                for i in range(3)]
    for arch in ("rwkv6", "jamba"):
        cfg = RECURRENT[arch]
        params = init_params(jax.random.key(0), cfg)
        got = _run_engine(params, cfg, PROMPTS[:3], max_new=4,
                          sampling=sampling, max_slots=2, max_len=32,
                          page_size=8)
        ref = sequential_generate(params, cfg, PROMPTS[:3],
                                  max_new_tokens=4, max_len=32,
                                  sampling=sampling)
        greedy = sequential_generate(params, cfg, PROMPTS[:3],
                                     max_new_tokens=4, max_len=32)
        assert got == ref, arch
        assert got != greedy, f"{arch}: sampling degenerated to greedy"


def test_chunked_equals_exact_prefill_oracle():
    """``prefill_mode="exact"`` (the retired per-request exact-length
    fallback, kept as a debug oracle) and the default chunked path
    produce identical tokens — multi-chunk prompts included."""
    prompts = [[(3 * i + j) % 64 for j in range(n)]
               for i, n in enumerate([23, 1, 17, 9])]
    for arch in ("mamba", "rwkv6"):
        cfg = RECURRENT[arch]
        params = init_params(jax.random.key(0), cfg)
        kw = dict(max_new=4, max_slots=2, max_len=32, page_size=4,
                  prefill_chunk=4)
        chunked = _run_engine(params, cfg, prompts, **kw)
        exact = _run_engine(params, cfg, prompts, prefill_mode="exact",
                            **kw)
        assert chunked == exact, arch


def test_mamba_conv_tail_across_chunk_boundaries():
    """PR 2's pad-then-crop fix covered one exact-length call; a prompt
    split into chunks must reproduce the IDENTICAL mixer output at every
    boundary (the carried conv tail supplies the k-1 pre-conv inputs the
    next chunk's conv window needs).  Chunk sizes 1, page_size, and
    prompt_len-1, compared bitwise — output, SSM state and tail."""
    from repro.models.mamba import (mamba_init, mamba_prefill_chunk,
                                    mamba_state_init)
    cfg = RECURRENT["mamba"]
    p = mamba_init(jax.random.key(3), cfg)
    B, S = 2, 13                        # S coprime with every chunk size
    x = jax.random.normal(jax.random.key(4), (B, S, cfg.d_model),
                          jnp.float32)
    y_ref, st_ref = mamba_prefill_chunk(p, x, cfg,
                                        mamba_state_init(cfg, B))
    page_size = 8
    for csize in (1, page_size, S - 1):
        st = mamba_state_init(cfg, B)
        ys = []
        for a in range(0, S, csize):
            y, st = mamba_prefill_chunk(p, x[:, a:a + csize], cfg, st)
            ys.append(y)
        y_split = jnp.concatenate(ys, axis=1)
        assert np.array_equal(np.asarray(y_split), np.asarray(y_ref)), \
            csize
        for k in ("h", "conv"):
            assert np.array_equal(np.asarray(st[k]),
                                  np.asarray(st_ref[k])), (csize, k)


def _poison_pools(eng, keep):
    """Set every KV pool position NOT in ``keep`` (a set of (page, off)
    pairs) to a huge finite value, in every layer.  Compressed formats
    carry parallel scale / residual pools; their positions poison too
    (int8 code pools saturate at +127, float scale pools get the huge
    value), so a mask leak would blow up regardless of format."""
    periods = {}
    for key, entry in eng.cache["periods"].items():
        entry = dict(entry)
        for name in ("k_pages", "v_pages", "k_scale", "v_scale",
                     "k_resid", "v_resid"):
            if name in entry:
                # head-major (n_periods, num_pages, Hkv, page, ...): view
                # it position-major to mask (num_pages, page) pairs
                page = entry[name].shape[3]
                pool = np.array(from_pages(entry[name]))
                mask = np.ones(pool.shape[1], bool)     # num_pages * page
                for pg, off in keep:
                    mask[pg * page + off] = False
                pool[:, mask] = 127 if pool.dtype == np.int8 else 3e4
                entry[name] = to_pages(jnp.asarray(pool), page)
        periods[key] = entry
    eng.cache = {"periods": periods}


@pytest.mark.parametrize("prefill_mode", ["chunked", "exact"])
def test_padded_tail_kv_positions_never_attend(prefill_mode):
    """The tail KV page holds non-prompt positions (zero-padded by the
    exact path's ``_scatter_prefill``, garbage-written by the chunked
    path), and padded table lanes point at the trash page.  None of
    them may EVER contribute to attention, for any plen % page_size:
    poison every non-prompt pool position with a huge finite value
    before AND after prefill — a mask leak would blow the logits up and
    flip tokens vs the oracle."""
    params = init_params(jax.random.key(0), CFG)
    page = 4
    for plen in (1, 3, 4, 6, 8):        # covers every residue mod 4
        # the second, shorter prompt pads its page table relative to the
        # first inside the shared prefill bucket, so the chunked gather
        # really reads (masked) trash-page rows during prefill
        prompts = [[(2 * plen + j) % 64 for j in range(plen)], [9, 10]]
        eng = ServeEngine(params, CFG, max_slots=2, max_len=16,
                          page_size=page, prefill_mode=prefill_mode)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        _poison_pools(eng, keep=set())  # prefill must mask trash reads
        eng._admit()
        keep = {(r._table.pages[t // page], t % page)
                for r in eng.slots if r is not None
                for t in range(len(r.prompt))}
        _poison_pools(eng, keep)        # decode must mask the tail pad
        done = eng.run_to_completion()
        got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
        ref = sequential_generate(params, CFG, prompts,
                                  max_new_tokens=4, max_len=16)
        assert got == ref, (prefill_mode, plen)


@pytest.mark.parametrize("fmt,datapath", [("int8", "qat"),
                                          ("sc", "sc_int")])
def test_padded_tail_never_attends_compressed(fmt, datapath):
    """The poison theorem on the compressed pools: codes, scales AND
    residuals outside the positions a request owns must never reach
    attention — poisoned scales would multiply into huge dequantized
    K/V if any masked position leaked through."""
    params = init_params(jax.random.key(0), CFG)
    page = 4
    prompts = [[3, 1, 4, 1, 5, 9], [2, 6]]
    eng = ServeEngine(params, CFG, max_slots=2, max_len=16,
                      page_size=page, datapath=datapath, kv_format=fmt)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    _poison_pools(eng, keep=set())      # prefill must mask trash reads
    eng._admit()
    keep = {(r._table.pages[t // page], t % page)
            for r in eng.slots if r is not None
            for t in range(len(r.prompt))}
    _poison_pools(eng, keep)            # decode must mask the tail pad
    done = eng.run_to_completion()
    got = [r.generated for r in sorted(done, key=lambda r: r.rid)]
    want = sequential_generate(params, CFG, prompts, max_new_tokens=4,
                               max_len=16, datapath=datapath,
                               kv_format=fmt)
    assert got == want, fmt


def test_boundary_prompts_recurrent_match_sequential():
    """The prompt-length boundary on the recurrent chunked path: a
    prompt of max_len-1 tokens must emit exactly one token then stop,
    max_len-2 exactly two — `_check_done` after prefill must agree with
    sequential_generate's loop condition, same as the attention
    configs."""
    max_len = 16
    prompts = [list(range(1, max_len - 1)),        # max_len - 2 tokens
               list(range(1, max_len))]            # max_len - 1 tokens
    for arch in ("mamba", "rwkv6"):
        cfg = RECURRENT[arch]
        params = init_params(jax.random.key(0), cfg)
        got = _run_engine(params, cfg, prompts, max_new=8, max_slots=2,
                          max_len=max_len, page_size=4)
        ref = sequential_generate(params, cfg, prompts, max_new_tokens=8,
                                  max_len=max_len)
        assert got == ref, arch
        assert [len(g) for g in got] == [2, 1], arch


def test_recurrent_preemption_under_page_pressure():
    """Preempting a request on the recurrent path requeues it through
    the chunked prefill again (state rows rebuilt from zero); greedy
    decode is deterministic so tokens still match the oracle."""
    cfg = RECURRENT["jamba"]
    params = init_params(jax.random.key(0), cfg)
    prompts = [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13]]
    got = _run_engine(params, cfg, prompts, max_new=12, max_slots=2,
                      max_len=24, page_size=8, num_pages=5)
    ref = sequential_generate(params, cfg, prompts, max_new_tokens=12,
                              max_len=24)
    assert got == ref


def test_sharded_serving_subprocess():
    """Tier-1 entry to the 8-device sharded suite
    (test_sharded_serving.py).  The forced host-device count must be
    set before jax initializes, so it needs a fresh interpreter; when
    this process already has 8 devices (the CI sharded job) the inner
    suite runs natively and this wrapper skips."""
    if jax.device_count() >= 8:
        pytest.skip("sharded suite runs natively in this process")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.join(os.path.dirname(here), "src")
        + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         os.path.join(here, "test_sharded_serving.py")],
        env=env, capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]


def test_decode_retraces_only_on_bucket_changes():
    """5 requests of mixed lengths through 2 slots crosses admissions,
    evictions and length growth constantly; the jitted decode must have
    compiled at most (slot buckets) x (page buckets) variants."""
    params = init_params(jax.random.key(0), CFG)
    eng = ServeEngine(params, CFG, max_slots=2, max_len=32, page_size=4)
    for p in PROMPTS + [[5] * 9]:
        eng.submit(p, max_new_tokens=7)
    eng.run_to_completion()
    if hasattr(eng._decode, "_cache_size"):
        # slot buckets {1, 2} x page buckets {1, 2, 4} is the ceiling
        assert eng._decode._cache_size() <= 6
