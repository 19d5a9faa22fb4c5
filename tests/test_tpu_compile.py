"""Ahead-of-time compiles of the served programs for a described TPU v5e.

Nothing here runs on a chip: each test lowers a program for one device
of a described ``v5e:2x2`` topology and compiles it with the installed
TPU compiler, which refuses what the chip would refuse — blocks that
are not tile-aligned, kernels past the scoped VMEM, programs past HBM.
Shapes are granite-3-2b's published widths (40 layers, d_model 2048,
32 query / 8 KV heads of 64, d_ff 8192, bf16) with the engine that
``chip_smoke.py`` drives: 8 slots x 2048 tokens, pages of 16.

The topology is described inside a module fixture (never at import, in
a ``skipif`` or in ``parametrize``): only the worker that runs this
file loads the TPU library.  The persistent compile cache is off around
the compiles, because a cache written for a described chip cannot be
read back without one.

The last test is CPU-only: it runs ``chip_smoke.run_phase`` at a tiny
config with the interpret kernels, so the script's phase logic stays
covered between chip runs.
"""

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core.bsn import default_approx_spec
from repro.kernels import dispatch
from repro.kernels.paged_attention import (paged_attn_decode_pallas,
                                           paged_attn_prefill_pallas)
from repro.models import (init_paged_cache, init_params, paged_decode_step,
                          paged_prefill)
from repro.serving.engine import _cfg_for_datapath

GRANITE = get_arch("granite-3-2b")
SLOTS, MAX_LEN, PAGE = 8, 2048, 16
MAXP = MAX_LEN // PAGE
NUM_PAGES = SLOTS * MAXP + 1                    # + the trash page
HKV, GQ, D = GRANITE.n_kv_heads, GRANITE.n_heads // GRANITE.n_kv_heads, 64
GiB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _sds(sharding, a.shape, a.dtype), tree)


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _pools(sharding, fmt):
    """Pool operand shapes of one layer, as the kernels take them."""
    kv_dt = jnp.bfloat16 if fmt == "fp" else jnp.int8
    kv = _sds(sharding, (NUM_PAGES, HKV, PAGE, D), kv_dt)
    aux = {}
    if fmt != "fp":
        sc = _sds(sharding, (NUM_PAGES, HKV, PAGE), jnp.float32)
        aux = {"k_scale": sc, "v_scale": sc}
    if fmt == "sc":
        aux |= {"k_resid": kv, "v_resid": kv}
    return kv, aux


@pytest.mark.parametrize("fmt", ["fp", "int8", "sc"])
def test_paged_decode_kernel_compiles(one_chip, fmt):
    """Every split count the autotuner sweeps (m/l partials included)."""
    kv, aux = _pools(one_chip, fmt)
    q = _sds(one_chip, (SLOTS, HKV, GQ, D), jnp.bfloat16)
    tables = _sds(one_chip, (SLOTS, MAXP), jnp.int32)
    lengths = _sds(one_chip, (SLOTS,), jnp.int32)
    for splits in (1, 2, 4, 8):
        c = _compile(lambda q, k, v, t, n, aux: paged_attn_decode_pallas(
            q, k, v, t, n, num_splits=splits, kv_format=fmt, **aux),
            q, kv, kv, tables, lengths, aux)
        assert "tpu_custom_call" in c.as_text(), splits


@pytest.mark.parametrize("fmt", ["fp", "int8", "sc"])
def test_paged_prefill_kernel_compiles(one_chip, fmt):
    kv, aux = _pools(one_chip, fmt)
    q = _sds(one_chip, (4, 64, HKV, GQ, D), jnp.bfloat16)
    tables = _sds(one_chip, (4, MAXP), jnp.int32)
    start = _sds(one_chip, (), jnp.int32)
    for block_q in (32, 64):
        c = _compile(lambda q, k, v, t, s, aux: paged_attn_prefill_pallas(
            q, k, v, t, start=s, block_q=block_q, kv_format=fmt, **aux),
            q, kv, kv, tables, start, aux)
        assert "tpu_custom_call" in c.as_text(), block_q


@pytest.mark.parametrize("width,channels", [(2048, 8192), (8192, 2048)])
def test_approx_bsn_kernel_compiles(one_chip, width, channels):
    """K=8192 is granite's down projection: the row tile must shrink to
    the VMEM budget (the default 256 rows would need 16 MiB of tiles)."""
    spec = default_approx_spec(width, 8)
    counts = _sds(one_chip, (8 * channels, width), jnp.int32)
    c = _compile(lambda x: dispatch.approx_bsn(x, spec, backend="pallas"),
                 counts)
    assert "tpu_custom_call" in c.as_text()


def _model(sharding, datapath, fmt):
    cfg = _cfg_for_datapath(GRANITE, datapath)
    params = _on(sharding, jax.eval_shape(partial(init_params, cfg=cfg),
                                          jax.random.key(0)))
    cache = _on(sharding, jax.eval_shape(lambda: init_paged_cache(
        cfg, SLOTS, NUM_PAGES, PAGE, fmt)))
    return cfg, params, cache


def test_decode_step_auto_backends_serve_compiled_kernels(one_chip,
                                                          monkeypatch):
    """The full 40-layer sc_int_approx decode step with auto backends, as
    dispatch resolves them on a TPU host: the compiled program holds the
    Pallas paged-attention and BSN kernels, and it fits the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, cache = _model(one_chip, "sc_int_approx", "sc")
    vec = _sds(one_chip, (SLOTS,), jnp.int32)
    dispatch.clear_resolved()
    c = _compile(partial(paged_decode_step, cfg=cfg), params, cache, vec,
                 vec, _sds(one_chip, (SLOTS, MAXP), jnp.int32), vec,
                 donate_argnums=(1,))
    assert set(dispatch.resolved_backends()) == {
        ("paged_attn_decode", "pallas"), ("approx_bsn", "pallas")}
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15 * GiB


def test_multichunk_prefill_temp_does_not_grow(one_chip, monkeypatch):
    """A 4-request group with a 1000-token prompt (L=1024, 16 chunks of
    64) needs no more temp than 2 chunks: the rolled chunk loop carries
    the pools in place.  What a loop of chunks adds over one chunk is a
    constant: one copy of granite's bf16 pools in the loop's (8, 128)
    tiling, where D=64 fills half of each 128-lane tile, so twice the
    pools' 1.34 GB."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params, cache = _model(one_chip, "qat", "fp")
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(cache) if a.ndim == 5)
    temp = {}
    for n_chunks in (1, 2, 16):
        lanes = _sds(one_chip, (4,), jnp.int32)
        c = _compile(lambda p, c, t, tb, pl, sl: paged_prefill(
            p, c, t, tb, pl, cfg, chunk=64, slot_ids=sl),
            params, cache, _sds(one_chip, (4, 64 * n_chunks), jnp.int32),
            _sds(one_chip, (4, MAXP), jnp.int32), lanes, lanes,
            donate_argnums=(1,))
        assert "tpu_custom_call" in c.as_text()
        temp[n_chunks] = c.memory_analysis().temp_size_in_bytes
    assert temp[1] < GiB // 4, temp
    assert temp[2] - temp[1] <= 2 * pool_bytes + 64 * 2 ** 20, temp
    assert temp[16] <= temp[2] + 16 * 2 ** 20, temp
    assert temp[16] < 3 * GiB, temp


# ---------------------------------------------------------------------------
# chip_smoke's phase logic, on the CPU at a tiny config
# ---------------------------------------------------------------------------

def _chip_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(root, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("datapath,fmt", [("qat", "fp"), ("sc_int", "int8"),
                                          ("sc_int_approx", "sc")])
def test_chip_smoke_phase_on_cpu(datapath, fmt):
    smoke = _chip_smoke()
    cfg = GRANITE.scaled(n_layers=2, d_model=64, n_heads=8, n_kv_heads=2,
                         d_ff=128, vocab_size=64, vocab_pad_multiple=32,
                         dtype="float32", attn_q_chunk=8)
    params = init_params(jax.random.key(0), cfg)
    prompts = smoke.make_prompts(0, cfg.vocab_size, lens=(3, 9, 17, 30))
    rec = smoke.run_phase(params, cfg, datapath=datapath, kv_format=fmt,
                          prompts=prompts, seed=0, max_new_tokens=4,
                          max_len=64, page_size=8, prefill_chunk=16,
                          kernel_backend="pallas-interpret")
    assert rec["tokens"] == 4 * len(prompts)
    assert {k.split(":")[1] for k in rec["backends"]} == {"pallas-interpret"}
    if datapath != "qat":
        assert rec["oracle_matched_tokens"] == rec["tokens"]
