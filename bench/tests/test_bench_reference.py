"""The plain reference against the engine at a tiny size (interpret-mode
kernels): a whole run's check reads no gap for a granite-shaped and a
stablelm-shaped configuration, the weights the engine gets in one
stacked call are the ones the reference regenerates, and the paged
attention the chip's check uses is the exact one when its operands are
kept in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import PEAKS, TINY, TINY_LM

from bench import run
from bench.reference.decoder import ATTENTION
from bench.weights import Dims, layer_weights, program_params, seed_key


@pytest.mark.parametrize("cell", [TINY, TINY_LM])
def test_a_whole_run_is_correct_against_the_reference(tiny_root, cell):
    res = run.run_cell(cell, 2 ** 31 + 11, 1.5, False, PEAKS,
                       root=tiny_root)
    assert res["correct"] is True
    assert res["check"] == {"share_gap_over_0.25": {"value": 0.0,
                                                    "limit": 0.02}}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tps", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "check"


def test_stacked_weights_equal_the_per_layer_regeneration():
    dims = Dims({"num_hidden_layers": 3, "hidden_size": 32,
                 "num_attention_heads": 2, "num_key_value_heads": 1,
                 "head_dim": 16, "intermediate_size": 64,
                 "vocab_size": 40, "layer_norm_eps": 1e-6,
                 "rope_theta": 1e4}, 64)
    alpha = dict.fromkeys(("wq", "wk", "wv", "wo", "w_gate", "w_up",
                           "w_down", "lm_head"), 1.0)
    p = program_params(2 ** 33 + 7, dims, alpha, jnp.bfloat16)
    key = seed_key(2 ** 33 + 7)
    for layer in range(3):
        w = jax.jit(lambda k, l: layer_weights(k, l, dims, jnp.bfloat16))(
            key, jnp.int32(layer))
        blk = p["periods"]["p0"]
        np.testing.assert_array_equal(blk["mixer"]["wq"]["w"][layer],
                                      w["wq"])
        np.testing.assert_array_equal(blk["ffn"]["w_down"]["alpha_w"][layer],
                                      w["w_down_alpha_w"])
        np.testing.assert_array_equal(blk["norm1"]["bias"][layer],
                                      w["norm1_bias"])
    assert p["embed"]["table"].dtype == jnp.bfloat16


@pytest.mark.parametrize("hkv,group,n_valid", [(2, 2, 29), (4, 1, 32)])
def test_paged_attention_with_float32_operands_is_the_exact_one(
        hkv, group, n_valid):
    """``paged_bf16`` differs from ``exact`` only in the operands' bfloat16
    rounding: with float32 operands (``paged_f32``) the page-at-a-time
    softmax gives exact attention's output to float32 rounding."""
    T, dh, page = 32, 16, 8
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (T, hkv, group, dh))
    k = jax.random.normal(ks[1], (T, hkv, dh))
    v = jax.random.normal(ks[2], (T, hkv, dh))
    with jax.default_matmul_precision("highest"):
        outs = {name: fn(q, k, v, n_valid, page, operand)
                for name, (fn, operand) in ATTENTION.items()}
    np.testing.assert_allclose(outs["paged_f32"], outs["exact"],
                               rtol=2e-5, atol=2e-6)
    assert np.abs(np.asarray(outs["paged_bf16"] - outs["exact"])).max() \
        > 1e-4
