"""The dense decoder family: every layer is attention plus a gated MLP
(granite, stablelm).  A configuration file that names no ``family`` is
of this one.

A family is a module ``bench/families/<family>.py``, found by the name
a configuration file gives under ``family`` (bench/spec.family), and
exports:

* ``model_config(cf)``: the program's ModelConfig for a configuration
  file, refusing a file that the program's arch does not describe;
* ``Dims(config, padded_vocab)``: sizes read from the file's ``config``,
  with at least ``layers``, ``d``, ``vocab`` and, for the paged-attention
  costs, ``attn_layers`` (the layers that attend), ``hq``, ``hkv``,
  ``dh``;
* ``program_params(seed, dims, alpha_a, dtype, abstract)``: the engine's
  parameter tree, made on the device from the seed in one jitted call
  and checked leaf for leaf against ``abstract``;
* ``Reference(dims, alpha_a, seed, wdtype, act_dtype, kv_int8,
  attention, page)``: the family's plain reference, whose
  ``readings(seqs, targets, pad_to)`` bench/check.py calls;
* ``prefill_ops(dims, prompt_len)``, ``decode_ops(dims, length)``: model
  operations of a prompt and of one decode token (``mfu``);
* ``projection_params(dims)``, ``head_params(dims)``,
  ``projection_io_bytes(dims)``: the SC projections' weights over all
  layers, the LM head's, and the int8 activation bytes one token moves
  through all layers' projections (bench/cost/sc_proj.py);
* ``KERNELS``: kernel names, beside the harness's own two, whose device
  time bench/trace.py reports under ``kernel_s``.

This one adapts bench/weights.py, bench/reference/decoder.py and
bench/cost/model.py, where the arithmetic lives.
"""

from __future__ import annotations

from bench.cost.model import decode_ops, head_params, prefill_ops
from bench.cost.model import layer_params as projection_params
from bench.cost.sc_proj import layer_io_bytes
from bench.reference.decoder import Reference
from bench.weights import Dims, program_params

__all__ = ["KERNELS", "model_config", "Dims", "program_params",
           "Reference", "prefill_ops", "decode_ops", "projection_params",
           "head_params", "projection_io_bytes"]

KERNELS = ()


def model_config(cf: dict):
    """The program's ModelConfig for a configuration file: the registered
    arch with every size the file states."""
    from repro.configs import get_arch
    c = cf["config"]
    arch = get_arch(cf["arch"])
    cfg = arch.scaled(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        rope_fraction=c.get("partial_rotary_factor", 1.0),
        dtype=c["torch_dtype"])
    norm = "layernorm" if "layer_norm_eps" in c else "rmsnorm"
    if cfg.norm != norm or cfg.tie_embeddings or cfg.padded_vocab \
            != cf["padded_vocab"] or not cfg.ffn_gated \
            or cfg.ffn_act != c["hidden_act"]:
        raise ValueError(f"{cf['name']}: the file does not describe arch "
                         f"{cf['arch']} (norm, tying, vocab padding or MLP)")
    return cfg


def projection_io_bytes(d) -> int:
    return d.layers * layer_io_bytes(d)
