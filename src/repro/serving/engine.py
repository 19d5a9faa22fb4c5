"""Continuous-batching serve engine v2: paged KV cache, batched decode.

Execution model (vLLM-style, scaled to this zoo):

* **Paged KV.**  Attention KV lives in a flat pool of fixed-size pages
  shared by every request; a host-side free-list allocator
  (serving/paging.py) hands pages to requests and the device code
  gathers/scatters through per-request page tables
  (models/attention.py).  HBM cost is proportional to *tokens actually
  held*, not ``max_slots x max_len``, and admission never copies or
  re-layouts a cache — prefill writes the same pages decode reads.
* **One batched decode step.**  Every engine step runs ALL active slots
  through a single jitted ``paged_decode_step`` — one period-scan
  forward for the whole batch, mixed progress handled by per-slot
  lengths/page tables.  Recurrent mixers (mamba/rwkv) keep per-slot
  state rows gathered/scattered by slot id inside the same step.
* **Chunked prefill — one path for every arch.**  Admitted requests
  prefill as one padded batch, chunk by chunk, directly into the page
  pools (``paged_prefill``).  Attention positions scatter whole K/V
  pages; recurrent positions (mamba/rwkv6) thread chunk-resumable state
  (conv tail + SSM/WKV state + token shifts) across chunk boundaries
  and scatter the final carry into their per-slot rows, all inside the
  same jitted call.  The recurrence runs per-token during prefill, so
  any chunk size reproduces the exact-length result bit for bit —
  order-exactness is preserved, it no longer costs a second datapath.
  ``prefill_mode="exact"`` keeps the old per-request exact-length
  fallback alive as a DEBUG ORACLE only.
* **Bucketed shapes.**  The decode step is traced per (slot-bucket,
  page-bucket) — both padded to powers of two — so jax recompiles only
  when a bucket boundary is crossed, not on every admission/eviction.
  Padded lanes point at the scratch state row and the trash page; they
  cost FLOPs, never correctness.

* **Mesh-sharded decode (tensor parallel).**  ``ServeEngine(mesh_rules=
  launch.mesh.serving_rules(mesh))`` shards params with the serving
  layout (column-parallel projections over ``"model"``, whole experts
  per device via ``moe_spec(serving=True)``), the KV page pools over
  their KV-head axis, and recurrent state rows over their channel axis;
  the jitted steps trace under the rules so GSPMD keeps weights
  resident and moves only the (tiny) decode activations.  Host-side
  paging/slot bookkeeping never sees the mesh.  The layout shards
  output channels only — never a contraction dim — because the SC
  accumulators (exact and approximate BSN) are per-output-channel
  units: each channel's K-term accumulation stays device-local, so
  mesh-on decode is token-identical to mesh-off (and to
  ``sequential_generate``) on every datapath.  With ``mesh_rules=None``
  nothing here activates and behavior is exactly single-device.

* **Seeded sampling.**  Each request carries :class:`SamplingParams`
  (temperature / top-k / top-p / min-p / seed; ``temperature == 0`` is
  greedy, the default).  The controls are packed into flat per-lane
  tensors and the categorical draw happens INSIDE the jitted decode /
  prefill steps (serving/sampling.py) — one traced step still advances
  the whole batch, bucketed shapes unchanged, no host round-trip.  The
  per-request PRNG key is a pure function of ``(seed, position)``, so
  batched, preempted-and-resumed, mesh-sharded and
  ``sequential_generate`` decode all draw identical tokens.  Whether a
  batch samples at all is a STATIC jit flag: all-greedy batches compile
  the plain argmax step (zero sampler compute — the default workload
  costs what the pre-sampling engine cost).

Datapath: ``datapath="qat"`` serves the fake-quant QAT forward;
``"sc_int"`` re-quantizes every projection on the fly and runs the
silicon-equivalent int8 x ternary -> int32 path
(``core.sc_layers.sc_linear_int_from_qat``); ``"sc_int_approx"``
additionally routes the accumulation through the paper's approximate
BSN adder, which dispatches to the fused Pallas kernel via
kernels/dispatch.  As in v1, every traced entry point runs inside
``backend_scope(bsn_backend)`` — dispatch decisions are made at trace
time, so the scope must surround the *first* (tracing) call.

Attention backend: paged decode and chunked prefill route their
attention through the same dispatch module — ``attn_backend=None``
(auto) serves the flash-decoding Pallas kernel
(kernels/paged_attention.py; interpret mode off-TPU), ``"reference"``
pins the XLA gather/scatter oracle.  ``attn_backend_scope`` wraps the
traced calls exactly like the BSN scope.  Under ``mesh_rules`` the
engine always serves the constrained reference (the kernel is a
single-device program; KV heads stay device-local over "model", so
mesh-on output is token-identical to the kernel path) and pinning a
pallas backend is rejected.
"""

from __future__ import annotations

import contextlib
import itertools
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.kv_quant import kv_quant
from repro.distributed.sharding import MeshRules, mesh_rules, shard_tree
from repro.kernels import dispatch as kernel_dispatch
from repro.kernels.ref import to_pages
from repro.models import (decode_step, gather_state_rows, init_paged_cache,
                          paged_cache_specs, paged_decode_step,
                          paged_prefill, paged_verify_step, param_specs,
                          prefill, scatter_state_rows,
                          select_state_snapshot, supports_paged_prefill)

from .config import DATAPATHS, EngineConfig
from .paging import (TRASH_PAGE, PageAllocator, PageTable, pad_pow2,
                     pages_needed)
from .sampling import (SamplingParams, greedy_tokens, pack_sampling,
                       sample_tokens, speculative_accept, token_logprobs)

__all__ = ["Request", "SamplingParams", "ServeEngine", "EngineConfig",
           "DATAPATHS", "sequential_generate"]

# Every program of the engine and of its oracle rounds bf16 where the
# program says.  By default XLA may keep a fused bf16 intermediate in
# f32 (TPU fusions do), so a row's result would follow the fusion
# choices of the program it runs in, and a batched step would stop
# matching the one-request oracle.
STRICT_ROUNDING = {"xla_allow_excess_precision": False}
_jit = partial(jax.jit, compiler_options=STRICT_ROUNDING)

# What ServeEngine.stats counts, as plain integers since construction
# (serving/README.md, "Reading the engine"); spec_stats is the view of
# the spec_* ones.
COUNTERS = ("steps", "decode_steps", "decode_lanes", "decode_lanes_padded",
            "prefill_groups", "prefill_tokens", "prefill_tokens_padded",
            "admitted", "preempted", "truncated", "finished",
            "queue_depth_peak", "spec_rounds", "spec_draft_tokens",
            "spec_accepted_tokens", "spec_emitted_tokens")


def _rids(reqs) -> str:
    """Request ids as a span's metadata (TraceMe splits on commas)."""
    return " ".join(str(r.rid) for r in reqs)


def _cfg_for_datapath(cfg: ModelConfig, datapath: str) -> ModelConfig:
    if datapath not in DATAPATHS:
        raise ValueError(f"datapath must be one of {DATAPATHS}, "
                         f"got {datapath!r}")
    if datapath == "qat" or not cfg.quant.enabled:
        return cfg
    import dataclasses
    q = dataclasses.replace(cfg.quant, mode="sc_int",
                            int_approx=(datapath == "sc_int_approx"))
    return cfg.scaled(quant=q)


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int | None = None
    sampling: SamplingParams = field(default_factory=SamplingParams)
    generated: list[int] = field(default_factory=list)
    done: bool = False
    # one dict per generated token when sampling.logprobs > 0 (else
    # stays empty): {"logprob": float, "top": [(token, logprob), ...]}
    # with the top list cropped to sampling.logprobs entries, scored
    # under the distribution the token was drawn from (see
    # sampling.token_logprobs)
    logprobs: list[dict] = field(default_factory=list)
    # engine internals
    _table: PageTable | None = field(default=None, repr=False)
    _len: int = field(default=0, repr=False)      # tokens held in cache


class ServeEngine:
    """Construct with :meth:`from_config` (an :class:`EngineConfig` is
    the single validated construction path); the keyword signature below
    is the back-compat shim — it builds the same ``EngineConfig`` and
    delegates, so both spellings hit identical validation."""

    def __init__(self, params, cfg: ModelConfig, max_slots: int = 4,
                 max_len: int = 256, bsn_backend: str | None = None,
                 page_size: int = 16, num_pages: int | None = None,
                 prefill_chunk: int = 64, datapath: str = "qat",
                 mesh_rules: MeshRules | None = None,
                 prefill_mode: str = "chunked",
                 attn_backend: str | None = None,
                 kv_format: str = "fp",
                 spec_decode: bool = False,
                 draft_len: int = 4,
                 config: EngineConfig | None = None):
        assert not cfg.is_encoder, "encoders are served via forward()"
        if config is None:
            config = EngineConfig(
                max_slots=max_slots, max_len=max_len, page_size=page_size,
                num_pages=num_pages, prefill_chunk=prefill_chunk,
                datapath=datapath, kv_format=kv_format,
                bsn_backend=bsn_backend, attn_backend=attn_backend,
                prefill_mode=prefill_mode, mesh_rules=mesh_rules,
                spec_decode=spec_decode, draft_len=draft_len)
        config.validate()
        self.config = config
        mesh_rules = config.mesh_rules
        self.prefill_mode = config.prefill_mode
        self.bsn_backend = config.bsn_backend
        self.attn_backend = config.attn_backend
        self.cfg = _cfg_for_datapath(cfg, config.datapath)
        self.datapath = config.datapath
        self.kv_format = config.kv_format
        # speculative decoding: draft on the cheap approximate-BSN
        # datapath, verify on the request's target datapath (self.cfg).
        # cfg_draft shares the SAME params pytree — the datapaths are
        # one model at three fidelities — so spec costs no extra weights.
        self.spec_decode = config.spec_decode
        self.draft_len = config.draft_len
        self.cfg_draft = _cfg_for_datapath(cfg, "sc_int_approx")
        self._n = dict.fromkeys(COUNTERS, 0)
        self.max_slots, self.max_len = config.max_slots, config.max_len
        self.page_size = config.page_size
        self.max_pages = pages_needed(config.max_len, config.page_size)
        num_pages = config.num_pages
        if num_pages is None:
            # full residency for every slot + the reserved trash page
            num_pages = config.max_slots * self.max_pages + 1
        self.allocator = PageAllocator(num_pages)
        self._rid = itertools.count()
        self.queue: list[Request] = []
        self.slots: list[Request | None] = [None] * config.max_slots
        cache = init_paged_cache(self.cfg, config.max_slots, num_pages,
                                 config.page_size, config.kv_format)
        self._chunk = pad_pow2(max(config.prefill_chunk, config.page_size))

        # Mesh-sharded serving (tensor-parallel decode): params take the
        # serving layout (every projection column-parallel over "model",
        # experts whole-per-device — see models/attention.attn_spec),
        # KV page pools and recurrent state rows shard their head /
        # channel axes (models/transformer.paged_cache_specs), and every
        # traced entry point runs under the rules so the
        # with_sharding_constraint annotations resolve.  All HOST
        # bookkeeping (allocator, page tables, slots) is device-count-
        # agnostic — it never sees the mesh.  With mesh_rules=None this
        # block is dead and behavior is exactly single-device.
        self.rules = mesh_rules
        if mesh_rules is not None:
            params = shard_tree(params, param_specs(self.cfg, serving=True),
                                mesh_rules)
            cache = shard_tree(cache,
                               paged_cache_specs(self.cfg, self.kv_format),
                               mesh_rules, logical=True)
        self.params = params
        self.cache = cache

        # jitted entry points.  The decode cache is donated: page pools
        # are updated in place across steps instead of copied.  Under a
        # mesh, output shardings are pinned to the input cache layout so
        # every step reuses one compiled variant per shape bucket
        # (donation stays clean, no sharding ping-pong).
        jit_kw, spec_jit_kw = {}, {}
        self._cache_sh = None
        if mesh_rules is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._cache_sh = jax.tree.map(lambda a: a.sharding, self.cache)
            rep = NamedSharding(mesh_rules.mesh, P())
            # (tokens, cache, logprobs-or-()) — the sharding entries
            # broadcast as pytree prefixes, so the empty lp_k=0 tuple
            # contributes no leaves and the lp_k>0 triple pins replicated
            jit_kw["out_shardings"] = (rep, self._cache_sh, rep)
            spec_jit_kw["draft"] = {
                "out_shardings": (rep, self._cache_sh)}
            spec_jit_kw["verify"] = {
                "out_shardings": (rep, rep, self._cache_sh, rep)}
        self._decode = _jit(self._decode_fn, donate_argnums=(1,),
                            static_argnames=("do_sample", "lp_k"),
                            **jit_kw)
        self._prefill_batched = _jit(self._prefill_batched_fn,
                                     static_argnames=("chunk",
                                                      "do_sample",
                                                      "lp_k"),
                                     donate_argnums=(1,), **jit_kw)
        # The exact-prefill debug oracle is donation-EXEMPT by design
        # (analysis/contracts.audit_donation records the exemption): it
        # takes (params, batch) only and builds a fresh exact-length
        # cache, so there is no input cache buffer to alias an output
        # into — donating nothing is correct, not an oversight.
        self._prefill_exact = _jit(self._prefill_exact_fn,
                                   static_argnames=("do_sample",
                                                    "lp_k"))
        self._draft = _jit(self._draft_fn, donate_argnums=(1,),
                           static_argnames=("do_sample",),
                           **spec_jit_kw.get("draft", {}))
        self._verify = _jit(self._verify_fn, donate_argnums=(1,),
                            static_argnames=("do_sample", "lp_k"),
                            **spec_jit_kw.get("verify", {}))

    @classmethod
    def from_config(cls, params, cfg: ModelConfig,
                    config: EngineConfig) -> "ServeEngine":
        """The preferred construction path: every knob in one validated
        :class:`EngineConfig` (see serving/config.py for the rules)."""
        return cls(params, cfg, config=config)

    # -- traced bodies --------------------------------------------------
    #
    # The categorical draw lives INSIDE each traced body: the logits
    # never leave the device, and the ``samp`` tensors follow the lane
    # bucket shape so sampling adds zero retraces within a mode.  Draw
    # positions are the fold-in counters of the (seed, position)
    # streams — the decode step writes its input token at ``lengths``,
    # so the token it draws sits at sequence index ``lengths + 1``;
    # prefill draws the first generated token at index ``prompt_len``.
    #
    # ``do_sample`` is a STATIC flag, true iff some live lane has
    # temperature > 0: an all-greedy batch (the default workload)
    # compiles the plain argmax step with zero sampler compute — no
    # sorts, no RNG — exactly the pre-sampling engine.  Worst case this
    # doubles the compiled variants per shape bucket (greedy + sampled);
    # temperature=0 lanes inside a sampled batch take the in-trace
    # greedy branch of ``sample_tokens``, which is bit-identical, so
    # batch composition never changes anyone's tokens.

    # ``lp_k`` is the second static flag: the pow2-bucketed batch max of
    # SamplingParams.logprobs.  lp_k == 0 (the default workload, and the
    # only value the analysis gate traces) compiles the historical step
    # byte for byte — token_logprobs (log_softmax + top_k sorts) never
    # enters the jaxpr, which test_spec_decode pins via the dot-profile
    # snapshot.  The lp slot is an EMPTY tuple then, so output pytrees
    # and out_shardings stay aligned across both variants.

    def _pick(self, logits, positions, samp, do_sample):
        """The in-jit token pick of every traced body."""
        with jax.named_scope("sampler"):
            if do_sample:
                return sample_tokens(logits, positions, samp,
                                     self.cfg.vocab_size)
            return greedy_tokens(logits, self.cfg.vocab_size)

    def _decode_fn(self, params, cache, tokens, slot_ids, tables, lengths,
                   samp, *, do_sample, lp_k=0):
        logits, cache = paged_decode_step(params, cache, tokens,
                                          slot_ids, tables, lengths,
                                          self.cfg)
        nxt = self._pick(logits, lengths + 1, samp, do_sample)
        lp = token_logprobs(logits, nxt, samp, self.cfg.vocab_size,
                            lp_k) if lp_k else ()
        return nxt, cache, lp

    def _prefill_batched_fn(self, params, cache, tokens, tables, lens,
                            slot_ids, samp, *, chunk, do_sample, lp_k=0):
        logits, cache = paged_prefill(params, cache, tokens, tables,
                                      lens, self.cfg, chunk=chunk,
                                      slot_ids=slot_ids)
        nxt = self._pick(logits, lens, samp, do_sample)
        lp = token_logprobs(logits, nxt, samp, self.cfg.vocab_size,
                            lp_k) if lp_k else ()
        return nxt, cache, lp

    def _prefill_exact_fn(self, params, batch, samp, *, do_sample,
                          lp_k=0):
        logits, cache = prefill(params, batch, self.cfg)
        plen = logits.shape[1]                    # static: exact length
        pos = jnp.full((1,), plen, jnp.int32)
        tok = self._pick(logits[:, -1], pos, samp, do_sample)
        lp = token_logprobs(logits[:, -1], tok, samp,
                            self.cfg.vocab_size, lp_k) if lp_k else ()
        return tok[0], cache, lp

    # -- speculative decoding (draft on sc_int_approx, verify on the
    #    target datapath) ------------------------------------------------
    #
    # One spec round = TWO jit dispatches for up to draft_len + 1
    # committed tokens:
    #
    # 1. _draft_fn: an in-jit scan of `draft_len` single-token decode
    #    steps on cfg_draft (the paper's approximate-BSN path), sharing
    #    the target's params AND paged cache.  The draft's K/V writes at
    #    positions len..len+k-1 are dead (the verify scatter overwrites
    #    every one before any read can see them: they sit past the
    #    committed length until then), and the recurrent state rows are
    #    checkpointed before / restored after, so approximate arithmetic
    #    never leaks into target state.
    # 2. _verify_fn: ONE parallel multi-token target forward over the
    #    window [t0, d_1..d_k] (paged_verify_step), drawing the target
    #    token tau_t at every window position from the SAME
    #    (seed, position) Gumbel stream the draft used.  The accepted
    #    prefix is simply where draft == target (shared noise makes the
    #    classic accept/resample rule collapse to token equality), and
    #    the engine always emits TARGET draws — so spec-on output is
    #    bit-identical to spec-off by construction, not just equal in
    #    distribution.

    def _draft_fn(self, params, cache, tokens, slot_ids, tables, lengths,
                  samp, *, do_sample):
        rows0 = gather_state_rows(cache, slot_ids)

        def body(carry, t):
            cache, tok = carry
            logits, cache = paged_decode_step(params, cache, tok,
                                              slot_ids, tables,
                                              lengths + t, self.cfg_draft)
            nxt = self._pick(logits, lengths + 1 + t, samp, do_sample)
            return (cache, nxt), nxt

        (cache, _), drafts = jax.lax.scan(
            body, (cache, tokens),
            jnp.arange(self.draft_len, dtype=jnp.int32))
        cache = scatter_state_rows(cache, rows0, slot_ids)
        return jnp.moveaxis(drafts, 0, 1), cache          # (S, k)

    def _verify_fn(self, params, cache, tokens, drafts, slot_ids, tables,
                   lengths, samp, *, do_sample, lp_k=0):
        win = jnp.concatenate([tokens[:, None], drafts], axis=1)
        logits, cache, snaps = paged_verify_step(
            params, cache, win, slot_ids, tables, lengths, self.cfg)
        S, T, V = logits.shape
        flat = logits.reshape(S * T, V)
        # row (s, t) draws the token at sequence index lengths[s]+1+t —
        # the very fold-in counters non-speculative decode would use
        pos = (lengths[:, None] + 1
               + jnp.arange(T, dtype=jnp.int32)[None, :]).reshape(-1)
        sampf = {k: jnp.repeat(v, T) for k, v in samp.items()}
        tau = self._pick(flat, pos, sampf, do_sample).reshape(S, T)
        m = speculative_accept(drafts, tau[:, :T - 1])    # (S,)
        cache = scatter_state_rows(
            cache, select_state_snapshot(snaps, m), slot_ids)
        if lp_k:
            chosen, ids, lps = token_logprobs(
                flat, tau.reshape(-1), sampf, self.cfg.vocab_size, lp_k)
            lp = (chosen.reshape(S, T), ids.reshape(S, T, lp_k),
                  lps.reshape(S, T, lp_k))
        else:
            lp = ()
        return tau, m, cache, lp

    @contextlib.contextmanager
    def _scope(self):
        """Every traced call runs here: BSN and paged-attention backend
        dispatch happens at trace time, and the mesh rules must be
        active so logical-axis constraints resolve (all are no-ops when
        unset)."""
        with kernel_dispatch.backend_scope(self.bsn_backend), \
                kernel_dispatch.attn_backend_scope(self.attn_backend):
            if self.rules is None:
                yield
            else:
                with mesh_rules(self.rules):
                    yield

    # -- submission -----------------------------------------------------
    def submit(self, prompt: list[int], max_new_tokens: int = 16,
               eos_id: int | None = None,
               sampling: SamplingParams | None = None) -> int:
        if len(prompt) == 0:
            # an empty prompt would reach prefill as a (1, 0) token batch
            # and fail deep inside the model (rope/scan over S=0);
            # sequential_generate has no first-token logit either — fail
            # loudly at the API boundary instead.
            raise ValueError("empty prompt: need at least one token")
        if max_new_tokens < 1:
            # a <= 0 budget used to be admitted anyway: _check_done only
            # runs AFTER a token lands, so the request produced one token
            # the caller never asked for (and the slot/pages were held
            # for a full prefill + decode round-trip meanwhile)
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if len(prompt) > self.max_len - 1:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"max_len={self.max_len}")
        need = pages_needed(len(prompt) + 1, self.page_size)
        if need > self.allocator.num_pages - 1:
            # would never admit, not even with an empty pool
            raise ValueError(f"prompt needs {need} pages but the pool "
                             f"holds {self.allocator.num_pages - 1}")
        r = Request(next(self._rid), list(prompt), max_new_tokens, eos_id,
                    sampling if sampling is not None else SamplingParams())
        self.queue.append(r)
        self._note_queue()
        return r.rid

    def _note_queue(self):
        self._n["queue_depth_peak"] = max(self._n["queue_depth_peak"],
                                          len(self.queue))

    def _free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    # -- admission ------------------------------------------------------
    def _admit(self):
        with TraceAnnotation("engine.admit") as span:
            group: list[tuple[int, Request]] = []
            while self.queue:
                slot = self._free_slot()
                if slot is None:
                    break
                req = self.queue[0]
                table = PageTable(self.page_size)
                # reserve prompt pages + the first decode write up front
                if not table.ensure(len(req.prompt) + 1, self.allocator):
                    break                         # pool pressure: wait
                self.queue.pop(0)
                req._table, req._len = table, len(req.prompt)
                self.slots[slot] = req
                group.append((slot, req))
            if not group:
                return
            self._n["admitted"] += len(group)
            span.set_metadata(rids=_rids(r for _, r in group))
            if supports_paged_prefill(self.cfg) \
                    and self.prefill_mode == "chunked":
                self._prefill_group(group)
            else:
                for _, r in group:
                    self._prefill_one(r)

    def _prefill_group(self, group: list[tuple[int, Request]]):
        """Batched chunked prefill: one padded (G, L) bucket.  Like the
        decode step, every shape is a pow2 bucket (group size, prompt
        length, table width) so admission retraces only on bucket
        changes; padded lanes are all-trash tables + zero lengths +
        the scratch state row."""
        reqs = [r for _, r in group]
        rids = _rids(reqs)
        plens = [len(r.prompt) for r in reqs]
        G = pad_pow2(len(reqs), hi=self.max_slots)
        L = pad_pow2(max(plens), lo=self.page_size)
        self._count_prefill(plens, G * L)
        with TraceAnnotation("engine.prefill", rids=rids):
            chunk = min(self._chunk, L)
            width = pad_pow2(max(L // self.page_size,
                                 max(len(r._table.pages) for r in reqs)))
            tokens = np.zeros((G, L), np.int32)
            tables = np.full((G, width), TRASH_PAGE, np.int32)
            lens = np.zeros((G,), np.int32)
            slot_ids = np.full((G,), self.max_slots, np.int32)  # scratch
            for g, (slot, r) in enumerate(group):
                tokens[g, :plens[g]] = r.prompt
                tables[g] = r._table.padded(width)
                lens[g] = plens[g]
                slot_ids[g] = slot
            samp = pack_sampling([r.sampling for r in reqs], pad_to=G)
            do_sample = any(not r.sampling.greedy for r in reqs)
            lp_k = self._lp_bucket(reqs)
            with self._scope():
                nxt, self.cache, lp = self._prefill_batched(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(tables), jnp.asarray(lens),
                    jnp.asarray(slot_ids), samp, chunk=chunk,
                    do_sample=do_sample, lp_k=lp_k)
        with TraceAnnotation("engine.prefill.sync", rids=rids):
            lp = jax.device_get(lp) if lp_k else None
            for g, r in enumerate(reqs):
                r.generated.append(int(nxt[g]))
                if lp is not None and r.sampling.logprobs > 0:
                    r.logprobs.append(self._lp_record(
                        lp[0][g], lp[1][g], lp[2][g], r.sampling.logprobs))
                self._check_done(r)

    def _count_prefill(self, plens, padded: int):
        n = self._n
        n["prefill_groups"] += 1
        n["prefill_tokens"] += sum(plens)
        n["prefill_tokens_padded"] += padded

    def _check_done(self, r: Request):
        """THE stop rule (the only copy: prefill and decode both route
        here).  Mirrors ``sequential_generate``'s loop condition — it
        keeps decoding while ``len(gen) < max_new_tokens and length <
        max_len - 1 and gen[-1] != eos`` — so a request stops after the
        token that makes any of the three false."""
        hit_eos = r.eos_id is not None and r.generated \
            and r.generated[-1] == r.eos_id
        if hit_eos or len(r.generated) >= r.max_new_tokens \
                or r._len >= self.max_len - 1:
            r.done = True

    def _prefill_one(self, req: Request):
        """Exact-length per-request prefill + eager scatter into the
        paged layout.  No longer any arch's hot path: the chunked paged
        prefill is order-exact for recurrent mixers too.  Kept as (a)
        the ``prefill_mode="exact"`` DEBUG ORACLE — it reproduces the
        chunked path token for token, which the tests assert — and (b)
        the route for frontend archs, whose inputs aren't token
        prompts (``supports_paged_prefill`` is False)."""
        rids = _rids([req])
        self._count_prefill([len(req.prompt)], len(req.prompt))
        with TraceAnnotation("engine.prefill", rids=rids):
            toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            samp = pack_sampling([req.sampling])
            lp_k = self._lp_bucket([req])
            with self._scope():
                tok, cache_one, lp = self._prefill_exact(
                    self.params, {"tokens": toks}, samp,
                    do_sample=not req.sampling.greedy, lp_k=lp_k)
            self._scatter_prefill(req, cache_one)
        with TraceAnnotation("engine.prefill.sync", rids=rids):
            req.generated.append(int(tok))
            if lp_k and req.sampling.logprobs > 0:
                lp = jax.device_get(lp)
                req.logprobs.append(self._lp_record(
                    lp[0][0], lp[1][0], lp[2][0], req.sampling.logprobs))
            self._check_done(req)

    def _scatter_prefill(self, req: Request, cache_one: dict):
        """Write a (B=1, exact-length) prefill cache into pages/rows.

        Compressed caches quantize here too (``kv_quant`` on the dense
        K/V rows, then pad + page-scatter codes/scales/residuals with the
        same indices): quantization is per-position and elementwise, so
        this exact oracle produces bit-identical pool contents to the
        chunked path's quantize-on-scatter."""
        plen = len(req.prompt)
        page = self.page_size
        npg = pages_needed(plen, page)
        phys = jnp.asarray(req._table.pages[:npg], jnp.int32)
        row = self.slots.index(req)
        periods = dict(self.cache["periods"])
        for i in range(len(self.cfg.period)):
            key = f"p{i}"
            entry = dict(periods[key])
            one = cache_one["periods"][key]
            for name, val in one.items():       # leaves: (P, 1, ...)
                if name in ("k", "v"):          # (P, 1, plen, Hkv, Dh)
                    qd = kv_quant(val[:, 0], self.kv_format)
                    stores = {name + "_pages": qd["q"]}
                    if "scale" in qd:
                        stores[name + "_scale"] = qd["scale"]
                    if "resid" in qd:
                        stores[name + "_resid"] = qd["resid"]
                    for pool_name, sv in stores.items():
                        pads = [(0, 0)] * sv.ndim
                        pads[1] = (0, npg * page - plen)
                        sv = to_pages(jnp.pad(sv, pads), page)
                        pool = entry[pool_name]
                        entry[pool_name] = pool.at[:, phys].set(
                            sv.astype(pool.dtype))
                else:                           # recurrent state rows
                    entry[name] = jax.tree.map(
                        lambda full, o: full.at[:, row].set(
                            o[:, 0].astype(full.dtype)),
                        entry[name], val)
            periods[key] = entry
        cache = {"periods": periods}
        if self._cache_sh is not None:
            # the eager scatters above leave GSPMD-inferred shardings on
            # the touched leaves; re-pin to the init-time layout so the
            # next decode step's donation (out_shardings pinned at
            # __init__) stays clean instead of copying the whole cache
            cache = jax.device_put(cache, self._cache_sh)
        self.cache = cache

    # -- logprobs -------------------------------------------------------
    @staticmethod
    def _lp_bucket(reqs) -> int:
        """The static top-k width traced into the step: the batch max of
        SamplingParams.logprobs, pow2-padded so requests asking for 3 vs
        4 top entries share a compiled variant.  0 (nobody asked) keeps
        the historical step — no sampler/sort compute in the jaxpr."""
        m = max((r.sampling.logprobs for r in reqs), default=0)
        return pad_pow2(m) if m else 0

    @staticmethod
    def _lp_record(chosen, ids, lps, n: int) -> dict:
        """Crop one lane's device logprob row to the request's own
        ``logprobs=N`` ask (the traced width is the batch bucket)."""
        return {"logprob": float(chosen),
                "top": [(int(t), float(p))
                        for t, p in zip(ids[:n], lps[:n])]}

    # -- stepping -------------------------------------------------------
    def _packed_sampling(self, active: list[int], Sb: int) -> dict:
        """Per-lane sampling tensors for the decode step.  They are
        constant for a given lane composition, so re-pack (5 host
        builds + uploads) only when admission/eviction/preemption
        changes which request rides which lane — not every token."""
        key = (tuple(self.slots[i].rid for i in active), Sb)
        if getattr(self, "_samp_key", None) != key:
            self._samp_key = key
            self._samp_packed = pack_sampling(
                [self.slots[i].sampling for i in active], pad_to=Sb)
        return self._samp_packed

    def _grow_or_preempt(self, active: list[int]) -> list[int]:
        """Make sure every active slot can take one more token; preempt
        the youngest request (free pages, requeue for re-prefill) under
        pool pressure.  Decode is deterministic — greedy trivially, and
        seeded sampling because its PRNG streams are keyed by (seed,
        position) only — so a preempted request regenerates the same
        tokens after re-admission."""
        for i in list(active):
            r = self.slots[i]
            if r is None or r.done:   # preempted / finished at prefill
                continue
            while not r._table.ensure(r._len + 1, self.allocator):
                victims = sorted((j for j in active if j != i),
                                 key=lambda j: self.slots[j].rid)
                if not victims:
                    # nothing left to evict: finish truncated
                    r.done = True
                    self._n["truncated"] += 1
                    break
                v = victims[-1]
                self._n["preempted"] += 1
                vr = self.slots[v]
                vr._table.release(self.allocator)
                vr._table, vr._len = None, 0
                vr.generated = []
                vr.logprobs = []
                self.queue.insert(0, vr)
                self._note_queue()
                self.slots[v] = None
                active.remove(v)
        return [i for i in active
                if self.slots[i] is not None and not self.slots[i].done]

    def _sweep_done(self, done: list[Request]) -> None:
        for i, r in enumerate(self.slots):
            if r is not None and r.done:
                r._table.release(self.allocator)
                r._table = None
                done.append(r)
                self.slots[i] = None
                self._n["finished"] += 1

    def _step_batch(self, active: list[int]):
        """The shared (Sb, maxp) pow2-bucketed lane tensors every decode
        variant (plain and speculative) feeds from."""
        Sb = pad_pow2(len(active), hi=self.max_slots)
        maxp = pad_pow2(max(len(self.slots[i]._table.pages)
                            for i in active))
        tokens = np.zeros((Sb,), np.int32)
        slot_ids = np.full((Sb,), self.max_slots, np.int32)  # scratch
        tables = np.full((Sb, maxp), TRASH_PAGE, np.int32)
        lengths = np.zeros((Sb,), np.int32)
        for lane, i in enumerate(active):
            r = self.slots[i]
            tokens[lane] = r.generated[-1]
            slot_ids[lane] = i
            tables[lane] = r._table.padded(maxp)
            lengths[lane] = r._len
        samp = self._packed_sampling(active, Sb)
        do_sample = any(not self.slots[i].sampling.greedy for i in active)
        lp_k = self._lp_bucket([self.slots[i] for i in active])
        return (jnp.asarray(tokens), jnp.asarray(slot_ids),
                jnp.asarray(tables), jnp.asarray(lengths), samp,
                do_sample, lp_k)

    def _ensure_spec_window(self, active: list[int]) -> bool:
        """All-or-nothing capacity check for ONE speculative round: every
        active lane must fit ``draft_len + 1`` more cache positions
        (window writes land at ``_len .. _len + draft_len``) and grow its
        page table WITHOUT preemption.  On any failure the step falls
        back to plain one-token decode — speculation is an optimization
        and must never evict work the plain path would have kept.  (A
        lane that grew some pages before a later lane failed keeps them:
        ``ensure`` is monotone and the pages stay owned by its table,
        used by the very next +1 growth or released at completion.)"""
        k = self.draft_len
        if any(self.slots[i]._len + k > self.max_len - 1 for i in active):
            return False
        return all(self.slots[i]._table.ensure(
            self.slots[i]._len + k + 1, self.allocator) for i in active)

    def _spec_round(self, active: list[int]):
        """Draft ``draft_len`` tokens on sc_int_approx, verify in one
        parallel target step, commit the accepted prefix + bonus token.
        Emitted tokens are always the target's own (seed, position) draws
        (see the traced-body comment), so requests cannot tell this path
        from plain decode — only the step count can."""
        n = self._n
        with TraceAnnotation("engine.spec.prepare"):
            tokens, slot_ids, tables, lengths, samp, do_sample, lp_k = \
                self._step_batch(active)
        with TraceAnnotation("engine.spec.dispatch"), self._scope():
            drafts, self.cache = self._draft(
                self.params, self.cache, tokens, slot_ids, tables,
                lengths, samp, do_sample=do_sample)
            tau, m, self.cache, lp = self._verify(
                self.params, self.cache, tokens, drafts, slot_ids,
                tables, lengths, samp, do_sample=do_sample, lp_k=lp_k)
        with TraceAnnotation("engine.spec.sync"):
            tau, m = np.asarray(tau), np.asarray(m)
            lp = jax.device_get(lp) if lp_k else None
        n["spec_rounds"] += 1
        n["spec_draft_tokens"] += self.draft_len * len(active)
        with TraceAnnotation("engine.spec.commit"):
            for lane, i in enumerate(active):
                r = self.slots[i]
                n["spec_accepted_tokens"] += int(m[lane])
                for j in range(int(m[lane]) + 1):
                    r.generated.append(int(tau[lane, j]))
                    r._len += 1
                    if lp is not None and r.sampling.logprobs > 0:
                        r.logprobs.append(self._lp_record(
                            lp[0][lane, j], lp[1][lane, j],
                            lp[2][lane, j], r.sampling.logprobs))
                    n["spec_emitted_tokens"] += 1
                    self._check_done(r)
                    if r.done:
                        break

    def _decode_round(self, active: list[int]):
        """One batched decode step: every active lane takes one token."""
        n = self._n
        with TraceAnnotation("engine.decode.prepare"):
            tokens, slot_ids, tables, lengths, samp, do_sample, lp_k = \
                self._step_batch(active)
        with TraceAnnotation("engine.decode.dispatch"), self._scope():
            nxt, self.cache, lp = self._decode(
                self.params, self.cache, tokens, slot_ids, tables,
                lengths, samp, do_sample=do_sample, lp_k=lp_k)
        with TraceAnnotation("engine.decode.sync"):
            nxt = np.asarray(nxt)
            lp = jax.device_get(lp) if lp_k else None
        n["decode_steps"] += 1
        n["decode_lanes"] += len(active)
        n["decode_lanes_padded"] += len(nxt)
        with TraceAnnotation("engine.decode.commit"):
            for lane, i in enumerate(active):
                r = self.slots[i]
                r.generated.append(int(nxt[lane]))
                r._len += 1
                if lp is not None and r.sampling.logprobs > 0:
                    r.logprobs.append(self._lp_record(
                        lp[0][lane], lp[1][lane], lp[2][lane],
                        r.sampling.logprobs))
                self._check_done(r)

    @property
    def stats(self) -> dict:
        """The engine's counters since construction (``COUNTERS``), plus
        the page pool's ``pages_in_use_peak`` and ``pages_total`` (pages
        it can hand out, the trash page excluded).  ``*_padded`` count
        the bucketed shapes the device ran: prefill ``G x L`` per group,
        decode ``Sb`` lanes per step; speculative rounds count under
        ``spec_*``, not ``decode_*``."""
        return {**self._n, "pages_in_use_peak": self.allocator.peak_in_use,
                "pages_total": self.allocator.capacity}

    @property
    def spec_stats(self) -> dict:
        """Speculative-decoding counters since construction.
        ``acceptance_rate`` = accepted drafts / drafted tokens;
        ``tokens_per_round`` = committed tokens per verify forward — the
        verifier-side speedup (each round costs ONE target-model
        multi-token step, so this is the decode-steps-saved factor on
        hardware where the drafter is cheap)."""
        n = self._n
        return {
            "rounds": n["spec_rounds"],
            "draft_tokens": n["spec_draft_tokens"],
            "accepted_tokens": n["spec_accepted_tokens"],
            "emitted_tokens": n["spec_emitted_tokens"],
            "acceptance_rate": (n["spec_accepted_tokens"]
                                / max(n["spec_draft_tokens"], 1)),
            "tokens_per_round": (n["spec_emitted_tokens"]
                                 / max(n["spec_rounds"], 1)),
        }

    def step(self) -> list[Request]:
        """Admit + ONE batched decode step (speculative round when
        ``spec_decode`` is on and every lane has window headroom).
        Returns finished requests.  Each phase runs in a host span
        (``engine.*``, serving/README.md) on the profiler's clock."""
        with TraceAnnotation("engine.step"):
            self._n["steps"] += 1
            self._admit()
            done: list[Request] = []
            # requests finished at prefill free their pages BEFORE growth,
            # so they are never preemption victims and their pages count
            # toward this step's headroom
            with TraceAnnotation("engine.sweep"):
                self._sweep_done(done)
            active = [i for i, r in enumerate(self.slots) if r is not None]
            spec = False
            if self.spec_decode and active:
                with TraceAnnotation("engine.spec.grow"):
                    spec = self._ensure_spec_window(active)
            if spec:
                self._spec_round(active)
            else:
                with TraceAnnotation("engine.grow"):
                    active = self._grow_or_preempt(active)
                if active:
                    self._decode_round(active)
            with TraceAnnotation("engine.sweep"):
                self._sweep_done(done)      # decode-finished + truncated
        return done

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        out = []
        for _ in range(max_steps):
            out += self.step()
            if not self.queue and all(s is None for s in self.slots):
                break
        return out


# ---------------------------------------------------------------------------
# sequential reference (the seed engine's execution model)
# ---------------------------------------------------------------------------

def _pad_prefill_cache(cache_one: dict, max_len: int) -> dict:
    def fit(path, one):
        names = [getattr(p, "key", None) for p in path]
        if names and names[-1] in ("k", "v") and one.ndim == 5:
            pad = [(0, 0)] * one.ndim
            pad[2] = (0, max_len - one.shape[2])
            one = jnp.pad(one, pad)
        return one
    return jax.tree_util.tree_map_with_path(fit, cache_one)


def sequential_generate(params, cfg: ModelConfig, prompts: list[list[int]],
                        max_new_tokens: int = 16, eos_id: int | None = None,
                        max_len: int = 256, bsn_backend: str | None = None,
                        datapath: str = "qat",
                        sampling: SamplingParams | list[SamplingParams]
                        | None = None,
                        kv_format: str = "fp",
                        page_size: int = 8) -> list[list[int]]:
    """Per-request prefill + one-token-at-a-time decode — the seed
    engine's per-slot execution model.

    This is the reference oracle: the batched paged engine must produce
    these tokens exactly (tests/test_paged_kv.py, test_sampling.py) and
    beat this loop's throughput (benchmarks/bench_serving.py).  Stop
    conditions mirror ``ServeEngine.step``.  ``sampling`` is one
    :class:`SamplingParams` for every prompt or a per-prompt list
    (default greedy); token picks route through the SAME
    ``sample_tokens`` the engine traces, at batch 1, with the same
    (seed, position) fold-in streams — position ``len(prompt) + n`` for
    the n-th generated token.

    ``kv_format="fp"`` runs the dense (un-paged) cache, bit-identical
    to the seed engine.  Compressed formats have no dense analogue (the
    codes live in page pools), so the oracle becomes a one-request-at-a-
    time PAGED loop: a private B=1 cache with an identity page table,
    one ``paged_prefill`` call, then per-token ``paged_decode_step`` —
    independent of the engine's allocator, bucketing, admission and
    batching (and of its ``page_size``: per-position quantization makes
    the codes page-layout-invariant), which is what makes the batched ==
    sequential differential meaningful for int8/sc too.
    """
    cfg = _cfg_for_datapath(cfg, datapath)
    sps = sampling if isinstance(sampling, list) \
        else [sampling] * len(prompts)
    if len(sps) != len(prompts):
        raise ValueError(f"sampling list has {len(sps)} entries for "
                         f"{len(prompts)} prompts")
    # None entries mean greedy, same as ServeEngine.submit(sampling=None)
    sps = [sp if sp is not None else SamplingParams() for sp in sps]
    if kv_format != "fp":
        return _paged_sequential_generate(
            params, cfg, prompts, sps, max_new_tokens, eos_id, max_len,
            bsn_backend, kv_format, page_size)
    # params are explicit jit ARGUMENTS, matching the engine's traced
    # entry points: closure-captured params constant-fold differently in
    # XLA, and on the fake-quant lattice that 1-ulp drift can flip exact
    # argmax ties — the differential theorem needs both sides compiled
    # under the same discipline.
    prefill_fn = _jit(lambda p, b: prefill(p, b, cfg))
    decode_fn = _jit(lambda p, c, t: decode_step(p, c, t, cfg))
    sample_fn = _jit(
        lambda lg, pos, sm: sample_tokens(lg, pos, sm, cfg.vocab_size))
    greedy_fn = _jit(lambda lg: greedy_tokens(lg, cfg.vocab_size))
    outs = []
    with kernel_dispatch.backend_scope(bsn_backend):
        for prompt, sp in zip(prompts, sps):
            samp = pack_sampling([sp])

            def pick(lg, t):
                # greedy requests skip the sampler entirely, mirroring
                # the engine's static do_sample split
                if sp.greedy:
                    return int(greedy_fn(lg)[0])
                return int(sample_fn(lg, jnp.asarray([t], jnp.int32),
                                     samp)[0])

            toks = jnp.asarray(prompt, jnp.int32)[None, :]
            logits, cache = prefill_fn(params, {"tokens": toks})
            cache = _pad_prefill_cache(cache, max_len)
            length = len(prompt)
            gen = [pick(logits[:, -1], length)]
            while (len(gen) < max_new_tokens
                   and length < max_len - 1
                   and (eos_id is None or gen[-1] != eos_id)):
                tok = jnp.asarray([[gen[-1]]], jnp.int32)
                logits, cache = decode_fn(params, cache, tok)
                gen.append(pick(logits[:, 0], length + 1))
                length += 1
            outs.append(gen)
    return outs


@partial(_jit, static_argnames=("cfg", "chunk", "bsn_backend"))
def _oracle_paged_prefill(params, cache, tokens, tables, plen, slot_ids,
                          *, cfg: ModelConfig, chunk: int,
                          bsn_backend: str | None):
    """Module-level jit for the paged oracle's prefill, cached across
    prompts AND across ``sequential_generate`` calls — the per-prompt
    ``jax.jit(lambda ...)`` it replaces re-traced every single prompt
    (the retrace audit's first confirmed catch; see
    analysis/contracts.py).  Keyed on (cfg, chunk, backend) statics plus
    arg shapes; the BSN backend is static because dispatch decisions
    happen at trace time inside the scope, so each pinned backend must
    own its trace."""
    with kernel_dispatch.backend_scope(bsn_backend):
        return paged_prefill(params, cache, tokens, tables, plen, cfg,
                             chunk=chunk, slot_ids=slot_ids)


@partial(_jit, static_argnames=("cfg", "bsn_backend"))
def _oracle_paged_decode(params, cache, tok, slot_ids, tables, lengths,
                         *, cfg: ModelConfig, bsn_backend: str | None):
    """Module-level jit for the paged oracle's decode step (same caching
    rationale as :func:`_oracle_paged_prefill`)."""
    with kernel_dispatch.backend_scope(bsn_backend):
        return paged_decode_step(params, cache, tok, slot_ids, tables,
                                 lengths, cfg)


def _paged_sequential_generate(params, cfg: ModelConfig, prompts, sps,
                               max_new_tokens: int, eos_id: int | None,
                               max_len: int, bsn_backend: str | None,
                               kv_format: str,
                               page_size: int) -> list[list[int]]:
    """The B=1 paged oracle behind ``sequential_generate(kv_format=...)``:
    a private single-slot cache per request, identity page table (page
    ``j`` of the request lives at physical page ``j + 1``), one chunked
    ``paged_prefill`` covering the whole prompt, then one
    ``paged_decode_step`` per token.  No allocator, no bucketing, no
    admission — exactly the "one request at a time" semantics of the
    dense oracle, on the compressed pool layout."""
    assert supports_paged_prefill(cfg), \
        "compressed-KV sequential oracle needs token prompts"
    sample_fn = _jit(
        lambda lg, pos, sm: sample_tokens(lg, pos, sm, cfg.vocab_size))
    greedy_fn = _jit(lambda lg: greedy_tokens(lg, cfg.vocab_size))
    slot_ids = jnp.zeros((1,), jnp.int32)
    outs = []
    with kernel_dispatch.backend_scope(bsn_backend):
        for prompt, sp in zip(prompts, sps):
            samp = pack_sampling([sp])

            def pick(lg, t):
                if sp.greedy:
                    return int(greedy_fn(lg)[0])
                return int(sample_fn(lg, jnp.asarray([t], jnp.int32),
                                     samp)[0])

            # prompt pages + every decode write fit the identity table
            L = pad_pow2(max(len(prompt), page_size))
            maxp = max(pages_needed(max_len, page_size), L // page_size)
            cache = init_paged_cache(cfg, 1, maxp + 1, page_size,
                                     kv_format)
            tables = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None, :]
            toks = np.zeros((1, L), np.int32)
            toks[0, :len(prompt)] = prompt
            plen = jnp.asarray([len(prompt)], jnp.int32)
            logits, cache = _oracle_paged_prefill(
                params, cache, jnp.asarray(toks), tables, plen, slot_ids,
                cfg=cfg, chunk=L, bsn_backend=bsn_backend)
            length = len(prompt)
            gen = [pick(logits, length)]
            while (len(gen) < max_new_tokens
                   and length < max_len - 1
                   and (eos_id is None or gen[-1] != eos_id)):
                tok = jnp.asarray([gen[-1]], jnp.int32)
                lengths = jnp.asarray([length], jnp.int32)
                logits, cache = _oracle_paged_decode(
                    params, cache, tok, slot_ids, tables, lengths,
                    cfg=cfg, bsn_backend=bsn_backend)
                gen.append(pick(logits, length + 1))
                length += 1
            outs.append(gen)
    return outs
