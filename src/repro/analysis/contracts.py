"""Hot-path contract checker: static audits of the engine's jitted steps.

The serving engine's correctness/speed story rests on invariants that
benchmarks only rediscover after they regress.  This module checks them
*statically*, from three artifacts jax hands us for free:

* the **jaxpr** of each traced step (dtype purity, host boundary),
* the **lowered MLIR** (`tf.aliasing_output` arg attributes — donation),
* the **compiled HLO** (collective wire bytes via
  :mod:`repro.analysis.hlo_cost`, the shared parser).

Pass families (see analysis/README.md for the catalog and the allowlist
policy):

``donation``   every cache leaf the engine donates actually aliases an
               output buffer — a silent donation failure doubles HBM.
``retrace``    re-running an identical workload adds zero lowerings
               (catches weak-type promotion, python-scalar closures and
               per-call ``jax.jit(lambda ...)`` wrappers).
``dtype``      no float ``dot_general``/``convolution`` inside the
               sc_int / sc_int_approx BSN region; float math is allowed
               only in the attention/recurrence/softmax/norm/sampler
               allowlist, and the integer datapath must actually be
               engaged.
``host``       no callback / infeed / device_put primitive inside a
               jitted hot-path trace.
``sharding``   under mesh rules, every pool leaf carries the sharding
               ``paged_cache_specs`` promises, and compiled decode stays
               within a collective wire-bytes budget.

Everything here is read-only: audits never execute a step (the sharding
budget compiles decode but does not run it).  ``tools/analyze.py`` drives
these over the config x datapath x kv_format matrix and gates CI.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .hlo_cost import analyze_hlo

__all__ = [
    "Violation", "PassResult", "iter_eqns", "eqn_provenance",
    "audit_donation", "audit_dtype_purity", "audit_host_boundary",
    "audit_sharding", "audit_engine_retrace", "decode_example_args",
    "prefill_example_args", "run_engine_contracts", "results_to_json",
    "FLOAT_DOT_ALLOW_FILES", "FLOAT_DOT_ALLOW_FUNCS",
]


@dataclass(frozen=True)
class Violation:
    passname: str          # donation | retrace | dtype | host | sharding
    label: str             # which lowering, e.g. "granite/sc_int/fp/decode"
    message: str

    def to_dict(self) -> dict:
        return {"pass": self.passname, "label": self.label,
                "message": self.message}


@dataclass
class PassResult:
    passname: str
    label: str
    violations: list = field(default_factory=list)
    notes: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def fail(self, message: str) -> None:
        self.violations.append(Violation(self.passname, self.label, message))

    def to_dict(self) -> dict:
        return {"pass": self.passname, "label": self.label, "ok": self.ok,
                "violations": [v.to_dict() for v in self.violations],
                "notes": list(self.notes)}


def results_to_json(results: list) -> dict:
    vios = [v for r in results for v in r.violations]
    return {"ok": not vios,
            "passes": [r.to_dict() for r in results],
            "violation_count": len(vios)}


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: dict) -> list:
    """Sub-jaxprs buried in an eqn's params (scan/while/pjit/cond/pallas),
    duck-typed so no deprecated jax.core symbols are touched."""
    out = []

    def visit(v):
        if hasattr(v, "eqns"):                        # Jaxpr
            out.append(v)
        elif hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
            out.append(v.jaxpr)                       # ClosedJaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                visit(x)
        elif isinstance(v, dict):
            for x in v.values():
                visit(x)

    for v in params.values():
        visit(v)
    return out


def iter_eqns(jaxpr):
    """Yield every eqn of ``jaxpr`` (Jaxpr or ClosedJaxpr), recursing into
    scan/while/cond/pjit/custom-call/pallas sub-jaxprs."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    seen, stack = set(), [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            stack.extend(_sub_jaxprs(eqn.params))


def eqn_provenance(eqn) -> str:
    """Innermost repro-source frame of an eqn, as ``"file.py:function"``
    (path relative to the ``repro`` package).  ``"<external>"`` when the
    traceback never enters the repo (e.g. pure-jax helper eqns)."""
    try:
        tb = eqn.source_info.traceback
        frames = tb.frames if tb is not None else []
    except AttributeError:
        frames = []
    for f in frames:
        fn = str(f.file_name).replace("\\", "/")
        if "/repro/" in fn and "/analysis/" not in fn:
            # key on the bare function name: newer jax reports qualified
            # names such as ``_decode_kernel.<locals>._accumulate``
            name = str(f.function_name).rsplit(".", 1)[-1]
            return f"{fn.split('/repro/')[-1]}:{name}"
    return "<external>"


# ---------------------------------------------------------------------------
# pass 1: donation
# ---------------------------------------------------------------------------

_MLIR_DTYPE = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int8": "i8", "int16": "i16", "int32": "i32",
    "int64": "i64", "uint8": "ui8", "uint16": "ui16", "uint32": "ui32",
    "uint64": "ui64", "bool": "i1",
}


def _mlir_type(ai) -> str:
    dt = _MLIR_DTYPE.get(str(np.dtype(ai.dtype)), str(ai.dtype))
    dims = "x".join(str(d) for d in ai.shape)
    return f"{dims}x{dt}" if dims else dt


def _parse_mlir_main_args(mlir: str) -> list:
    """(index, tensor type, has tf.aliasing_output) per %argN of @main."""
    m = re.search(r"func\.func\s+(?:public\s+)?@\w+\(", mlir)
    if not m:
        return []
    start = m.end() - 1
    depth = 0
    end = start
    for i in range(start, len(mlir)):
        if mlir[i] == "(":
            depth += 1
        elif mlir[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    sig = mlir[start + 1:end]
    out = []
    for chunk in sig.split("%arg")[1:]:
        idx = re.match(r"(\d+)", chunk)
        typ = re.search(r"tensor<([^>]*)>", chunk)
        out.append((int(idx.group(1)), typ.group(1) if typ else "",
                    "tf.aliasing_output" in chunk))
    return out


def audit_donation(label: str, lowered, *,
                   donated_prefix: str = "[0][1]") -> PassResult:
    """Every arg leaf under ``donated_prefix`` (the keystr path prefix of
    the donated cache argument) must be (a) marked donated in
    ``args_info`` and (b) actually aliased to an output in the lowered
    MLIR (``tf.aliasing_output``).  The default prefix is the second
    positional arg — ``args_info`` is an ((args...), {kwargs}) pytree, so
    the engine's donated cache lives at ``[0][1]``.  (a) catches a dropped
    ``donate_argnums``; (b) catches donation silently falling through
    (shape/dtype/sharding mismatch between the donated input and every
    output — jax only warns, and nobody reads serving logs)."""
    res = PassResult("donation", label)
    leaves = jax.tree_util.tree_flatten_with_path(lowered.args_info)[0]
    paths = [(jax.tree_util.keystr(kp), ai) for kp, ai in leaves]
    donated = [(p, ai) for p, ai in paths if p.startswith(donated_prefix)]
    if not donated:
        res.fail(f"no arg leaves under path {donated_prefix!r} — wrong "
                 "donated-arg prefix or empty cache pytree")
        return res
    for p, ai in donated:
        if not ai.donated:
            res.fail(f"cache leaf {p} is not marked for donation "
                     "(donate_argnums does not cover it)")
    mlir_args = _parse_mlir_main_args(lowered.as_text())
    if not mlir_args:
        res.fail("could not parse @main signature from lowered MLIR")
        return res
    if len(mlir_args) == len(paths):
        # 1:1 positional mapping between flat arg leaves and MLIR args
        by_pos = {i: al for (i, _, al), _ in zip(mlir_args, paths)}
        for i, (p, ai) in enumerate(paths):
            if p.startswith(donated_prefix) and ai.donated \
                    and not by_pos.get(i, False):
                res.fail(f"donated cache leaf {p} has no "
                         "tf.aliasing_output in the lowered MLIR — "
                         "donation fell through (no output aliases it)")
    else:
        # donated-but-unused leaves get DCE'd out of @main; fall back to
        # type-multiset accounting so the audit stays sound
        from collections import Counter
        want = Counter(_mlir_type(ai) for p, ai in donated if ai.donated)
        have = Counter(t for _, t, al in mlir_args if al)
        for t, n in want.items():
            if have.get(t, 0) < n:
                res.fail(f"{n - have.get(t, 0)} donated cache leaves of "
                         f"type tensor<{t}> missing from the lowered "
                         "MLIR aliasing set — donation fell through")
        res.notes.append(
            f"arg-count mismatch (flat {len(paths)} vs MLIR "
            f"{len(mlir_args)}): DCE'd donated leaves; checked by "
            "type multiset")
    aliased = sum(1 for _, _, al in mlir_args if al)
    res.notes.append(f"{len(donated)} cache leaves under {donated_prefix}, "
                     f"{aliased} MLIR args aliased")
    return res


# ---------------------------------------------------------------------------
# pass 2: dtype purity
# ---------------------------------------------------------------------------

# Float dots are ALLOWED only where the paper keeps float math: the
# attention score/value contractions (softmax is float by definition),
# the recurrent mixers' state updates (SSM/WKV recurrences are not BSN
# accumulations), and the sampler.  The projection modules — common.py
# dense_apply, core/sc_layers.py, moe.py expert matmuls — ARE the BSN
# region: a float dot attributed there is a precision leak.
FLOAT_DOT_ALLOW_FILES = (
    "kernels/paged_attention.py", "kernels/flash_attention.py",
    "kernels/ref.py", "models/attention.py", "models/mamba.py",
    "models/rwkv6.py", "serving/sampling.py",
)
# function-level allows: the MoE router draws its gate in f32 by design
# (outside the quantized datapath); expert matmuls are NOT allowed.
FLOAT_DOT_ALLOW_FUNCS = (
    ("models/moe.py", "moe_apply"),
)

_DOT_PRIMS = ("dot_general", "conv_general_dilated")


def audit_dtype_purity(label: str, jaxpr, *, datapath: str) -> PassResult:
    """No float dot/conv inside the integer BSN region (sc_int /
    sc_int_approx), plus a positive check that the integer datapath was
    actually engaged (an audit that passes because quantization silently
    turned itself off is worse than no audit)."""
    res = PassResult("dtype", label)
    if datapath == "qat":
        res.notes.append("qat datapath: float projections are the "
                         "datapath — purity not applicable")
        return res
    float_dots, int_dots, sc_eqns = [], [], 0
    for eqn in iter_eqns(jaxpr):
        prov = eqn_provenance(eqn)
        if prov.startswith("core/sc_layers.py") \
                or prov.startswith("core/bsn.py"):
            sc_eqns += 1
        if eqn.primitive.name not in _DOT_PRIMS:
            continue
        try:
            dt = eqn.outvars[0].aval.dtype
        except (AttributeError, IndexError):
            continue
        if jnp.issubdtype(dt, jnp.floating):
            float_dots.append((prov, str(dt), eqn.primitive.name))
        else:
            int_dots.append(prov)
    for prov, dt, prim in float_dots:
        f, _, fn = prov.partition(":")
        if any(f.endswith(a) for a in FLOAT_DOT_ALLOW_FILES):
            continue
        if any(f.endswith(af) and fn == an
               for af, an in FLOAT_DOT_ALLOW_FUNCS):
            continue
        res.fail(f"float {prim} ({dt}) at {prov} inside the {datapath} "
                 "BSN region — not in the float-math allowlist "
                 "(analysis/README.md)")
    if datapath == "sc_int":
        engaged = [p for p in int_dots if p.startswith("core/sc_layers.py")]
        if not engaged:
            res.fail("sc_int datapath produced no integer dot from "
                     "core/sc_layers.py — the integer datapath is not "
                     "engaged (quantization silently off?)")
    elif datapath == "sc_int_approx" and sc_eqns == 0:
        res.fail("sc_int_approx datapath produced no ops attributed to "
                 "core/sc_layers.py or core/bsn.py — the approximate "
                 "BSN datapath is not engaged")
    res.notes.append(f"{len(float_dots)} float dots (allowlisted), "
                     f"{len(int_dots)} integer dots")
    return res


# ---------------------------------------------------------------------------
# pass 3: host boundary
# ---------------------------------------------------------------------------

_HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "host_callback", "outside_call", "device_put",
})


def audit_host_boundary(label: str, jaxpr) -> PassResult:
    """No host-boundary primitive inside a jitted hot-path trace: every
    callback / infeed / device_put is a device->host (or host->device)
    sync that serializes the decode loop."""
    res = PassResult("host", label)
    count = 0
    for eqn in iter_eqns(jaxpr):
        count += 1
        if eqn.primitive.name in _HOST_PRIMS:
            res.fail(f"host-boundary primitive {eqn.primitive.name} at "
                     f"{eqn_provenance(eqn)} inside a jitted hot path")
    res.notes.append(f"scanned {count} eqns")
    return res


# ---------------------------------------------------------------------------
# pass 4: sharding coverage
# ---------------------------------------------------------------------------

def audit_sharding(eng, label: str, *, cache=None,
                   wire_budget_mult: float = 8.0,
                   check_collectives: bool = True) -> PassResult:
    """Under mesh rules: every paged-cache leaf carries exactly the
    sharding ``paged_cache_specs`` promises (resolved through the rules
    and ``fit_spec``, so non-dividing axes are *expected* replicated),
    and compiled decode stays within a collective wire-bytes budget of
    ``mult x (logits gather + per-layer activation reductions)``."""
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import fit_spec
    from repro.models import paged_cache_specs

    res = PassResult("sharding", label)
    if eng.rules is None:
        res.notes.append("no mesh rules: sharding audit skipped")
        return res
    mesh = eng.rules.mesh
    cache = eng.cache if cache is None else cache
    spec_tree = paged_cache_specs(eng.cfg, eng.kv_format)
    is_spec = lambda s: s is None or isinstance(s, tuple)  # noqa: E731
    cache_leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    spec_leaves = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=is_spec)[0]
    assert len(cache_leaves) == len(spec_leaves), \
        "cache / spec tree mismatch"
    sharded = 0
    for (kp, arr), (_, lg) in zip(cache_leaves, spec_leaves):
        from jax.sharding import PartitionSpec as P
        spec = eng.rules.resolve(lg) if lg is not None else P()
        spec = fit_spec(spec, arr.shape, mesh)
        want = NamedSharding(mesh, spec)
        actual = getattr(arr, "sharding", None)
        if actual is None or not actual.is_equivalent_to(want, arr.ndim):
            res.fail(f"cache leaf {jax.tree_util.keystr(kp)}: sharding "
                     f"{getattr(actual, 'spec', actual)} != expected "
                     f"{spec} (paged_cache_specs through the mesh rules)")
        elif any(ax is not None for ax in spec):
            sharded += 1
    res.notes.append(f"{len(cache_leaves)} cache leaves checked, "
                     f"{sharded} sharded, mesh "
                     f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    if not check_collectives:
        return res

    args = decode_example_args(eng)
    with eng._scope():
        compiled = eng._decode.lower(eng.params, eng.cache, *args,
                                     do_sample=False).compile()
    cost = analyze_hlo(compiled.as_text())
    cfg = eng.cfg
    S = int(args[0].shape[0])
    vpad = getattr(cfg, "vocab_pad_multiple", 1) or 1
    V = -(-cfg.vocab_size // vpad) * vpad
    # one logits gather + up to 4 activation reductions per layer, f32
    budget = wire_budget_mult * 4.0 * S * (V + 4 * cfg.n_layers
                                           * cfg.d_model)
    wire = cost.total_collective_bytes
    if wire > budget:
        res.fail(f"decode collective wire bytes {wire:.0f} exceed budget "
                 f"{budget:.0f} ({cost.collective_count} collectives: "
                 f"{cost.collective_bytes}) — a pool or weight is being "
                 "re-gathered every step")
    res.notes.append(f"decode wire bytes {wire:.0f} / budget "
                     f"{budget:.0f} ({cost.collective_count} collectives)")
    return res


# ---------------------------------------------------------------------------
# pass 5: retrace
# ---------------------------------------------------------------------------

def audit_engine_retrace(eng, prompts, label: str, *,
                         max_new: int = 4,
                         max_decode_lowerings: int | None = None,
                         max_prefill_lowerings: int | None = None
                         ) -> PassResult:
    """Run a prompt ladder twice through a live engine: the second,
    byte-identical pass must add ZERO lowerings to the decode/prefill jit
    caches (a growth means something non-hashable-by-shape leaked into
    the trace: weak types, python scalars, per-call wrappers).  Optional
    absolute ceilings pin the pow2 bucket ladder count itself."""
    res = PassResult("retrace", label)
    fns = {"decode": eng._decode, "prefill": eng._prefill_batched}
    if not all(hasattr(f, "_cache_size") for f in fns.values()):
        res.notes.append("jit cache size introspection unavailable on "
                         "this jax: retrace audit skipped")
        return res

    def run():
        for p in prompts:
            eng.submit(list(p), max_new_tokens=max_new)
        eng.run_to_completion()

    run()
    first = {k: f._cache_size() for k, f in fns.items()}
    run()
    second = {k: f._cache_size() for k, f in fns.items()}
    for k in fns:
        if second[k] > first[k]:
            res.fail(f"{k} retraced on an identical repeated workload: "
                     f"{first[k]} -> {second[k]} lowerings (non-static "
                     "value leaked into the trace key)")
    caps = {"decode": max_decode_lowerings, "prefill": max_prefill_lowerings}
    for k, cap in caps.items():
        if cap is not None and first[k] > cap:
            res.fail(f"{k} traced {first[k]} lowerings for the bucket "
                     f"ladder, expected <= {cap} (one per pow2 bucket)")
    res.notes.append(f"lowerings after ladder: decode {first['decode']}, "
                     f"prefill {first['prefill']}; stable on repeat")
    return res


# ---------------------------------------------------------------------------
# example args + orchestrator
# ---------------------------------------------------------------------------

def decode_example_args(eng, lanes: int = 2):
    """(tokens, slot_ids, tables, lengths, samp) for one decode-step
    lowering at a representative (pow2) bucket.  Values are all zeros /
    trash pages — audits only trace, never execute."""
    from repro.serving.paging import pad_pow2
    from repro.serving.sampling import SamplingParams, pack_sampling
    S = min(pad_pow2(lanes), pad_pow2(eng.max_slots))
    width = pad_pow2(min(4, eng.max_pages))
    return (jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            jnp.zeros((S, width), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            pack_sampling([SamplingParams()] * S))


def prefill_example_args(eng, lanes: int = 2):
    """((tokens, tables, lens, slot_ids, samp), chunk) for one batched
    chunked-prefill lowering.  L and chunk are pow2 multiples of the page
    size (paged_prefill asserts page alignment)."""
    from repro.serving.paging import pad_pow2
    from repro.serving.sampling import SamplingParams, pack_sampling
    G = min(pad_pow2(lanes), pad_pow2(eng.max_slots))
    L = pad_pow2(eng.page_size)
    if 2 * L <= eng.max_len:
        L *= 2                                    # two pages when they fit
    chunk = min(eng._chunk, L)
    width = max(L // eng.page_size, 1)
    args = (jnp.zeros((G, L), jnp.int32),
            jnp.zeros((G, width), jnp.int32),
            jnp.ones((G,), jnp.int32),
            jnp.zeros((G,), jnp.int32),
            pack_sampling([SamplingParams()] * G))
    return args, chunk


def run_engine_contracts(eng, label: str, *,
                         check_collectives: bool = True) -> list:
    """Static audit battery for one constructed engine: donation +
    dtype + host over decode, batched prefill and the sampler, plus the
    sharding audit under mesh rules.  Returns a list of PassResults and
    never executes a step.  The exact-prefill debug oracle is donation-
    exempt BY DESIGN (it takes no cache input — it builds a fresh
    exact-length cache; see ServeEngine.__init__), recorded as a note so
    the exemption stays visible in ANALYSIS.json."""
    from repro.serving.sampling import sample_tokens

    d_args = decode_example_args(eng)
    p_args, chunk = prefill_example_args(eng)
    with eng._scope():
        dec_low = eng._decode.lower(eng.params, eng.cache, *d_args,
                                    do_sample=False)
        pre_low = eng._prefill_batched.lower(eng.params, eng.cache,
                                             *p_args, chunk=chunk,
                                             do_sample=False)
        dec_jx = jax.make_jaxpr(partial(eng._decode_fn, do_sample=False))(
            eng.params, eng.cache, *d_args)
        pre_jx = jax.make_jaxpr(
            partial(eng._prefill_batched_fn, chunk=chunk,
                    do_sample=False))(eng.params, eng.cache, *p_args)
        S = d_args[0].shape[0]
        samp_jx = jax.make_jaxpr(
            lambda lg, pos, sm: sample_tokens(lg, pos, sm,
                                              eng.cfg.vocab_size))(
            jnp.zeros((S, eng.cfg.vocab_size), jnp.float32),
            jnp.zeros((S,), jnp.int32), d_args[4])

    results = [
        audit_donation(f"{label}/decode", dec_low),
        audit_donation(f"{label}/prefill", pre_low),
        audit_dtype_purity(f"{label}/decode", dec_jx,
                           datapath=eng.datapath),
        audit_dtype_purity(f"{label}/prefill", pre_jx,
                           datapath=eng.datapath),
        audit_host_boundary(f"{label}/decode", dec_jx),
        audit_host_boundary(f"{label}/prefill", pre_jx),
        audit_host_boundary(f"{label}/sampler", samp_jx),
        audit_sharding(eng, f"{label}/sharding",
                       check_collectives=check_collectives),
    ]
    exempt = PassResult("donation", f"{label}/prefill_exact")
    exempt.notes.append(
        "exempt by design: the exact-prefill debug oracle takes "
        "(params, batch) only and BUILDS a fresh exact-length cache — "
        "there is no input cache buffer to alias into")
    results.append(exempt)
    return results
