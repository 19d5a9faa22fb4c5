import os
# a CPU dry run: pin this process and the --all children (which inherit
# the environment) to the CPU, so no launcher ever takes a chip, and
# add the forced host device count to any XLA_FLAGS already set
os.environ["JAX_PLATFORMS"] = "cpu"
_FORCE = "--xla_force_host_platform_device_count=512"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FORCE).strip()

# --- everything below may import jax -------------------------------------
"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell on 512 forced host devices,
record memory_analysis / cost_analysis / roofline terms.

One cell:   python -m repro.launch.dryrun --arch granite-3-2b \
                --shape train_4k --mesh both
All cells:  python -m repro.launch.dryrun --all   (subprocess per cell so a
            pathological compile can't take the sweep down — straggler
            containment for the sweep itself)

Skip rules (DESIGN.md §4): encoder archs skip decode shapes; pure
full-attention archs skip long_500k. Skips are *recorded* in the report.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.roofline import roofline_from_compiled
from repro.configs import SHAPES, get_arch, list_archs, shape_by_name
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import (MeshRules, mesh_rules,
                                        multipod_mapping)
from repro.launch.mesh import make_production_mesh, mesh_chips, mesh_name
from repro.models import (batch_specs, cache_specs, decode_step, init_cache,
                          init_params, loss_fn, param_specs, prefill)
from repro.optim import opt_state_specs
from repro.train import build_train_step, init_train_state

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun")

VLM_IMG_TOKENS = 2880


def cell_skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if cfg.is_encoder and shape.kind == "decode":
        return "encoder-only: no decode step"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "pure full-attention arch: 524k context needs sub-quadratic attention"
    return None


def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in list_archs() for s in SHAPES]


# ---------------------------------------------------------------------------
# ShapeDtypeStruct builders
# ---------------------------------------------------------------------------

def _sds(tree, spec_tree, rules: MeshRules, logical: bool):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(sd, spec):
        if sd is None:                     # e.g. TrainState.error unused
            return None
        if spec is None:
            spec = P()
        if logical:
            spec = rules.resolve(spec)
        return jax.ShapeDtypeStruct(
            sd.shape, sd.dtype, sharding=NamedSharding(rules.mesh, spec))

    is_leaf = (lambda s: s is None or isinstance(s, (tuple,))) if logical \
        else (lambda s: s is None or isinstance(
            s, jax.sharding.PartitionSpec))
    return jax.tree.map(one, tree, spec_tree, is_leaf=is_leaf)


def _batch_sds(cfg: ModelConfig, shape: ShapeConfig, rules: MeshRules,
               kind: str):
    b, s = shape.global_batch, shape.seq_len
    fields = {}
    if cfg.frontend == "vision_stub":
        img = min(VLM_IMG_TOKENS, s // 2)
        fields["patch_embeds"] = jax.ShapeDtypeStruct((b, img, 1024),
                                                      jnp.bfloat16)
        fields["tokens"] = jax.ShapeDtypeStruct((b, s - img), jnp.int32)
    elif cfg.frontend == "audio_stub":
        fields["frames"] = jax.ShapeDtypeStruct((b, s, 512), jnp.bfloat16)
    else:
        fields["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if kind == "train":
        fields["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        fields["loss_mask"] = jax.ShapeDtypeStruct((b, s), jnp.float32)
    specs = {k: batch_specs(cfg, kind).get(k, ("batch", None))
             for k in fields}
    return _sds(fields, specs, rules, logical=True)


def _params_sds(cfg: ModelConfig, rules: MeshRules, serving: bool = False):
    shapes = jax.eval_shape(partial(init_params, cfg=cfg), jax.random.key(0))
    return _sds(shapes, param_specs(cfg, serving=serving), rules,
                logical=False)


# ---------------------------------------------------------------------------
# lowering per cell kind
# ---------------------------------------------------------------------------

def lower_cell(cfg: ModelConfig, shape: ShapeConfig, rules: MeshRules):
    from jax.sharding import PartitionSpec as P
    mesh = rules.mesh
    p_sds = _params_sds(cfg, rules, serving=(shape.kind == "decode"))

    if shape.kind == "train":
        state_shapes = jax.eval_shape(
            lambda p: init_train_state(p, cfg), p_sds)
        pspecs = param_specs(cfg)
        from repro.train.step import TrainState
        state_specs = TrainState(params=pspecs,
                                 opt=opt_state_specs(pspecs),
                                 step=P(), error=None)
        state_sds = _sds(state_shapes, state_specs, rules, logical=False)
        batch = _batch_sds(cfg, shape, rules, "train")
        step_fn = build_train_step(cfg, lambda s: 3e-4)
        jitted = jax.jit(step_fn, donate_argnums=0)
        return jitted.lower(state_sds, batch)

    if shape.kind == "prefill":
        batch = _batch_sds(cfg, shape, rules, "prefill")
        fn = jax.jit(lambda p, b: prefill(p, b, cfg))
        if cfg.is_encoder:
            from repro.models import forward
            fn = jax.jit(lambda p, b: forward(p, b, cfg, mode="train")[0])
        return fn.lower(p_sds, batch)

    # decode
    b, s = shape.global_batch, shape.seq_len
    seq_shard = shape.name == "long_500k"
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    kv_head_shard = (cfg.n_kv_heads % model_size == 0) and not seq_shard
    cache_shapes = jax.eval_shape(
        partial(init_cache, cfg, b, s))
    cspecs = cache_specs(cfg, seq_shard=seq_shard,
                         kv_head_shard=kv_head_shard)
    cache_sds = _sds(cache_shapes, cspecs, rules, logical=True)
    tok = _sds(jax.ShapeDtypeStruct((b, 1), jnp.int32), ("batch", None),
               rules, logical=True)
    fn = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg),
                 donate_argnums=1)
    return fn.lower(p_sds, cache_sds, tok)


# ---------------------------------------------------------------------------
# one cell end-to-end
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             quant: str | None = None, report_dir: str = REPORT_DIR,
             verbose: bool = True, overrides: dict | None = None) -> dict:
    cfg = get_arch(arch)
    if quant:
        cfg = cfg.with_quant(quant) if quant != "none" \
            else cfg.scaled(quant=cfg.quant.with_mode("none"))
    if overrides:
        cfg = cfg.scaled(**overrides)
    shape = shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mname = mesh_name(mesh)
    record = {"arch": arch, "shape": shape_name, "mesh": mname,
              "chips": mesh_chips(mesh), "quant": cfg.quant.mode,
              "status": "?"}

    skip = cell_skip_reason(cfg, shape)
    if skip:
        record.update(status="skipped", reason=skip)
        _save(record, report_dir)
        if verbose:
            print(f"[dryrun] SKIP {arch} x {shape_name} x {mname}: {skip}")
        return record

    mapping = multipod_mapping()
    if shape.global_batch == 1:
        # long_500k: batch can't occupy a mesh axis; "seq" (data) carries
        # the context parallelism instead
        mapping = dict(mapping, batch=())
    rules = MeshRules(mesh=mesh, mapping=mapping)
    t0 = time.time()
    with mesh_rules(rules):
        lowered = lower_cell(cfg, shape, rules)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        rep = roofline_from_compiled(compiled, cfg, shape, mname,
                                     mesh_chips(mesh))
    record.update(
        status="ok", lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory_analysis={
            "argument_size_in_bytes": ma.argument_size_in_bytes,
            "output_size_in_bytes": ma.output_size_in_bytes,
            "temp_size_in_bytes": ma.temp_size_in_bytes,
            "alias_size_in_bytes": ma.alias_size_in_bytes,
            "peak_memory_in_bytes": ma.peak_memory_in_bytes,
        },
        cost_analysis={k: v for k, v in
                       (compiled.cost_analysis() or {}).items()
                       if k in ("flops", "bytes accessed")},
        roofline=json.loads(rep.to_json()),
    )
    _save(record, report_dir)
    if verbose:
        gb = ma.peak_memory_in_bytes / 2 ** 30
        r = record["roofline"]
        print(f"[dryrun] OK {arch} x {shape_name} x {mname}: "
              f"peak/device {gb:.2f} GiB  "
              f"terms(c/m/coll)={r['t_compute']:.3e}/{r['t_memory']:.3e}/"
              f"{r['t_collective']:.3e}s  bottleneck={r['bottleneck']} "
              f"frac={r['roofline_fraction']:.2f} "
              f"(lower {record['lower_s']}s compile {record['compile_s']}s)")
    return record


def _save(record: dict, report_dir: str):
    os.makedirs(report_dir, exist_ok=True)
    fn = (f"{record['arch']}__{record['shape']}__{record['mesh']}"
          f"__{record.get('quant', 'q')}.json")
    with open(os.path.join(report_dir, fn), "w") as f:
        json.dump(record, f, indent=1)


# ---------------------------------------------------------------------------
# sweep orchestration (subprocess per cell)
# ---------------------------------------------------------------------------

def sweep(meshes: list[bool], quant: str | None, report_dir: str,
          only_missing: bool = False):
    results = []
    for arch, shape_name in all_cells():
        for multi in meshes:
            mname = "2x16x16" if multi else "16x16"
            out = os.path.join(
                report_dir, f"{arch}__{shape_name}__{mname}"
                f"__{quant or get_arch(arch).quant.mode}.json")
            if only_missing and os.path.exists(out):
                with open(out) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skipped"):
                    results.append((arch, shape_name, mname, prev["status"]))
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", "multi" if multi else "single",
                   "--report-dir", report_dir]
            if quant:
                cmd += ["--quant", quant]
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600)
            status = "ok"
            if r.returncode != 0:
                status = "FAILED"
                fail = {"arch": arch, "shape": shape_name, "mesh": mname,
                        "quant": quant or "default", "status": "failed",
                        "stderr": r.stderr[-4000:]}
                _save(fail, report_dir)
            print(f"[sweep] {arch} x {shape_name} x {mname}: {status} "
                  f"({time.time() - t0:.0f}s)")
            sys.stdout.write(r.stdout[-2000:] if r.returncode == 0
                             else r.stderr[-2000:] + "\n")
            results.append((arch, shape_name, mname, status))
    bad = [r for r in results if r[3] == "FAILED"]
    print(f"[sweep] done: {len(results)} cells, {len(bad)} failed")
    for b in bad:
        print("  FAILED:", b)
    return 1 if bad else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--quant", choices=["none", "sc_qat"], default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--report-dir", default=REPORT_DIR)
    ap.add_argument("--set", action="append", default=[],
                    help="config overrides for perf iterations, "
                         "e.g. --set ce_chunks=8 --set attn_q_chunk=2048")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        if v.lstrip("-").isdigit():
            overrides[k] = int(v)
        elif v in ("True", "False"):
            overrides[k] = v == "True"
        else:
            overrides[k] = v

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        sys.exit(sweep(meshes, args.quant, args.report_dir,
                       args.only_missing))
    assert args.arch and args.shape, "--arch/--shape or --all"
    for multi in meshes:
        try:
            run_cell(args.arch, args.shape, multi, args.quant,
                     args.report_dir, overrides=overrides or None)
        except Exception:
            traceback.print_exc()
            sys.exit(1)


if __name__ == "__main__":
    main()
