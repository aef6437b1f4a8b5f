import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware: (a) the sharding config is
coherent (no mismatched collectives, divisibility holes, or partitioner
failures), (b) the per-device memory fits a 16 GB v5e chip
(``memory_analysis``), and (c) the compiled collective schedule is the one
the roofline model assumes (HLO text). Artifacts land in
``artifacts/dryrun/<cell>.json`` and feed benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod      # 2-pod mesh
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.distributed.sharding import logical_to_spec, tree_pspecs, shape_structs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.serving.decode import make_serve_step
from repro.training.optimizer import OptConfig
from repro.training.train_loop import make_train_step

COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\("
)
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
}


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind, parsed from (post-SPMD) HLO.

    Note: ops inside while/scan bodies appear once — the dry-run records the
    SCHEDULE; per-step totals are scaled by trip counts in the roofline model
    (benchmarks/roofline.py, EXPERIMENTS.md §Roofline methodology)."""
    out: dict[str, float] = {}
    count = 0
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.groups()
        size = DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d:
                size *= int(d)
        out[kind] = out.get(kind, 0.0) + size
        count += 1
    out["num_collectives"] = count
    return out


def batch_specs(cfg, shape, mesh):
    """(structs, pspecs) for the data batch of a train cell."""
    b, s = shape.global_batch, shape.seq_len
    bspec = logical_to_spec(("batch", "seq"), (b, s), mesh)
    structs = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    pspecs = {"tokens": bspec, "targets": bspec}
    if cfg.vision_seq:
        structs["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.vision_seq, cfg.d_model), jnp.bfloat16
        )
        pspecs["patches"] = logical_to_spec(
            ("batch", None, None), structs["patches"].shape, mesh
        )
    if cfg.is_encdec:
        structs["enc_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16
        )
        pspecs["enc_frames"] = logical_to_spec(
            ("batch", None, None), structs["enc_frames"].shape, mesh
        )
    return structs, pspecs


def state_specs(cfg, mesh):
    """Train state (params f32 + AdamW moments) structs and pspecs."""
    pspec_tree = param_pspecs(cfg, mesh)
    params = shape_structs(transformer.param_specs(cfg), jnp.float32)
    structs = {
        "params": params,
        "opt": {
            "mu": params,
            "nu": params,
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        },
    }
    pspecs = {
        "params": pspec_tree,
        "opt": {"mu": pspec_tree, "nu": pspec_tree, "step": P()},
    }
    return structs, pspecs


def param_pspecs(cfg, mesh):
    return tree_pspecs(transformer.param_specs(cfg), mesh)


def cache_specs(cfg, batch, max_seq, mesh):
    shapes = transformer.cache_shapes(cfg, batch, max_seq)
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    structs = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], leaf[1]), shapes, is_leaf=is_leaf
    )
    pspecs = jax.tree.map(
        lambda leaf: logical_to_spec(leaf[2], leaf[0], mesh), shapes, is_leaf=is_leaf
    )
    return structs, pspecs


def aux_specs(cfg, batch, mesh):
    structs = {}
    pspecs = {}
    if cfg.vision_seq:
        shp = (batch, cfg.vision_seq, cfg.d_model)
        structs["patches"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
        pspecs["patches"] = logical_to_spec(("batch", None, None), shp, mesh)
    if cfg.is_encdec:
        shp = (batch, cfg.encoder_seq, cfg.d_model)
        structs["enc_frames"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
        pspecs["enc_frames"] = logical_to_spec(("batch", None, None), shp, mesh)
    return (structs or None), (pspecs or None)


def build_cell(cfg, shape, mesh):
    """Returns (fn, arg_structs tuple, in_shardings tuple, donate)."""
    ns = lambda tree: jax.tree.map(
        lambda p: NamedSharding(mesh, p), tree, is_leaf=lambda x: isinstance(x, P)
    )
    if shape.kind == "train":
        step = make_train_step(cfg, OptConfig())
        st, sp = state_specs(cfg, mesh)
        bt, bp = batch_specs(cfg, shape, mesh)
        return step, (st, bt), (ns(sp), ns(bp)), (0,)

    if shape.kind == "prefill":
        def prefill_step(params, tokens, aux):
            params = transformer.cast_for_compute(params, cfg)
            logits, caches = transformer.prefill(
                params, tokens, cfg, shape.seq_len, aux=aux
            )
            return logits[:, -1, :], caches  # last-token logits + filled cache

        params = shape_structs(transformer.param_specs(cfg), jnp.bfloat16)
        psp = param_pspecs(cfg, mesh)
        b = shape.global_batch
        tok = jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32)
        tsp = logical_to_spec(("batch", "seq"), tok.shape, mesh)
        ax, axsp = aux_specs(cfg, b, mesh)
        return (
            prefill_step,
            (params, tok, ax),
            (ns(psp), NamedSharding(mesh, tsp), ns(axsp) if ax else None),
            (),
        )

    # decode
    serve = make_serve_step(cfg)

    def serve_step(params, caches, tokens, pos, aux):
        params = transformer.cast_for_compute(params, cfg)
        return serve(params, caches, tokens, pos, aux=aux)

    params = shape_structs(transformer.param_specs(cfg), jnp.bfloat16)
    psp = param_pspecs(cfg, mesh)
    b = shape.global_batch
    ct, csp = cache_specs(cfg, b, shape.seq_len, mesh)
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tsp = logical_to_spec(("batch", None), tok.shape, mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    ax, axsp = aux_specs(cfg, b, mesh)
    return (
        serve_step,
        (params, ct, tok, pos, ax),
        (
            ns(psp),
            ns(csp),
            NamedSharding(mesh, tsp),
            NamedSharding(mesh, P()),
            ns(axsp) if ax else None,
        ),
        (1,),
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, artifacts_dir: str,
             mesh_override: tuple[int, int] | None = None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    runs, reason = applicable(cfg, shape)
    suffix = "pod2" if multi_pod else "pod1"
    if mesh_override:
        suffix += f"_d{mesh_override[0]}m{mesh_override[1]}"
    cell = f"{arch}__{shape_name}__{suffix}"
    if not runs:
        rec = {"cell": cell, "status": "skip", "reason": reason}
        _save(artifacts_dir, cell, rec)
        return rec

    if mesh_override:
        d, m = mesh_override
        shape_t = (2, d, m) if multi_pod else (d, m)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = compat.make_mesh(shape_t, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    fn, args, shardings, donate = build_cell(cfg, shape, mesh)
    jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
    with jax.sharding.set_mesh(mesh):  # activates SP activation constraints
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    num_devices = mesh.devices.size

    rec = {
        "cell": cell,
        "status": "ok",
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "multi_pod": multi_pod,
        "num_devices": int(num_devices),
        "lower_seconds": round(t_lower, 2),
        "compile_seconds": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
        },
        "cost": {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        },
        "collectives_schedule_bytes": coll,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    # per-device fit check against v5e HBM
    hbm = 16 * 1024**3
    per_dev = (
        rec["memory"]["argument_bytes"]
        + rec["memory"]["output_bytes"]
        + rec["memory"]["temp_bytes"]
        - rec["memory"]["alias_bytes"]
    )
    rec["memory"]["per_device_total"] = int(per_dev)
    rec["memory"]["fits_16gb"] = bool(per_dev < hbm)
    _save(artifacts_dir, cell, rec)
    return rec


def _save(artifacts_dir, cell, rec):
    os.makedirs(artifacts_dir, exist_ok=True)
    with open(os.path.join(artifacts_dir, f"{cell}.json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS + ["all"])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--artifacts", default="artifacts/dryrun")
    ap.add_argument("--data", type=int, default=None,
                    help="override data-axis size (with --model; 256 chips/pod)")
    ap.add_argument("--model", type=int, default=None)
    args = ap.parse_args()
    mesh_override = (args.data, args.model) if args.data and args.model else None

    archs = ARCHS if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape in (None, "all") else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, mp, args.artifacts,
                                   mesh_override=mesh_override)
                except Exception as e:  # a failure here is a sharding bug
                    rec = {
                        "cell": f"{arch}__{shape}__{'pod2' if mp else 'pod1'}",
                        "status": "fail",
                        "error": f"{type(e).__name__}: {e}",
                    }
                    _save(args.artifacts, rec["cell"], rec)
                    traceback.print_exc()
                    failures.append(rec["cell"])
                status = rec["status"]
                extra = ""
                if status == "ok":
                    m = rec["memory"]
                    extra = (
                        f" mem/dev={m['per_device_total']/2**30:.2f}GiB"
                        f" fits={m['fits_16gb']}"
                        f" compile={rec['compile_seconds']:.0f}s"
                    )
                elif status == "skip":
                    extra = f" ({rec['reason']})"
                print(f"[{status:4s}] {rec['cell']}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print("dry-run complete: all cells lowered + compiled")


if __name__ == "__main__":
    main()
