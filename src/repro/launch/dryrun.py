import os
os.environ["XLA_FLAGS"] = os.environ.get("REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell, builds allocation-free ShapeDtypeStruct stand-ins for every
input (params, optimizer state, batch, KV-cache), lowers the cell's step
function under the production mesh, compiles it, and records
``memory_analysis()`` / ``cost_analysis()`` / collective-byte roofline
terms to a per-cell JSON under ``results/dryrun/``.

Run (single cell):     python -m repro.launch.dryrun --arch tinyllama_1_1b --shape train_4k
Run (full sweep):      python -m repro.launch.dryrun --all [--multi-pod]
Mesh override (tests): REPRO_XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                          python -m repro.launch.dryrun --mesh 4x2 --arch ... --shape ...

Cell skips (documented in DESIGN.md §5): long_500k runs only for the
subquadratic archs (xlstm, zamba2).
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_config
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_mesh_from_spec, make_production_mesh
from repro.models.common import abstract_params, n_params
from repro.models.registry import SHAPES, applicable, batch_specs, build_model, cache_specs_for
from repro.sharding.rules import MeshRules
from repro.training.optim import moment_specs
from repro.training.step import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def abstract_state(model, rules):
    """Abstract train state: params + ZeRO-sharded AdamW moments."""
    pspecs = model.param_specs()
    mspecs = moment_specs(pspecs, rules)
    return {
        "params": abstract_params(pspecs, rules),
        "opt": {
            "m": abstract_params(mspecs, rules),
            "v": abstract_params(mspecs, rules),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        },
        "rng": jax.ShapeDtypeStruct((2,), jnp.uint32),
    }


def cell_args(cfg, shape_name, mesh, seq=None, batch=None):
    """(fn, abstract_args) for one cell."""
    rules = MeshRules(mesh)
    model = build_model(cfg)
    kind = SHAPES[shape_name]["kind"]
    batch_abs = abstract_params(batch_specs(cfg, shape_name, seq=seq, batch=batch), rules)
    if kind == "train":
        step = make_train_step(model, TrainConfig(), rules)
        return step, (abstract_state(model, rules), batch_abs)
    params_abs = abstract_params(model.param_specs(), rules)
    cache_abs = abstract_params(cache_specs_for(cfg, shape_name, seq=seq, batch=batch), rules)
    fn = model.prefill if kind == "prefill" else model.decode
    return fn, (params_abs, batch_abs, cache_abs)


def bytes_per_device(abstract_tree, mesh) -> int:
    """Exact per-device bytes of a sharded ShapeDtypeStruct tree."""
    total = 0
    for leaf in jax.tree.leaves(abstract_tree):
        n = 1
        for d in leaf.shape:
            n *= d
        shards = 1
        spec = leaf.sharding.spec if leaf.sharding is not None else ()
        for entry in spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                shards *= mesh.shape[ax]
        total += n * leaf.dtype.itemsize // shards
    return total


def run_cell(arch, shape_name, mesh, mesh_name, seq=None, batch=None, verbose=True):
    cfg = get_config(arch)
    ok, why = applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name, "skipped": why}
    t0 = time.time()
    fn, args = cell_args(cfg, shape_name, mesh, seq=seq, batch=batch)
    arg_bytes_dev = bytes_per_device(args, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    roof = ha.analyze(compiled, hlo)
    s = SHAPES[shape_name]
    mf = ha.model_flops(
        cfg, s["kind"], seq or s["seq"], batch or s["global_batch"], mesh.devices.size
    )
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": int(mesh.devices.size),
        "n_params": int(n_params(build_model(cfg).param_specs())),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": roof.flops,
        "hbm_bytes_per_device": roof.hbm_bytes,
        "collective_wire_bytes": roof.coll_bytes,
        "t_compute": roof.t_compute,
        "t_memory": roof.t_memory,
        "t_collective": roof.t_collective,
        "bottleneck": roof.bottleneck,
        "model_flops_per_device": mf,
        "useful_flops_ratio": mf / roof.flops if roof.flops else 0.0,
        "arg_bytes_per_device": arg_bytes_dev,
        "collectives": ha.collective_bytes(hlo),
        **ha.analyze_xla_raw(compiled),
    }
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                rec[f"mem_{k}"] = int(v)
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"flops/dev {roof.flops:.3g} hbm {roof.hbm_bytes:.3g} "
              f"coll {roof.coll_bytes:.3g} -> {roof.bottleneck}")
        print("  memory_analysis:", mem)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mesh", default=None, help="override, e.g. 4x2 or 2x2x2")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = []
    if args.mesh:
        meshes.append((args.mesh, make_mesh_from_spec(args.mesh)))
    else:
        if args.both_meshes or not args.multi_pod:
            meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
        if args.both_meshes or args.multi_pod:
            meshes.append(("2pod16x16", make_production_mesh(multi_pod=True)))

    failures = []
    for mesh_name, mesh in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {tag}")
                    continue
                try:
                    rec = run_cell(arch, shape, mesh, mesh_name, seq=args.seq, batch=args.batch)
                except Exception as e:  # a failing cell is a bug: record + surface
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "error": f"{type(e).__name__}: {e}"}
                    failures.append(tag)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        print("FAILED cells:", failures)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
