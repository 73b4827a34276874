import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract roofline terms. MUST be run as its own process (the XLA_FLAGS line
above executes before any jax import).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
      --shape train_4k --mesh pod1 --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import REGISTRY, get_spec  # noqa: E402
from ..models.sharding import tree_filter_specs, filter_spec  # noqa: E402
from ..sparse.dist import make_dryrun_rank_sweep  # noqa: E402
from . import hlo_analysis  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .steps import build_step  # noqa: E402


# the chip the production meshes are made of (the dry run compiles on host
# devices, so the target is named, not read from jax.devices())
TARGET_KIND = "TPU v5 lite"


def _axis_size(a, mesh) -> int:
    if a is None:
        return 1
    if isinstance(a, (tuple, list)):
        n = 1
        for x in a:
            n *= mesh.shape.get(x, 1)
        return n
    return mesh.shape.get(a, 1)


def _divisible_spec(spec: P, shape, mesh) -> P:
    """Drop sharding on dims the mesh axes don't divide (B=1 decode, 24
    heads over model=16, 429-dim cross layers, ...). Correctness first;
    the roofline records what replication costs."""
    out = []
    for i, a in enumerate(spec):
        if i >= len(shape):
            out.append(None)
            continue
        size = _axis_size(a, mesh)
        out.append(a if size > 1 and shape[i] % size == 0 else
                   (a if size == 1 else None))
    return P(*out)


def _to_named(tree, mesh, args=None):
    specs = jax.tree.map(lambda s: filter_spec(s, mesh), tree,
                         is_leaf=lambda s: isinstance(s, P))
    if args is not None:
        specs = jax.tree.map(
            lambda s, a: _divisible_spec(s, getattr(a, "shape", ()), mesh),
            specs, args, is_leaf=lambda s: isinstance(s, P))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             mode: str = "baseline", force: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_name}__{mode}"
    out_path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            cached = json.load(f)
        if cached.get("status") in ("ok", "skipped"):
            return cached  # errors are always retried

    spec = get_spec(arch)
    skip = spec.skip_shapes.get(shape_name)
    if skip:
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "mode": mode, "status": "skipped", "reason": skip}
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        return result

    multi_pod = mesh_name == "pod2"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    t0 = time.time()
    try:
        if spec.family == "ranking":
            step = build_step(spec, shape_name, n_devices=n_devices, mode=mode)
            shp = spec.shapes[shape_name]
            n_hub = int(shp["n_nodes"] * (1 - shp.get("dangling_frac", 0.0)))
            fn = make_dryrun_rank_sweep(
                mesh, shp["n_nodes"], axes=mesh.axis_names, mode=mode,
                n_hub=n_hub)
        else:
            step = build_step(spec, shape_name, mode=mode)
            fn = step.fn
        in_sh = _to_named(step.in_specs, mesh, step.args)
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh)
            lowered = jitted.lower(*step.args)
            compiled = lowered.compile()
            analysis = hlo_analysis.analyze(
                compiled, step.meta.get("model_flops_per_step", 0), n_devices,
                TARGET_KIND)
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "mode": mode, "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "meta": {k: v for k, v in step.meta.items()
                     if isinstance(v, (int, float, str))},
            **analysis,
        }
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "mode": mode, "status": "error", "error": repr(e),
                  "traceback": traceback.format_exc()[-2000:],
                  "compile_s": round(time.time() - t0, 1)}
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--mode", default="baseline")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-ranking", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch_id, spec in REGISTRY.items():
            if spec.family == "ranking" and not args.include_ranking:
                continue
            for shape_name in spec.shapes:
                cells.append((arch_id, shape_name))
    else:
        spec = get_spec(args.arch)
        shapes = [args.shape] if args.shape else list(spec.shapes)
        cells = [(args.arch, s) for s in shapes]

    for arch_id, shape_name in cells:
        r = run_cell(arch_id, shape_name, args.mesh, args.out, args.mode,
                     args.force)
        status = r["status"]
        extra = ""
        if status == "ok":
            rl = r["roofline"]
            extra = (f" bottleneck={rl['bottleneck']}"
                     f" frac={rl['roofline_fraction']:.3f}"
                     f" compile={r['compile_s']}s")
        elif status == "error":
            extra = " " + r["error"][:120]
        print(f"[{status:7s}] {arch_id:22s} {shape_name:14s} {args.mesh}{extra}",
              flush=True)


if __name__ == "__main__":
    main()
