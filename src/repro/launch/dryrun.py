import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (architecture x input shape) cell
on the production meshes and extract memory/cost/roofline evidence.

The two lines above MUST stay the first statements in this module: jax locks
the device count at first initialization, and the 512 placeholder host
devices exist only for the dry-run (smoke tests and benches see 1 device).
(`from __future__` is therefore deliberately absent here.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_8b \
        --shape train_4k [--multi-pod] [--out results/]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--out results/]
"""
import argparse
import json
import time
import traceback


from repro import hw
from repro.configs.base import SHAPES, cells, get_config
from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             overrides: dict | None = None, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, overrides)
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print("  memory_analysis:", mem)
    rl = analyze(compiled, get_config(arch), SHAPES[shape_name], mesh_name,
                 chips, arch, hw.V5E)
    out = rl.to_dict()
    out.update({
        "lower_s": t_lower, "compile_s": t_compile,
        "plan": dataclass_dict(cell.plan),
        "memory_analysis": {
            k: getattr(mem, k, None)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
        } if mem is not None else None,
        "status": "ok",
    })
    if verbose:
        print("  cost:", f"flops={rl.hlo_flops:.3e}",
              f"bytes={rl.hlo_bytes:.3e}",
              f"coll_bytes={rl.collective_bytes:.3e}")
        print("  roofline:", f"compute={rl.t_compute*1e3:.2f}ms",
              f"memory={rl.t_memory*1e3:.2f}ms",
              f"mem_floor={rl.t_memory_floor*1e3:.2f}ms",
              f"collective={rl.t_collective*1e3:.2f}ms",
              f"dominant={rl.dominant}",
              f"useful={rl.useful_ratio:.3f}",
              f"roofline_frac={rl.roofline_fraction:.3f}")
    return out


def dataclass_dict(dc):
    import dataclasses
    return dataclasses.asdict(dc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--override", default="",
                    help="json dict of CellPlan overrides")
    args = ap.parse_args()
    overrides = json.loads(args.override) if args.override else None

    grid = (cells() if args.all else [(args.arch, args.shape)])
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]
    results = []
    for arch, shape in grid:
        for mp in meshes:
            try:
                results.append(run_cell(arch, shape, mp, overrides))
            except Exception as e:
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape,
                                "mesh": "2x16x16" if mp else "16x16",
                                "status": f"error: {e}"})
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                tag = "all" if args.all else f"{args.arch}_{args.shape}"
                with open(os.path.join(args.out, f"dryrun_{tag}.json"),
                          "w") as f:
                    json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n{ok}/{len(results)} cells compiled OK")
    return 0 if ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
