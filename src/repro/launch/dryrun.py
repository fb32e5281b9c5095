import os
if __name__ == "__main__":
    # 512 host devices for the production mesh, set before any import
    # (jax fixes the device count when it first initializes); importing
    # this module sets nothing, so no chip path inherits the flag
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import cells, get_config, get_shape  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline import analysis  # noqa: E402

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             outdir: str | None = None, verbose: bool = True) -> dict:
    """Lower + compile one (arch × shape × mesh) cell; return the record
    (memory analysis, cost analysis, collective bytes)."""
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    jitted, args = steps_mod.build_cell(arch, shape_name, mesh)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(compiled.as_text())
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                  None),
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        "collectives": coll,
    }
    if verbose:
        print(f"[{mesh_name}] {arch} × {shape_name}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        print("  memory_analysis:", record["memory"])
        print("  cost_analysis: flops=%.3e bytes=%.3e" % (
            cost.get("flops", 0) or 0, cost.get("bytes accessed", 0) or 0))
        print("  collective bytes:", {k: f"{v:.3e}" for k, v in
                                      coll.items() if isinstance(v, float)})
    if outdir:
        os.makedirs(outdir, exist_ok=True)
        path = os.path.join(outdir, f"{arch}__{shape_name}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--outdir", default=os.path.normpath(ART_DIR))
    args = ap.parse_args()

    todo = cells()
    if args.arch:
        todo = [(a, s) for a, s in todo if a == args.arch]
    if args.shape:
        todo = [(a, s) for a, s in todo if s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape_name in todo:
        for multi_pod in meshes:
            try:
                run_cell(arch, shape_name, multi_pod, outdir=args.outdir)
            except Exception as e:  # noqa: BLE001 — report, keep going
                failures.append((arch, shape_name, multi_pod, repr(e)))
                print(f"[{'2x16x16' if multi_pod else '16x16'}] {arch} × "
                      f"{shape_name}: FAIL {e}")
                traceback.print_exc()
    print(f"\n{len(todo) * len(meshes) - len(failures)} ok, "
          f"{len(failures)} failed")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
