import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh(es) and emit memory / cost / roofline records.

The XLA_FLAGS line above MUST stay the first statement — jax locks the
device count on first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.analysis.roofline import analyze_cell
from repro.configs import ARCH_NAMES, SHAPES, applicable_shapes
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str | None,
             roofline: bool = True) -> dict:
    multi_pod = mesh_name == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    lowered, kind = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
        "kind": kind, "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "n_micro": cell.n_micro,
        "memory": {
            "arg_GiB": round(ma.argument_size_in_bytes / 2**30, 3),
            "out_GiB": round(ma.output_size_in_bytes / 2**30, 3),
            "temp_GiB": round(ma.temp_size_in_bytes / 2**30, 3),
        },
    }
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed")
           if k in ca})
    if roofline:
        rep = analyze_cell(arch, shape, mesh_name, chips, compiled,
                           n_micro=cell.n_micro)
        rec["roofline"] = dataclasses.asdict(rep)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        safe = arch.replace(".", "_")
        with open(os.path.join(out_dir,
                               f"{safe}__{shape}__{mesh_name}.json"),
                  "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list(ARCH_NAMES),
                    help="one architecture (default: with --all, every one)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every applicable (arch × shape) cell")
    ap.add_argument("--out", default=None, help="JSON output directory")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    meshes = (["pod", "multipod"] if args.mesh == "both" else [args.mesh])
    if not args.all and args.arch is None:
        ap.error("pass --arch or --all")

    results = []
    for arch in archs:
        app = applicable_shapes(arch)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for shape in shapes:
            for mesh_name in meshes:
                if app[shape] != "run":
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "skip", "reason": app[shape]}
                    print(f"[dryrun] SKIP  {arch:28s} {shape:12s} "
                          f"{mesh_name}: {app[shape][:60]}", flush=True)
                    results.append(rec)
                    continue
                print(f"[dryrun] CELL  {arch:28s} {shape:12s} {mesh_name}",
                      flush=True)
                try:
                    rec = run_cell(arch, shape, mesh_name, args.out,
                                   roofline=not args.no_roofline)
                    rl = rec.get("roofline", {})
                    print(f"[dryrun]   ok: compile={rec['compile_s']}s "
                          f"temp={rec['memory']['temp_GiB']}GiB "
                          f"dominant={rl.get('dominant', '?')}", flush=True)
                except Exception as e:   # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "FAIL", "error": repr(e)}
                results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"[dryrun] total={len(results)} ok={n_ok} skip={n_skip} "
          f"fail={n_fail}")
    if args.out:
        with open(os.path.join(args.out, "summary.json"), "w") as f:
            json.dump(results, f, indent=1, default=str)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
