#!/usr/bin/env python3
"""The readings each limit of the check is set from (``PERF.md``): the
program's compared numbers over many seeds, and its control's, in one
process. The benchmark's own runs never run this.

    python chipbench/limits.py --workload dcnv2-criteo.saturate \\
        --seeds 1,2,3 --control-seeds 101,102,103

For each of ``--seeds`` it makes one whole untraced run of the cell at
the benchmark's ``run_seconds`` (``bench.run_cell``) and prints its
compared numbers. For each of ``--control-seeds`` it puts the control in
the program's place: the reference computed in bfloat16, the precision
below the configuration's float32, on the rows a run of that seed sends
in its window, compared with the float32 reference as a run's answers
are (``check.compare``). It prints those numbers and whether they pass:
a control that passes shows that the check could not see the loss. One
JSON line per seed.
"""

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]


def control_reading(bench, workload: str, seed: int,
                    seconds: float) -> dict:
    import jax
    from chipbench import check
    from chipbench.bench import seed_streams, window_traffic
    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    ref_model = bench.model(cfg["model"])
    streams = seed_streams(seed)
    rows, _, _ = window_traffic(streams, bench.mix(cell["traffic"]),
                                cfg["schema"], seconds)
    w = jax.jit(lambda k: ref_model.init_weights(cfg, k))(streams["key"])
    checks = check.control_checks(cfg, ref_model, w, rows)
    return {"workload": workload, "seed": seed, "side": "control bfloat16",
            "requests": len(rows), "correct": check.passed(checks),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from chipbench import bench, registry
    b = registry.Benchmark()
    import jax
    if jax.devices()[0].platform != "tpu":
        bench.log("limits: JAX's first device is not a TPU; nothing was run")
        return 3
    bench.enable_compile_cache(bench.CACHE_DIR)
    seconds = b.spec["run_seconds"]
    seeds = [int(x) for x in args.seeds.split(",") if x]
    for seed in seeds:
        out = bench.run_cell(b, args.workload, seed, seconds, False,
                             time.perf_counter())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": "program", "correct": out["correct"],
                          "attempted": out["attempted"],
                          "metrics": out["metrics"],
                          "checks": out["checks"]}), flush=True)
    for seed in (int(x) for x in args.control_seeds.split(",") if x):
        print(json.dumps(control_reading(b, args.workload, seed, seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
