"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (scaffold contract).
``--quick`` shrinks sweeps for CI; ``--dry`` shrinks further to a smoke
configuration (every driver must *run*, numbers are throwaway — the CI
bench-smoke job uses it so drivers can't silently rot); default exercises
the paper grids.
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dry", action="store_true",
                    help="tiny smoke config (implies --quick)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--json", default=None,
                    help="write every driver's run() result dict to this "
                         "JSON file (CI uploads it as the perf-trajectory "
                         "artifact)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (accuracy_parity, breakdown, e2e_speedup, embedding_cache,
                   embedding_host, embedding_sensitivity, mlp_quant,
                   roofline_report, scheduling, serving_async,
                   serving_batching, serving_mesh, serving_updates,
                   workload_allocation)
    suites = {
        "accuracy_parity": accuracy_parity,       # Table I
        "e2e_speedup": e2e_speedup,               # Fig. 7 / Table II
        "breakdown": breakdown,                   # Fig. 8
        "embedding_sensitivity": embedding_sensitivity,  # Fig. 10
        "embedding_cache": embedding_cache,       # store tiering sweep
        "embedding_host": embedding_host,         # out-of-HBM host tier
        "mlp_quant": mlp_quant,                   # int8 dense-branch compute
        "workload_allocation": workload_allocation,      # Fig. 11
        "scheduling": scheduling,                 # Fig. 12/13
        "serving_batching": serving_batching,     # Fig. 7 serving policies
        "serving_async": serving_async,           # async runtime + refresh
        "serving_updates": serving_updates,       # online trainer deltas
        "serving_mesh": serving_mesh,             # multi-chip plans+refresh
        "roofline_report": roofline_report,       # §Roofline
    }
    only = set(args.only.split(",")) if args.only else None
    failed = []
    collected: dict[str, dict] = {}
    for name, mod in suites.items():
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        kwargs = {"quick": args.quick or args.dry}
        if args.dry and "dry" in inspect.signature(mod.run).parameters:
            kwargs["dry"] = True
        try:
            result = mod.run(**kwargs)
            if isinstance(result, dict):
                collected[name] = result
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            # default=str: numpy scalars/bools and PartitionSpecs all
            # stringify rather than breaking the artifact dump
            json.dump({"failed": failed, "results": collected}, f,
                      indent=2, default=str, sort_keys=True)
        print(f"# wrote {args.json}")
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
