#!/usr/bin/env python3
"""Run one benchmark cell on the chip this machine holds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Exits non-zero, printing no result, unless JAX's first device is a TPU
and there are as many as the cell asks for. See ``chipbench/bench.py``.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout root (for ``chipbench``) and the program, in place of this
# script's own directory
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

if __name__ == "__main__":
    from chipbench import bench
    sys.exit(bench.main(t_start=T_START))
