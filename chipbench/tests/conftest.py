"""Fixtures of the harness's CPU tests: a tiny copy of the benchmark.

The copy keeps every file of ``chipbench/`` and ``BENCHMARK.json`` and
shrinks only the sizes inside the configuration and mix files (field
vocabularies, widths, buckets, rates), so a whole run fits a CPU test.
"""

import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_tiny_copy(dst: str) -> str:
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    cdir = os.path.join(dst, "chipbench", "configs")
    for f in os.listdir(cdir):
        with open(os.path.join(cdir, f)) as fh:
            c = json.load(fh)
        c["schema"]["field_sizes"] = [min(n, 3000)
                                      for n in c["schema"]["field_sizes"]]
        c["hidden"] = [64, 64, 64]
        c["store"].update(capacity=512, admit_requests=1024)
        c["batching"]["buckets"] = [16, 64]
        with open(os.path.join(cdir, f), "w") as fh:
            json.dump(c, fh)
    tdir = os.path.join(dst, "chipbench", "traffic")
    for f in os.listdir(tdir):
        with open(os.path.join(tdir, f)) as fh:
            m = json.load(fh)
        if m["loop"] == "closed":
            m.update(outstanding=128, pool=2048, max_rate_per_s=50000)
        else:
            m["arrivals"]["phases"] = [{"seconds": 1.0, "rate_per_s": 400}]
        with open(os.path.join(tdir, f), "w") as fh:
            json.dump(m, fh)
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_copy(str(tmp_path_factory.mktemp("tiny")))
