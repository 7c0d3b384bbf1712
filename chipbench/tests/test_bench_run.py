"""A whole run on the CPU at a tiny size: the look for a chip refuses
it, and, with that look skipped, a sound program comes out correct and a
program broken underneath the timed path comes out not correct."""

import ast
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import bench, registry
from chipbench.tests.conftest import ROOT


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    p = _run(["--workload", "dcnv2-criteo.saturate", "--seed", "2147483999",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "Nothing was run" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and chipbench/ runs
    nothing."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "dcnv2-criteo.steady", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def _cell(tiny_root, workload, seed=11, seconds=1.5):
    b = registry.Benchmark(tiny_root)
    return bench.run_cell(b, workload, seed, seconds, False,
                          time.perf_counter())


def _cells():
    """One cell of each loop kind that ``BENCHMARK.json`` holds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    kinds = {}
    for w in spec["workloads"]:
        kinds.setdefault(w["traffic"].startswith("steady"), w["name"])
    return sorted(kinds.values())


@pytest.mark.parametrize("workload", _cells())
def test_sound_program_is_correct(tiny_root, workload):
    out = _cell(tiny_root, workload)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 100
    assert out["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {
        m["name"] for m in registry.Benchmark(tiny_root).metrics_for(
            workload, False)}
    json.dumps(out)


def _alter_one_answer(orig):
    def predict(self, ids):
        out = orig(self, ids).copy()
        out[0] += 0.01                  # one answer altered where made
        return out
    return predict


def _half_batch_left_out(orig):
    def predict(self, ids):
        ids = np.asarray(ids)
        half = max(1, len(ids) // 2)
        first = orig(self, ids[:half])
        # the rest never computed: the mean over the half that was
        return np.concatenate([first, np.full(len(ids) - half,
                                              first.mean(), first.dtype)])
    return predict


def _answer_dropped(orig):
    def predict(self, ids):
        return orig(self, ids)[:-1]     # the last request never answered
    return predict


@pytest.mark.parametrize("fault", [_alter_one_answer, _half_batch_left_out,
                                   _answer_dropped])
def test_broken_program_is_not_correct(tiny_root, monkeypatch, fault):
    """The fault is planted as the window opens, under the program's
    plan call, which every timed batch goes through."""
    from repro.core.plan import InferencePlan
    window = bench.Session.window

    def broken_window(self, *args, **kw):
        monkeypatch.setattr(InferencePlan, "predict",
                            fault(InferencePlan.predict))
        return window(self, *args, **kw)
    monkeypatch.setattr(bench.Session, "window", broken_window)
    monkeypatch.setattr(bench, "GRACE_S", 1.0)
    out = _cell(tiny_root, _cells()[-1], seed=12)
    assert out["correct"] is False
    assert not bench.check.passed(out["checks"])


_SETUP = """
import sys
sys.path[0:0] = [sys.argv[1], sys.argv[2]]
from chipbench import bench, registry
bench.enable_compile_cache(sys.argv[3])
s = bench.Session(registry.Benchmark(sys.argv[1]), sys.argv[4], 2**40 + 3)
s.close()
"""


def test_plans_compile_in_every_run_and_the_rest_is_cached(tiny_root,
                                                           tmp_path):
    """Two set-ups of one seed sharing a compile cache: the second loads
    every executable from the cache except the plans', which hold the
    seed's weights and are compiled anew in every run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    phases = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", _SETUP, tiny_root,
             os.path.join(ROOT, "src"), str(tmp_path / "cache"),
             _cells()[0]], env=env, capture_output=True, text=True,
            timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        line = [x for x in p.stderr.splitlines() if "per phase" in x][-1]
        phases.append(ast.literal_eval(line[line.index("{"):]))
    first, second = phases
    assert first["plans"][1] > 0 and first["plans"][2] == 0
    assert second["plans"][1:] == (first["plans"][1], 0)
    for name, (_, made, loaded) in second.items():
        if name != "plans":
            assert loaded == made, (name, second)
