"""BENCHMARK.json against the contract it is written to, and the files it
names found by name, including ones added in a copy without editing any
file already there."""

import ast
import json
import os
import re
import shutil
import types

import pytest

from chipbench import registry
from chipbench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_paths(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["chipbench"]
    assert spec["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    # a full check of 24 cells at this run length fits in 43,200 s
    runs = 2 + 14 * 24
    assert (spec["run_seconds"] + 60) * runs + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_configs(spec):
    assert 1 <= len(spec["configs"]) <= 24
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("chipbench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"] == []
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "models",
                                           cfg["model"] + ".py"))
        assert any(w["config"] == c["name"] for w in spec["workloads"])


def test_workloads(spec):
    names = [w["name"] for w in spec["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "traffic",
                                           w["traffic"] + ".json"))


def test_metrics_match_their_files(spec):
    bench = registry.Benchmark()
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in spec["workloads"]}
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            mod = bench.metric(m["name"])
            assert (mod.UNIT, mod.SOURCE) == (m["unit"], m["source"])
            assert set(m.get("workloads", cells)) <= cells
            if group == "end_to_end":
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.25
            else:
                assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
                assert m["moves"] in e2e
    for cell in cells:
        got = {m["name"] for m in bench.metrics_for(cell, False)}
        assert "setup_s" in got and len(got) >= 2
        assert bench.metrics_for(cell, True)
        for m in bench.metrics_for(cell, True):
            assert m["moves"] in got, (cell, m["name"])


def test_harness_names_no_config_mix_or_metric():
    """Only the data files and BENCHMARK.json name configurations, mixes,
    models and metrics; the harness finds them by those names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = ({c["name"] for c in spec["configs"]}
             | {w["name"] for w in spec["workloads"]}
             | {w["traffic"] for w in spec["workloads"]}
             | {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
             | {"dcnv2", "deepfm"})
    here = os.path.join(ROOT, "chipbench")
    for f in os.listdir(here):
        if not f.endswith(".py"):
            continue
        with open(os.path.join(here, f)) as fh:
            tree = ast.parse(fh.read())
        consts = {n.value for n in ast.walk(tree)
                  if isinstance(n, ast.Constant) and isinstance(n.value, str)}
        assert not (consts & names), (f, consts & names)


def test_references_import_nothing_of_the_program():
    here = os.path.join(ROOT, "chipbench")
    files = [os.path.join(here, "models", f)
             for f in os.listdir(os.path.join(here, "models"))
             if f.endswith(".py")]
    files += [os.path.join(here, f) for f in ("refmath.py", "check.py",
                                              "loadgen.py", "trace.py")]
    for path in files:
        with open(path) as fh:
            tree = ast.parse(fh.read())
        for n in ast.walk(tree):
            if isinstance(n, ast.Import):
                mods = [a.name for a in n.names]
            elif isinstance(n, ast.ImportFrom):
                mods = [n.module or ""]
            else:
                continue
            assert not any(m.split(".")[0] == "repro" for m in mods), path


def test_added_files_are_found_by_name(tmp_path):
    """A later change adds a mix, a metric and a cell by adding files and
    entries only."""
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (tmp_path / "chipbench" / "traffic" / "burst-test.json").write_text(
        json.dumps({"loop": "open", "ids": {"dist": "zipf",
                                            "exponent": 1.1},
                    "arrivals": {"process": "poisson", "phases": [
                        {"seconds": 0.25, "rate_per_s": 30000},
                        {"seconds": 0.75, "rate_per_s": 4000}]}}))
    (tmp_path / "chipbench" / "metrics" / "queue_wait_p99_ms.py").write_text(
        'UNIT = "ms"\nLAYER = "engine queue"\nMOVES = "p50_ms"\n'
        'SOURCE = "host_clock"\n\n\ndef read(ctx):\n    return 4.25\n')
    spec["workloads"].append({"name": "dcnv2-criteo.burst",
                              "config": "dcnv2-criteo-d32-h1024",
                              "traffic": "burst-test", "chips": 1,
                              "why": "on/off bursts"})
    spec["per_layer"].append({"name": "queue_wait_p99_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "engine queue", "moves": "p50_ms",
                              "workloads": ["dcnv2-criteo.burst"]})
    for m in spec["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("dcnv2-criteo.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = registry.Benchmark(str(tmp_path))
    cell = bench.workload("dcnv2-criteo.burst")
    assert bench.mix(cell["traffic"])["arrivals"]["phases"][0][
        "rate_per_s"] == 30000
    assert bench.config(cell["config"])["model"] == "dcnv2"
    layer = [m["name"] for m in bench.metrics_for("dcnv2-criteo.burst",
                                                  True)]
    assert layer == ["queue_wait_p99_ms"]
    assert bench.metric("queue_wait_p99_ms").read(
        types.SimpleNamespace()) == 4.25
    assert "queue_wait_p99_ms" not in [
        m["name"] for m in bench.metrics_for("dcnv2-criteo.steady", True)]
    with pytest.raises(KeyError):
        bench.metric("no_such_metric")
    with pytest.raises(KeyError):
        bench.workload("no_such_cell")
