"""Everything a run needs, found by the names in ``BENCHMARK.json``.

    configs/<config>.json     a deployment: model, schema, widths, store,
                              batching, runtime, limits of the check
    traffic/<mix>.json        a traffic mix (read by ``loadgen.py``)
    models/<model>.py         the model's plain reference, weights, costs
    metrics/<metric>.py       one metric: unit, layer, ``read(ctx)``

A configuration, mix, model or metric is added by adding its file and an
entry in ``BENCHMARK.json``; no file of the harness names any of them.

Beside the keys every configuration has (``model``, ``schema.field_sizes``,
``embed_dim``, ``hidden``, ``dtype``, ...), four optional ones let a
configuration state more without a change to the harness:

    arch                 keys of the program's model spec beyond those,
                         passed through as keywords (lists as tuples);
                         a key the program does not know fails set-up
    schema.numeric       {"caps": [c_1, ..., c_m], "exponent": s}: m
                         numeric features, integer counts in [0, c_j),
                         zipf with exponent s (the model transforms them)
    schema.hotness       [h_1, ..., h_k]: ids per field, each drawn on its
                         own from the field's zipf (default one each)
    init.block_rows      B: tables drawn B rows at a time, in place, so a
                         table that fills most of a chip can be made there

A request row is int32, ``m + Σh`` wide::

    [count_1 .. count_m | field 1's h_1 ids | ... | field k's h_k ids]

``schema.field_sizes`` stays the vocabulary of each field: ids index one
table of ``Σ field_sizes`` rows, field by field. ``refmath.split_rows``
and ``refmath.pooled_lookup`` read such rows in a model's reference.

A model file gives ``tables(cfg)`` (weight key -> row width),
``init_weights(cfg, key, table=refmath.table)`` (its tables made through
``table``), ``logits(cfg, weights, rows)``, ``flops_per_request(cfg)``
and ``gather_bytes(cfg, batch)``; it imports nothing of the program.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import re
import sys

#: the checkout: ``chipbench/`` sits at its root
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(kind: str, name: str, path: str):
    path = os.path.abspath(path)
    tag = hashlib.sha1(path.encode()).hexdigest()[:12]
    mod_name = f"chipbench_{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}_{tag}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` of a checkout, and the files it names."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.here = os.path.join(root, "chipbench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r}; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r}")

    def mix(self, name: str) -> dict:
        with open(os.path.join(self.here, "traffic", f"{name}.json")) as f:
            return json.load(f)

    def model(self, name: str):
        return _load_module("model", name,
                            os.path.join(self.here, "models", f"{name}.py"))

    def metric(self, name: str):
        return _load_module("metric", name,
                            os.path.join(self.here, "metrics", f"{name}.py"))

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        """The cell's metrics: its end-to-end metrics in an untraced run,
        its per-layer metrics in a traced one (an entry without a
        ``workloads`` list applies to every cell)."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]
