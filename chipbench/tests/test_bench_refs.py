"""The model references against the program at a tiny schema, their
bfloat16 control against the limit, and the cost functions against hand
counts at the cells' sizes."""

import json
import os

import numpy as np
import pytest
import jax

from chipbench import check, loadgen, program, registry
from chipbench.tests.conftest import ROOT

MODELS = {"dcnv2": "dcnv2-criteo-d32-h1024",
          "deepfm": "deepfm-avazu-d16-h256"}


def full_cfg(name):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           MODELS[name] + ".json")) as f:
        return json.load(f)


def tiny_cfg(name):
    cfg = full_cfg(name)
    cfg["schema"]["field_sizes"] = [min(n, 3000)
                                    for n in cfg["schema"]["field_sizes"]]
    cfg["hidden"] = [64, 64, 64]
    cfg["store"].update(capacity=512)
    return cfg


@pytest.fixture(scope="module")
def bench():
    return registry.Benchmark()


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_agrees_with_the_program(bench, name):
    """Same weights, same ids: the program's compiled plan (through its
    CachedStore, the cells' store) and the float32 reference agree to
    float32 rounding on the CPU."""
    from repro.core.plan import compile_plan
    from repro.models.ctr import CTR_MODELS
    cfg = tiny_cfg(name)
    ref_model = bench.model(name)
    key = jax.random.PRNGKey(3)
    params = program.make_params(cfg, ref_model, key)
    model = CTR_MODELS[name](program.model_spec(cfg))
    params = model.use_store(program._store(cfg), params)
    ids = loadgen.zipf_ids(np.random.default_rng(0), 256,
                           cfg["schema"]["field_sizes"], 1.1)
    plan = compile_plan(model, params, "dual", 256)
    served = plan.predict(ids)
    weights = jax.jit(lambda k: ref_model.init_weights(cfg, k))(key)
    ref = check.reference_scores(cfg, ref_model, weights, ids)
    assert np.abs(served - ref).max() < 2e-6
    # the scores spread: a constant answer could not pass
    assert ref.std() > 1e-3


@pytest.mark.parametrize("name", sorted(MODELS))
def test_bf16_control_fails_the_limit(bench, name):
    """The control, the reference in bfloat16 (the precision below the
    configuration's), at the configuration's own widths and limit (only
    the vocabularies cut, to fit a test), goes through the comparison that
    decides ``correct`` and does not pass; the float32 reference does."""
    cfg = full_cfg(name)
    cfg["schema"]["field_sizes"] = [min(n, 3000)
                                    for n in cfg["schema"]["field_sizes"]]
    ref_model = bench.model(name)
    weights = jax.jit(lambda k: ref_model.init_weights(cfg, k))(
        jax.random.PRNGKey(5))
    ids = loadgen.zipf_ids(np.random.default_rng(1), 2048,
                           cfg["schema"]["field_sizes"], 1.1)
    res = check.control_checks(cfg, ref_model, weights, ids)
    assert res["max_dscore"]["value"] > 3 * cfg["limits"]["max_dscore"]
    assert not check.passed(res)
    ref = check.reference_scores(cfg, ref_model, weights, ids)
    ok = np.ones(len(ids), bool)
    assert check.passed(check.compare(ref, ok, ref, cfg["limits"]))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_takes_the_configured_precision(bench, name):
    """Every product of the reference runs at the configuration's
    ``matmul_precision``: ``reference_scores`` traces the model under it,
    and each of the model's dots carries it (the CPU computes float32 at
    any setting, so the lowered program is what shows it)."""
    cfg = tiny_cfg(name)
    ref_model = bench.model(name)
    w = jax.jit(lambda k: ref_model.init_weights(cfg, k))(
        jax.random.PRNGKey(6))
    ids = loadgen.zipf_ids(np.random.default_rng(3), 8,
                           cfg["schema"]["field_sizes"], 1.1)
    seen = []

    class Traced:
        @staticmethod
        def logits(c, wt, block):
            seen.append(jax.config.jax_default_matmul_precision)
            return ref_model.logits(c, wt, block)
    for p in ("default", "highest"):
        check.reference_scores({**cfg, "matmul_precision": p}, Traced, w,
                               ids)
        with jax.default_matmul_precision(p):
            text = jax.jit(lambda wt, b: ref_model.logits(cfg, wt, b)) \
                .lower(w, ids).as_text()
        dots = text.count("stablehlo.dot_general")
        assert dots >= len(cfg["hidden"]) + 1
        assert text.count("precision = [HIGHEST, HIGHEST]") == (
            dots if p == "highest" else 0)
    assert seen == ["default", "highest"]


def test_compare_counts_failures_and_nan():
    ref = np.full(4, 0.5, np.float32)
    served = np.array([0.5, np.nan, 0.5, 0.5], np.float32)
    ok = np.array([True, True, False, True])
    res = check.compare(served, ok, ref, {"max_dscore": 1e-3})
    assert res["max_dscore"]["value"] == 1.0
    assert res["unanswered"]["value"] == 1
    assert not check.passed(res)


def test_reference_blocks_match_one_call(bench):
    """Blocked evaluation (the last block padded) equals row-at-a-time."""
    cfg = tiny_cfg("dcnv2")
    ref_model = bench.model("dcnv2")
    w = jax.jit(lambda k: ref_model.init_weights(cfg, k))(
        jax.random.PRNGKey(0))
    ids = loadgen.zipf_ids(np.random.default_rng(2), check.BLOCK + 7,
                           cfg["schema"]["field_sizes"], 1.1)
    got = check.reference_scores(cfg, ref_model, w, ids)
    want = np.asarray(jax.nn.sigmoid(ref_model.logits(cfg, w, ids[-7:])))
    np.testing.assert_allclose(got[-7:], want, rtol=0, atol=1e-6)


def test_costs_against_hand_counts(bench):
    dcn, dfm = full_cfg("dcnv2"), full_cfg("deepfm")
    m, f = bench.model("dcnv2"), bench.model("deepfm")
    # DCNv2: 3 cross layers of 1248x1248, MLP 1248-1024-1024-1024, head
    # (1248 + 1024) -> 1; 2 FLOP per multiply-add
    d = 39 * 32
    assert m.flops_per_request(dcn) == 2 * (
        3 * d * d + d * 1024 + 2 * 1024 * 1024 + (d + 1024))
    assert m.flops_per_request(dcn) == 16_099_776
    # DeepFM: MLP 384-256-256-256 and the 256 -> 1 head
    assert f.flops_per_request(dfm) == 2 * (
        384 * 256 + 2 * 256 * 256 + 256) == 459_264
    # a 512-row step reads and writes 512x39 rows of 32 floats, reads ids
    assert m.gather_bytes(dcn, 512) == 512 * 39 * (2 * 32 * 4 + 4) \
        == 5_191_680
    # DeepFM gathers 16-wide rows and 1-wide FM weights
    assert f.gather_bytes(dfm, 512) == 512 * 24 * (2 * 16 * 4 + 4) \
        + 512 * 24 * (2 * 1 * 4 + 4) == 1_769_472


@pytest.mark.parametrize("store,policy,tol", [
    ({"kind": "cached", "capacity": 512, "row_dtype": "float32"},
     "timeout", 2e-6),
    ({"kind": "host", "capacity": 512, "staging_capacity": 4096,
      "row_dtype": "float32"}, "bucketed", 2e-6),
    ({"kind": "dense", "row_dtype": "float32"}, "timeout", 2e-6),
    # int8 rows: within the repo's int8 parity gate, not float32's
    ({"kind": "cached", "capacity": 512, "row_dtype": "int8"},
     "timeout", 1e-2),
])
def test_every_store_kind_serves_through_the_runtime(bench, store, policy,
                                                     tol):
    """The deployment kinds a configuration file may name, served through
    ``ServingRuntime`` and checked against the reference."""
    cfg = tiny_cfg("dcnv2")
    cfg["store"] = {**store, "admit_requests": 256, "refresh_every": None}
    cfg["batching"] = {"policy": policy, "buckets": [16, 64],
                       "max_wait_ms": 1.0}
    ref_model = bench.model("dcnv2")
    key = jax.random.PRNGKey(9)
    dep = program.Deployment(cfg, program.make_params(cfg, ref_model, key))
    ids = loadgen.zipf_ids(np.random.default_rng(4), 100,
                           cfg["schema"]["field_sizes"], 1.1)
    try:
        dep.warm_plans()
        dep.start()
        futs = [dep.submit(r) for r in ids]
        served = np.array([f.result(timeout=60) for f in futs])
        dep.admit()
    finally:
        dep.stop()
    weights = jax.jit(lambda k: ref_model.init_weights(cfg, k))(key)
    ref = check.reference_scores(cfg, ref_model, weights, ids)
    assert np.abs(served - ref).max() < tol
