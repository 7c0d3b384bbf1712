"""The DLRM-DCNv2 configuration: its reference against the program served
through ``ServingRuntime`` at a tiny vocabulary, its costs against counts
from shapes, and its bfloat16 control against its limit."""

import json
import os

import numpy as np
import pytest
import jax

from chipbench import check, loadgen, program, registry
from chipbench.tests.conftest import ROOT

NAME = "dlrm-dcnv2-mlperf-d128"
ZIPF = {"dist": "zipf", "exponent": 1.1}


def full_cfg():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def tiny_cfg(store_kind):
    """4 fields of uneven hotness, 3 counts, d=8, bottom (16, 8), rank 4,
    top (16, 8); the configuration's serving otherwise."""
    cfg = full_cfg()
    hot = [3, 1, 5, 2]
    cfg["schema"] = {"field_sizes": [500, 7, 3000, 40],
                     "numeric": {"caps": [100, 1000, 10], "exponent": 1.1},
                     "hotness": hot}
    cfg.update(embed_dim=8, hidden=[16, 8], cross_layers=2)
    cfg["arch"] = {"n_numeric": 3, "hotness": hot, "bottom": [16, 8],
                   "cross_rank": 4,
                   "matmul_precision": cfg["matmul_precision"]}
    cfg["init"] = {"table_std": 0.05}
    cfg["store"] = {"kind": store_kind, "capacity": 256,
                    "row_dtype": "float32", "admit_requests": 256,
                    "refresh_every": None}
    cfg["batching"] = {"policy": "timeout", "buckets": [16, 64],
                       "max_wait_ms": 1.0}
    return cfg


@pytest.fixture(scope="module")
def ref_model():
    return registry.Benchmark().model("dlrm_dcnv2")


@pytest.mark.parametrize("store_kind", ["cached", "dense"])
def test_served_through_the_runtime_equals_the_reference(ref_model,
                                                         store_kind):
    cfg = tiny_cfg(store_kind)
    key = jax.random.PRNGKey(2**31 + 5)
    rows = loadgen.request_rows(np.random.default_rng(3), 200,
                                cfg["schema"], ZIPF)
    with jax.default_matmul_precision("highest"):
        dep = program.Deployment(cfg, program.make_params(cfg, ref_model,
                                                          key))
        try:
            dep.warm_plans()
            dep.start()
            futs = [dep.submit(r) for r in rows]
            served = np.array([f.result(timeout=120) for f in futs])
            dep.admit()
            again = np.array([f.result(timeout=120)
                              for f in [dep.submit(r) for r in rows[:64]]])
        finally:
            dep.stop()
        w = jax.jit(lambda k: ref_model.init_weights(cfg, k))(key)
        ref = np.asarray(jax.nn.sigmoid(ref_model.logits(cfg, w, rows)))
    np.testing.assert_allclose(served, ref, rtol=1e-5)
    np.testing.assert_allclose(again, ref[:64], rtol=1e-5)
    assert ref.std() > 1e-3


def test_configuration_against_counts_from_shapes(ref_model):
    from repro.models.ctr import CTR_MODELS
    cfg = full_cfg()
    hot = cfg["schema"]["hotness"]
    assert cfg["arch"]["hotness"] == hot and sum(hot) == 214
    assert cfg["arch"]["n_numeric"] == len(cfg["schema"]["numeric"]["caps"])
    spec = program.model_spec(cfg)
    assert spec.row_width == 13 + 214
    # the program's products at the precision the reference runs
    assert cfg["arch"]["matmul_precision"] == cfg["matmul_precision"]
    assert spec.matmul_precision == "highest"
    # the program's parameter shapes, without making them
    model = CTR_MODELS[cfg["model"]](spec)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    dense = [leaf.shape for k, v in shapes.items() if k != "emb"
             for leaf in jax.tree.leaves(v) if len(leaf.shape) == 2]
    flops = sum(2 * a * b for a, b in dense)
    assert ref_model.flops_per_request(cfg) == flops == 32_060_928
    assert 340_992 + 3 * 2 * 2 * 3456 * 512 + 10_486_272 == flops
    assert ref_model.gather_bytes(cfg, 1) == 123_736
    # the one-chip slice: an eighth of each published table, 76% of 16 GiB
    cut = cfg["one_chip_cut"]
    assert cfg["schema"]["field_sizes"] == [-(-n // 8)
                                            for n in cut["published"]]
    table = sum(cfg["schema"]["field_sizes"]) * 128 * 4
    assert table == 13_067_818_496 and table > 0.7 * (16 << 30)


def test_bf16_control_fails_the_limit(ref_model):
    """At the configuration's widths, hotness and limit (the vocabulary
    cut to fit a test), the bfloat16 reference does not pass the
    comparison that decides ``correct``; the float32 one does."""
    cfg = full_cfg()
    cfg["schema"]["field_sizes"] = [min(n, 2000)
                                    for n in cfg["schema"]["field_sizes"]]
    cfg["init"].pop("block_rows")
    w = jax.jit(lambda k: ref_model.init_weights(cfg, k))(
        jax.random.PRNGKey(7))
    rows = loadgen.request_rows(np.random.default_rng(5), 256,
                                cfg["schema"], ZIPF)
    res = check.control_checks(cfg, ref_model, w, rows)
    assert res["max_dscore"]["value"] > 3 * cfg["limits"]["max_dscore"]
    assert not check.passed(res)
    ref = check.reference_scores(cfg, ref_model, w, rows)
    assert check.passed(check.compare(ref, np.ones(len(rows), bool), ref,
                                      cfg["limits"]))

