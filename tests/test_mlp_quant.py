"""Quantized compute (compute_dtype="int8") through plans and serving.

The dense-branch half of the quantization story: per-output-channel int8
weights baked at plan compile, per-row int8 activations, fused
dequant+bias+ReLU — scored against the fp32 plan and exercised through
the engine with the full int8 stack (rows + matmuls) under refresh.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ctr_spec
from repro.core import COMPUTE_DTYPES, compile_plan, plan_key_for
from repro.data.synthetic import CRITEO, synthetic_batch
from repro.models.ctr import CTR_MODELS

VOCAB = 2_000
BATCH = 16


def _setup(model_name, hidden=64):
    spec = ctr_spec(model_name, "criteo", 8, hidden, max_field=VOCAB)
    if model_name == "dlrm_dcnv2":
        # DLRM-DCNv2 reads numeric counts before the ids (13, as Criteo)
        spec = dataclasses.replace(spec, n_numeric=13, bottom=(hidden, 8),
                                   cross_rank=16)
    model = CTR_MODELS[model_name](spec)
    params = model.init(jax.random.PRNGKey(0))
    ids = synthetic_batch(CRITEO.scaled(VOCAB), 5, BATCH)["ids"]
    counts = jnp.arange(BATCH * spec.n_numeric, dtype=jnp.int32) % 997
    ids = jnp.concatenate([counts.reshape(BATCH, -1), ids], axis=1)
    return model, params, ids


def test_compute_dtype_is_plan_identity():
    model, params, _ = _setup("dcn")
    k32 = plan_key_for(model, "dual", BATCH)
    k8 = plan_key_for(model, "dual", BATCH, compute_dtype="int8")
    assert k32 != k8
    assert k32.compute_dtype == "fp32" and k8.compute_dtype == "int8"
    assert set(COMPUTE_DTYPES) == {"fp32", "int8"}


def test_compile_plan_rejects_unknown_dtype():
    model, params, _ = _setup("dcn")
    with pytest.raises(ValueError, match="compute_dtype"):
        compile_plan(model, params, "dual", BATCH, compute_dtype="int4")


@pytest.mark.parametrize("model_name", list(CTR_MODELS))
def test_int8_plan_scores_close_to_fp32(model_name):
    model, params, ids = _setup(model_name)
    p32 = compile_plan(model, params, "dual", BATCH)
    p8 = compile_plan(model, params, "dual", BATCH, compute_dtype="int8")
    assert p32.key != p8.key
    s32 = np.asarray(p32(ids)).reshape(-1)
    s8 = np.asarray(p8(ids)).reshape(-1)
    # logit-level budget on untrained params; the trained, score-level
    # gate is benchmarks/accuracy_parity --quant-mlp
    assert float(np.abs(s32 - s8).max()) < 1e-2


def test_int8_plan_stats_counters():
    model, params, _ = _setup("widedeep", hidden=64)
    p8 = compile_plan(model, params, "dual", BATCH, compute_dtype="int8")
    st = p8.stats
    assert st.compute_dtype == "int8"
    assert st.mlp_quant_matmuls == 3              # (64,)*3 deep branch
    # int8 payload + 4B/channel scales vs 4B/elem fp32: >= 3.5x smaller
    fp32_bytes = st.mlp_quant_weight_bytes + st.mlp_quant_weight_bytes_saved
    assert st.mlp_quant_weight_bytes > 0
    assert fp32_bytes / st.mlp_quant_weight_bytes >= 3.5

    p32 = compile_plan(model, params, "dual", BATCH)
    assert p32.stats.compute_dtype == "fp32"
    assert p32.stats.mlp_quant_matmuls == 0
    assert p32.stats.mlp_quant_weight_bytes == 0


def test_engine_int8_stack_refresh_is_recompile_free():
    """int8 rows + int8 matmuls served together: a mid-stream refresh is
    a tensor swap — plan cache intact, counters flowing."""
    from repro.embedding import CachedStore
    from repro.serving import FixedBatch, InferenceEngine

    model, params, _ = _setup("dcn")
    store = CachedStore(model.spec.embedding_spec(), capacity=256,
                        row_dtype="int8")
    eng = InferenceEngine(model, params, policy=FixedBatch(BATCH),
                          store=store, compute_dtype="int8")
    ids = synthetic_batch(CRITEO.scaled(VOCAB), 9, BATCH * 4)["ids"]
    waves = np.array_split(np.asarray(ids), 2)

    eng.submit_many(list(waves[0]))
    first = eng.serve_pending()
    misses = eng.stats.cache_misses
    assert misses >= 1
    eng.refresh_cache()
    eng.submit_many(list(waves[1]))
    second = np.concatenate([eng.serve_pending(), eng.flush()])
    assert eng.stats.cache_misses == misses       # zero recompiles
    assert eng.stats.emb_cache_refreshes == 1
    assert first.size + second.size == BATCH * 4

    s = eng.stats
    # 3 q8 matmuls per executed batch, mirrored weight-byte counters
    assert s.mlp_quant_matmuls == 3 * s.n_batches
    assert s.mlp_quant_weight_bytes > 0
    assert s.mlp_quant_weight_bytes_saved > 3.5 * 0  # present and positive
    assert (s.mlp_quant_weight_bytes + s.mlp_quant_weight_bytes_saved
            ) / s.mlp_quant_weight_bytes >= 3.5


def test_runtime_aggregates_mlp_quant_counters():
    from repro.serving import FixedBatch, ServingRuntime

    rt = ServingRuntime()
    for name in ("dcn", "deepfm"):
        model, params, _ = _setup(name)
        rt.add_model(name, model, params, policy=FixedBatch(BATCH),
                     compute_dtype="int8")
    ids = synthetic_batch(CRITEO.scaled(VOCAB), 13, BATCH)["ids"]
    for name in ("dcn", "deepfm"):
        rt.submit_many(name, list(np.asarray(ids)))
        rt.engine(name).serve_pending()
    agg = rt.stats()
    per = [rt.engine(n).stats for n in ("dcn", "deepfm")]
    assert agg.mlp_quant_matmuls == sum(s.mlp_quant_matmuls for s in per) > 0
    assert agg.mlp_quant_weight_bytes == sum(s.mlp_quant_weight_bytes
                                             for s in per) > 0
