"""End-to-end behaviour tests for the DPIFrame system.

Covers: the Fig.-8 level ladder (numerical invariance), Alg.-2 scheduling,
C5 fusion bookkeeping, training convergence + checkpoint/restart, the
serving engine, and pipeline determinism (fault-tolerance substrate).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ctr_spec
from repro.core import DualParallelExecutor
from repro.data.synthetic import CRITEO, synthetic_batch
from repro.models.ctr import CTR_MODELS
from repro.training import (AdamWConfig, TrainLoopConfig, adamw_init,
                            adamw_update, roc_auc, run_train_loop,
                            latest_step, restore_checkpoint, save_checkpoint)

SCHEMA = CRITEO.scaled(2_000)
SPEC_KW = dict(embed_dim=8, hidden=64, max_field=2_000)


def make(model_name):
    spec = ctr_spec(model_name, "criteo", **SPEC_KW)
    if model_name == "dlrm_dcnv2":
        # DLRM-DCNv2 reads numeric counts before the ids (13, as Criteo)
        spec = dataclasses.replace(spec, n_numeric=13, bottom=(64, 8),
                                   cross_rank=16)
    model = CTR_MODELS[model_name](spec)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def request_rows(model, ids):
    """``ids`` behind the model's numeric counts, if it reads any."""
    m = model.spec.n_numeric
    counts = jnp.arange(ids.shape[0] * m, dtype=jnp.int32) % 997
    return jnp.concatenate([counts.reshape(ids.shape[0], m), ids], axis=1)


@pytest.mark.parametrize("model_name", list(CTR_MODELS))
def test_level_ladder_is_numerically_invariant(model_name):
    """Paper Table I: DPIFrame is a pure re-scheduling layer."""
    model, params = make(model_name)
    ids = request_rows(model, synthetic_batch(SCHEMA, 0, 64)["ids"])
    outs = {}
    for level in ("naive", "fused_emb", "fused_all", "dual"):
        ex = DualParallelExecutor(model.build_graph, level=level)
        outs[level] = np.asarray(ex.build(params)({"ids": ids}))
    for level, out in outs.items():
        np.testing.assert_allclose(out, outs["naive"], rtol=1e-5, atol=1e-6,
                                   err_msg=level)


@pytest.mark.parametrize("model_name", list(CTR_MODELS))
def test_fusion_reduces_dispatch_count(model_name):
    model, params = make(model_name)
    naive = DualParallelExecutor(model.build_graph, level="naive")
    naive.prepare(params)
    dual = DualParallelExecutor(model.build_graph, level="dual")
    dual.prepare(params)
    assert dual.stats.n_ops_after < naive.stats.n_ops_after
    assert dual.stats.schedule_policy == "breadth_first"


def test_breadth_first_queue_interleaves_branches():
    model, params = make("dcnv2")
    ex = DualParallelExecutor(model.build_graph, level="dual")
    graph, order = ex.prepare(params)
    # both branches appear within the first two queue slots
    mods = {graph.op(name).module for name in ex.stats.queue[:2]}
    assert mods == {"explicit", "implicit"}
    assert graph.is_valid_order(order)


def test_branch_order_ablation_changes_queue_head():
    model, params = make("deepfm")
    heads = {}
    for order in ("explicit_first", "implicit_first"):
        ex = DualParallelExecutor(model.build_graph, level="dual",
                                  branch_order=order)
        graph, _ = ex.prepare(params)
        heads[order] = graph.op(ex.stats.queue[0]).module
    assert heads["explicit_first"] == "explicit"
    assert heads["implicit_first"] == "implicit"


def test_training_learns_and_metrics_improve():
    model, params = make("dcnv2")
    opt = AdamWConfig(lr=3e-3)
    state = adamw_init(params, opt)

    @jax.jit
    def step_fn(state, batch):
        loss, grads = jax.value_and_grad(model.loss)(state.params, batch)
        state, m = adamw_update(state, grads, opt)
        return state, {"loss": loss, **m}

    losses = []
    for s in range(120):
        state, m = step_fn(state, synthetic_batch(SCHEMA, s, 256))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10])
    val = synthetic_batch(SCHEMA, 999, 2048)
    probs = np.asarray(model.predict_proba(state.params, val["ids"]))
    auc = roc_auc(np.asarray(val["labels"]), probs)
    assert auc > 0.55, f"planted signal not learned (auc={auc})"


def test_checkpoint_restart_resumes_exactly(tmp_path):
    model, params = make("dcn")
    opt = AdamWConfig(lr=1e-3)

    @jax.jit
    def step_fn(state, batch):
        loss, grads = jax.value_and_grad(model.loss)(state.params, batch)
        state, m = adamw_update(state, grads, opt)
        return state, {"loss": loss, **m}

    batch_fn = lambda s: synthetic_batch(SCHEMA, s, 64)
    cfg = TrainLoopConfig(total_steps=6, ckpt_every=3,
                          ckpt_dir=str(tmp_path / "a"), log_every=100)
    s1, _ = run_train_loop(step_fn, adamw_init(params, opt), batch_fn, cfg)
    # interrupted run: 3 steps, then a fresh loop resumes from the ckpt
    cfg2 = TrainLoopConfig(total_steps=3, ckpt_every=3,
                           ckpt_dir=str(tmp_path / "b"), log_every=100)
    run_train_loop(step_fn, adamw_init(params, opt), batch_fn, cfg2)
    cfg3 = TrainLoopConfig(total_steps=6, ckpt_every=3,
                           ckpt_dir=str(tmp_path / "b"), log_every=100)
    s2, _ = run_train_loop(step_fn, adamw_init(params, opt), batch_fn, cfg3)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_checkpoint_atomicity(tmp_path):
    tree = {"w": jnp.arange(10.0), "b": jnp.ones((3, 3))}
    save_checkpoint(str(tmp_path), 5, tree)
    # a stale tmp dir from a crashed writer must be ignored
    (tmp_path / ".tmp_step_7").mkdir()
    assert latest_step(str(tmp_path)) == 5
    back = restore_checkpoint(str(tmp_path), 5, tree)
    np.testing.assert_allclose(np.asarray(back["w"]), np.asarray(tree["w"]))


def test_serving_engine_batches_and_pads():
    from repro.serving import FixedBatch, InferenceEngine
    model, params = make("widedeep")
    eng = InferenceEngine(model, params, policy=FixedBatch(32), level="dual")
    eng.warmup()
    rng = np.random.default_rng(0)
    n = 50   # 32 + 18 (padded partial batch)
    rows = [np.array([rng.integers(0, s) for s in SCHEMA.field_sizes],
                     dtype=np.int32) for _ in range(n)]
    for r in rows:
        eng.submit(r)
    scores = eng.serve_pending()
    assert scores.shape == (n,)
    # sigmoid saturates to exactly 0.0/1.0 in f32 for |logit| > ~17
    assert np.all((scores >= 0) & (scores <= 1))
    assert eng.stats.n_batches == 2
    direct = np.asarray(model.predict_proba(params,
                                            jnp.asarray(np.stack(rows))))
    np.testing.assert_allclose(scores, direct, rtol=1e-5, atol=1e-5)


def test_data_pipeline_determinism():
    a = synthetic_batch(SCHEMA, 7, 32)
    b = synthetic_batch(SCHEMA, 7, 32)
    assert np.array_equal(np.asarray(a["ids"]), np.asarray(b["ids"]))
    c = synthetic_batch(SCHEMA, 8, 32)
    assert not np.array_equal(np.asarray(a["ids"]), np.asarray(c["ids"]))


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """Entry points turn on the persistent compile cache: a set
    JAX_COMPILATION_CACHE_DIR is left to JAX (nothing set in code);
    otherwise a fixed directory in the checkout, never a temp name."""
    import pathlib
    from repro.compile_cache import MAX_BYTES, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    size_before = jax.config.jax_compilation_cache_max_size
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
        assert enable_compile_cache() == "cache-from-env"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        root = pathlib.Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_compilation_cache_max_size == MAX_BYTES
        assert enable_compile_cache() == path           # stable per checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_max_size", size_before)
