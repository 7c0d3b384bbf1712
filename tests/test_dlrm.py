"""DLRM-DCNv2 on the normal serving path, on the CPU at a tiny size:
request rows wider than the field count (numeric counts, then uneven
multi-hot id slots), the pooled lookup, the bottom MLP beside the lookup,
the low-rank cross and its tiled elementwise kernel."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import ctr_spec
from repro.core import DualParallelExecutor, compile_plan
from repro.core.scheduler import full_order
from repro.embedding import CachedStore, HostBackedStore
from repro.kernels import ref as kref
from repro.kernels.fused_cross import _width_block, fused_cross_v2
from repro.models.ctr import CTR_MODELS, CTRModelSpec
from repro.serving import BucketedBatch, InferenceEngine, ServingRuntime

HOT = (3, 1, 5, 2)
SIZES = (50, 7, 300, 20)
M = 3


def spec(**kw):
    base = dict(name="dlrm-tiny", field_sizes=SIZES, embed_dim=8,
                hidden=(16, 8), cross_layers=2, n_numeric=M, hotness=HOT,
                bottom=(16, 8), cross_rank=4)
    return CTRModelSpec(**{**base, **kw})


def rows(n, seed=0):
    """Request rows: ``M`` counts, then each field's ``h_i`` ids."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 1000, (n, M))
    ids = [rng.integers(0, s, (n, h)) for s, h in zip(SIZES, HOT)]
    return np.concatenate([counts, *ids], axis=1).astype(np.int32)


def numpy_forward(params, model, x):
    """The model's equations in float64 numpy over its own parameters."""
    sp = model.spec
    table = np.asarray(model.embedding.dense_view(params["emb"]),
                       np.float64)
    offs = np.concatenate([[0], np.cumsum(SIZES)[:-1]])
    ids = x[:, M:]
    pooled, s = [], 0
    for f, h in enumerate(HOT):
        pooled.append(sum(table[ids[:, s + j] + offs[f]] for j in range(h)))
        s += h

    def dense(h, layer, relu):
        out = h @ np.asarray(layer["w"], np.float64) + np.asarray(
            layer["b"], np.float64)
        return np.maximum(out, 0) if relu else out
    z = np.log1p(x[:, :M].astype(np.float64))
    for layer in params["bottom"]:
        z = dense(z, layer, True)
    x0 = np.concatenate([z, *pooled], axis=1)
    xl = x0
    for layer in params["cross"]:
        proj = xl @ np.asarray(layer["v"], np.float64)
        xl = x0 * dense(proj, layer, False) + xl
    h = xl
    for layer in params["top"]:
        h = dense(h, layer, True)
    return dense(h, params["head"], False)[:, 0]


@pytest.mark.parametrize("precision", [None, "highest"])
def test_plan_matches_the_equations(precision):
    """Bottom MLP of log1p counts, pooled uneven fields, the low-rank
    cross ``x0 ⊙ ((x V) W + b) + x``, top MLP and head, at every level of
    the Fig.-8 ladder: with the products' precision from the context
    (None) or from the spec."""
    model = CTR_MODELS["dlrm_dcnv2"](spec(matmul_precision=precision))
    params = model.init(jax.random.PRNGKey(0))
    assert params["cross"][0]["v"].shape == (8 + 4 * 8, 4)
    x = rows(24)
    want = numpy_forward(params, model, x)
    for level in ("naive", "fused_emb", "fused_all", "dual"):
        with jax.default_matmul_precision(precision or "highest"):
            plan = compile_plan(model, params, level, 32)
            got = np.asarray(plan(jnp.asarray(np.concatenate(
                [x, np.zeros((8, x.shape[1]), np.int32)]))))[:24, 0]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=level)
    assert plan.row_width == M + sum(HOT) == model.spec.row_width


@pytest.mark.parametrize("store", ["dense", "cached"])
def test_pooled_lookup_equals_a_loop_over_slots(store):
    model = CTR_MODELS["dlrm_dcnv2"](spec())
    params = model.init(jax.random.PRNGKey(1))
    if store == "cached":
        params = model.use_store(
            CachedStore(model.spec.embedding_spec(), capacity=40), params)
    table = np.asarray(model.embedding.dense_view(params["emb"]))
    offs = model.embedding.spec.offsets
    slots = rows(9, seed=2)[:, M:]
    got = np.asarray(model.embedding.apply(params["emb"],
                                           jnp.asarray(slots)))
    want = np.zeros((9, len(SIZES), 8), np.float32)
    for b in range(9):
        s = 0
        for f, h in enumerate(HOT):
            for _ in range(h):
                want[b, f] += table[offs[f] + slots[b, s]]
                s += 1
    np.testing.assert_allclose(got, want.reshape(9, -1), rtol=1e-6,
                               atol=1e-7)


def test_observe_counts_each_id_slot_at_its_field():
    """The engine hands the store the id slots, never the counts: hits
    plus misses are batch × Σh, and each slot's row is counted at its
    field's offset."""
    model = CTR_MODELS["dlrm_dcnv2"](spec())
    store = CachedStore(model.spec.embedding_spec(), capacity=16)
    eng = InferenceEngine(model, model.init(jax.random.PRNGKey(2)),
                          policy=BucketedBatch((8, 16)), store=store)
    x = rows(16, seed=3)
    eng.submit_many(list(x))
    eng.serve_pending()
    assert store.stats.hits + store.stats.misses == 16 * sum(HOT)
    want = np.zeros(store.spec.rows, np.int64)
    np.add.at(want, (x[:, M:] + store.spec.slot_offsets).reshape(-1), 1)
    np.testing.assert_array_equal(store._counts, want)
    st = eng.stats.snapshot()
    assert st.emb_cache_hits + st.emb_cache_misses == 16 * sum(HOT)


@pytest.mark.parametrize("name", ["dcnv2", "deepfm", "dlrm_dcnv2"])
def test_served_scores_bit_identical_to_plan_predict(name):
    """Through ServingRuntime, DCNv2 and DeepFM serve what a plain
    ``plan.predict`` of their one-id-a-field rows gives, and DLRM-DCNv2
    what it gives of its wide rows."""
    if name == "dlrm_dcnv2":
        sp, x = spec(), rows(40, seed=4)
    else:
        sp = ctr_spec(name, "criteo", embed_dim=8, hidden=32,
                      max_field=500)
        rng = np.random.default_rng(4)
        x = np.stack([rng.integers(0, sp.field_sizes)
                      for _ in range(40)]).astype(np.int32)
    model = CTR_MODELS[name](sp)
    params = model.init(jax.random.PRNGKey(5))
    rt = ServingRuntime(pool_size=2)
    eng = rt.add_model("m", model, params, policy=BucketedBatch((64,)),
                       store=CachedStore(sp.embedding_spec(), capacity=64))
    try:
        rt.warmup()
        rt.start()
        futs = [rt.submit("m", r) for r in x]
        served = np.array([f.result(timeout=120.0) for f in futs])
    finally:
        rt.stop()
    plan = eng.plan_for(64)
    assert plan.row_width == x.shape[1]
    np.testing.assert_array_equal(served, plan.predict(x))


def test_bottom_runs_beside_the_embedding():
    """The dual level interleaves the lookup and the bottom MLP (the
    graph's two branches); one-id-a-field models keep explicit ∥
    implicit, after the embedding, in the order they always had."""
    model = CTR_MODELS["dlrm_dcnv2"](spec())
    ex = DualParallelExecutor(model.build_graph, level="dual")
    graph, order = ex.prepare(model.init(jax.random.PRNGKey(6)))
    assert graph.branches == ("embedding", "bottom")
    assert {graph.op(n).module for n in ex.stats.queue[:2]} == {
        "embedding", "bottom"}
    assert graph.is_valid_order(order)
    for name in ("dcn", "dcnv2", "deepfm", "widedeep"):
        m = CTR_MODELS[name](ctr_spec(name, "criteo", 8, 32,
                                      max_field=200))
        ex = DualParallelExecutor(m.build_graph, level="dual")
        graph, order = ex.prepare(m.init(jax.random.PRNGKey(0)))
        sched = ex.stats.queue
        pre = [op.name for op in graph.ops if op.module == "embedding"]
        post = [op.name for op in graph.ops if op.module == "head"]
        assert order == pre + list(sched) + post, name


def test_one_id_models_refuse_wide_rows_and_staging_refuses_them():
    with pytest.raises(ValueError, match="one id a field"):
        CTR_MODELS["dcnv2"](spec())
    for kw in ({"bottom": ()}, {"cross_rank": 0}):
        with pytest.raises(ValueError, match="cross rank"):
            CTR_MODELS["dlrm_dcnv2"](spec(**kw))
    model = CTR_MODELS["dlrm_dcnv2"](spec())
    host = HostBackedStore(model.spec.embedding_spec(), capacity=16,
                           staging_capacity=256)
    with pytest.raises(NotImplementedError, match="staging"):
        InferenceEngine(model, model.init(jax.random.PRNGKey(7)),
                        store=host)
    with pytest.raises(ValueError, match="hotness"):
        spec(hotness=(1, 2)).embedding_spec()


@pytest.mark.parametrize("shape,blocks", [
    ((256, 3456), 3),       # 27 lane tiles in 3 blocks of 9
    ((256, 4992), 3),       # 39 lane tiles in 3 blocks of 13
    ((256, 2200), 2),       # not a multiple of 128: a ragged last block
    ((100, 1248), 1),       # DCNv2-Criteo's tail: one block, whole width
])
def test_fused_cross_v2_tiles_the_width(shape, blocks):
    b, dim = shape
    bd = _width_block(min(256, b), dim, 4)
    assert -(-dim // bd) == blocks
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    x0, xw, x = (jax.random.normal(k, shape, jnp.float32) for k in keys)
    got = fused_cross_v2(x0, xw, x, interpret=True)
    # jitted, so the multiply-add contracts as in the kernel's body
    want = jax.jit(kref.ref_cross_v2_elementwise)(x0, xw, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_full_order_is_the_graph_order_outside_the_branches():
    """The interleaved lookup and bottom MLP come first (no op precedes
    both), then the interaction, the cross and the top MLP in graph
    order."""
    model = CTR_MODELS["dlrm_dcnv2"](spec())
    ex = DualParallelExecutor(model.build_graph, level="dual")
    graph, order = ex.prepare(model.init(jax.random.PRNGKey(9)))
    queue = list(ex.stats.queue)
    assert order == full_order(graph, _schedule(ex))
    assert order[:len(queue)] == queue
    rest = [op.name for op in graph.ops if op.name not in set(queue)]
    assert order[len(queue):] == rest
    assert graph.op(rest[0]).module == "interaction"
    assert graph.op(order[-1]).module == "top"


def _dot_precisions(jaxpr) -> list:
    """The ``precision`` of every ``dot_general`` in ``jaxpr`` and the
    jaxprs it calls (a Pallas kernel's body excepted)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    out += _dot_precisions(sub)
    return out


@pytest.mark.parametrize("name", ["dcn", "dcnv2", "deepfm", "widedeep",
                                  "dlrm_dcnv2"])
def test_matmul_precision_reaches_every_product(name):
    """``spec.matmul_precision`` is the precision of every dense product
    the model's step traces; None leaves each to XLA's default."""
    for precision in (None, "highest"):
        if name == "dlrm_dcnv2":
            sp, x = spec(matmul_precision=precision), rows(4)
        else:
            sp = dataclasses.replace(
                ctr_spec(name, "criteo", 8, 32, max_field=200),
                matmul_precision=precision)
            x = np.zeros((4, sp.k), np.int32)
        model = CTR_MODELS[name](sp)
        params = model.init(jax.random.PRNGKey(10))
        got = _dot_precisions(jax.make_jaxpr(model.apply)(
            params, jnp.asarray(x)).jaxpr)
        want = None if precision is None else (
            jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
        assert got and all(p == want for p in got), (precision, got)


def _schedule(ex):
    from repro.core.scheduler import Schedule
    return Schedule(streams={}, queue=list(ex.stats.queue),
                    policy=ex.stats.schedule_policy)
