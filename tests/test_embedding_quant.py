"""Quantized embedding tier tests — int8 rows with in-kernel dequant.

Acceptance surface: ``row_dtype="int8"`` on ``CachedStore`` /
``HostBackedStore`` serves within the per-row grid-step bound of the fp32
``DenseStore`` (one-hot + multi-hot, pre and post ``refresh()``, on a
simulated mesh with the scale leaves replicated like ``slot_of_row``),
moves ``d + 4`` wire bytes per row instead of ``4·d``, keeps refreshes
recompile-free (the scales are runtime plan inputs), and the fp32 default
stays bit-exact and untouched. The shared absmax helpers in ``repro.quant``
round-trip within half a grid step and keep zero rows exactly zero.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import quant
from repro.compat import make_mesh
from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO, zipf_ids
from repro.embedding import (CachedStore, DenseStore,
                             FusedEmbeddingCollection, FusedEmbeddingSpec,
                             HostBackedStore)
from repro.models.ctr import CTR_MODELS
from repro.serving import FixedBatch, InferenceEngine

SPEC = FusedEmbeddingSpec(field_sizes=(60, 7, 350, 90), dim=8)
SCHEMA = CRITEO.scaled(2_000)
SPEC_KW = dict(embed_dim=8, hidden=64, max_field=2_000)


def needs(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (run under XLA_FLAGS="
               "--xla_force_host_platform_device_count=8)")


def traffic(batch=128, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack([rng.integers(0, s, size=batch)
                                 for s in SPEC.field_sizes], axis=1),
                       dtype=jnp.int32)


def grid_bound(table, ids, offsets):
    """Per-element error bound of the int8 round trip: half a grid step
    of each gathered row's absmax scale (+ fp slack)."""
    scale = np.asarray(quant.absmax_scale(np.asarray(table)))
    rows = np.asarray(ids) + np.asarray(offsets)[None, :]
    return scale[rows] * 0.5 + 1e-6


# --- repro.quant helpers ----------------------------------------------------

def test_quant_round_trip_within_half_grid_step():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(50, 16)).astype(np.float32) * 0.3
    q, scale = quant.quantize_rows(x)
    assert q.dtype == np.int8 and scale.shape == (50, 1)
    err = np.abs(quant.dequantize_rows(q, scale) - x)
    assert np.all(err <= scale * 0.5 + 1e-7)


def test_quant_zero_rows_round_trip_to_exact_zero():
    x = np.zeros((4, 8), np.float32)
    q, scale = quant.quantize_rows(x)
    assert np.all(q == 0) and np.all(scale > 0)   # eps-floored, not 0/0
    assert np.all(quant.dequantize_rows(q, scale) == 0.0)


def test_quant_symmetric_grid_never_uses_minus_128():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(100, 16)).astype(np.float32) * 10.0
    q, _ = quant.quantize_rows(x)
    assert q.min() >= -127 and q.max() <= 127


def test_quant_jnp_and_numpy_agree():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(20, 8)).astype(np.float32)
    qn, sn = quant.quantize_rows(x)
    qj, sj = quant.quantize_rows(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(qj), qn)
    np.testing.assert_array_equal(np.asarray(sj), sn)


# --- spec surface -----------------------------------------------------------

def test_spec_wire_row_bytes():
    assert SPEC.wire_row_bytes == SPEC.dim * 4 and not SPEC.quantized
    q = dataclasses.replace(SPEC, row_dtype="int8")
    assert q.quantized and q.wire_row_bytes == SPEC.dim + 4


def test_spec_rejects_unknown_row_dtype():
    with pytest.raises(ValueError):
        dataclasses.replace(SPEC, row_dtype="int4")


def test_describe_distinguishes_quantized_stores():
    """PlanKeys hash store.describe(): fp32 and int8 stores over the same
    spec must never collide in an engine's plan cache."""
    fp = CachedStore(SPEC, capacity=16)
    q8 = CachedStore(SPEC, capacity=16, row_dtype="int8")
    assert fp.describe() != q8.describe() and ",int8" in q8.describe()
    hq = HostBackedStore(SPEC, capacity=16, row_dtype="int8")
    assert ",int8" in hq.describe()


# --- store-level parity vs fp32 DenseStore ----------------------------------

@pytest.mark.parametrize("store_cls", [CachedStore, HostBackedStore])
def test_quantized_store_within_grid_bound_of_dense(store_cls):
    dense = FusedEmbeddingCollection(SPEC)
    pd = dense.init(jax.random.PRNGKey(0))
    store = store_cls(SPEC, capacity=48, row_dtype="int8")
    coll = FusedEmbeddingCollection(SPEC, store=store)
    pq = store.from_dense(pd)
    ids = traffic()
    if store_cls is HostBackedStore:
        pq = store.stage(pq, np.asarray(ids))     # resolve misses first
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    got = np.asarray(coll.apply(pq, ids, strategy="jnp"))
    bound = grid_bound(dense.dense_view(pd), ids,
                       SPEC.offsets).repeat(SPEC.dim, axis=-1)
    assert np.all(np.abs(got - want).reshape(bound.shape) <= bound)
    # Pallas kernel twin agrees with the jnp twin on the same int8 grid
    got_pl = np.asarray(coll.apply(pq, ids[:16], strategy="pallas",
                                   interpret=True))
    np.testing.assert_allclose(got_pl, got[:16], rtol=1e-6, atol=1e-6)


def test_quantized_store_multihot_within_pooled_bound():
    h = 3
    spec = dataclasses.replace(SPEC, hotness=(h,) * SPEC.k)
    dense = FusedEmbeddingCollection(spec)
    pd = dense.init(jax.random.PRNGKey(0))
    store = CachedStore(spec, capacity=48, row_dtype="int8")
    coll = FusedEmbeddingCollection(spec, store=store)
    pq = store.from_dense(pd)
    rng = np.random.default_rng(3)
    ids = np.stack([rng.integers(0, s, size=(32, h))
                    for s in SPEC.field_sizes], axis=1)     # (b, k, h)
    # each field's h ids side by side: the hotness spec's slot layout
    slots = jnp.asarray(ids.reshape(32, -1), dtype=jnp.int32)
    want = np.asarray(dense.apply(pd, slots, strategy="jnp"))
    got = np.asarray(coll.apply(pq, slots, strategy="jnp"))
    scale = np.asarray(quant.absmax_scale(np.asarray(dense.dense_view(pd))))
    rows = ids + np.asarray(SPEC.offsets)[None, :, None]
    pooled = (scale[rows][..., 0] * 0.5 + 1e-6).sum(axis=-1, keepdims=True)
    err = np.abs(got - want).reshape(32, SPEC.k, SPEC.dim)
    assert np.all(err <= pooled + 1e-6)


def test_quantized_refresh_is_value_stable():
    """All tiers copy the same int8 grid, so a refresh (tier re-election)
    never changes served values — equality, not tolerance."""
    store = CachedStore(SPEC, capacity=32, row_dtype="int8")
    coll = FusedEmbeddingCollection(SPEC, store=store)
    params = coll.init(jax.random.PRNGKey(1))
    ids = traffic(seed=4)
    before = np.asarray(coll.apply(params, ids, strategy="jnp"))
    coll.observe(np.asarray(ids + np.asarray(SPEC.offsets)[None, :]))
    params = store.refresh(params)
    after = np.asarray(coll.apply(params, ids, strategy="jnp"))
    np.testing.assert_array_equal(after, before)
    assert store.stats.refreshes == 1


def test_dense_store_adopts_quantized_subtree():
    """DenseStore.adopt reconstitutes fp32 rows from an int8 subtree —
    exactly the dequantized grid, the only values that remain."""
    store = CachedStore(SPEC, capacity=16, row_dtype="int8")
    pq = store.init(jax.random.PRNGKey(2))
    dense = DenseStore(SPEC)
    pd = dense.adopt(pq)
    want = quant.dequantize_rows(np.asarray(store.unpack(pq["backing"])),
                                 np.asarray(pq["backing_scale"]))
    np.testing.assert_array_equal(np.asarray(dense.dense_view(pd)), want)


def test_collection_accepts_quantized_store_over_fp32_spec():
    """row_dtype is a store-layout knob, not a schema change: a collection
    built from an fp32 spec accepts the int8 store of the same schema."""
    store = CachedStore(SPEC, capacity=16, row_dtype="int8")
    coll = FusedEmbeddingCollection(SPEC, store=store)
    assert coll.store is store
    with pytest.raises(ValueError):
        FusedEmbeddingCollection(
            dataclasses.replace(SPEC, dim=SPEC.dim * 2), store=store)


# --- engine: wire bytes, counters, recompile-free refresh -------------------

def make_engine_pair(store_cls, model_name="widedeep", capacity=64,
                     row_dtype="int8", batch=8, mesh=None, dim=8):
    kw = dict(SPEC_KW, embed_dim=dim)
    spec = ctr_spec(model_name, "criteo", **kw)
    dense_model = CTR_MODELS[model_name](spec)
    dense = InferenceEngine(dense_model,
                            dense_model.init(jax.random.PRNGKey(0)),
                            policy=FixedBatch(batch), mesh=mesh)
    model = CTR_MODELS[model_name](spec)
    params = model.init(jax.random.PRNGKey(0))
    store = store_cls(spec.embedding_spec(), capacity=capacity,
                      row_dtype=row_dtype)
    eng = InferenceEngine(model, params, policy=FixedBatch(batch),
                          store=store, mesh=mesh)
    return dense, eng, store


def zipf_stream(n, seed=0, exponent=1.1):
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                               SCHEMA.field_sizes, exponent=exponent))


@pytest.mark.parametrize("store_cls", [CachedStore, HostBackedStore])
def test_engine_serves_quantized_within_tolerance_no_recompiles(store_cls):
    dense, eng, store = make_engine_pair(store_cls)
    ids = zipf_stream(40)
    want = dense.predict(ids)
    for wave in np.array_split(ids, 2):
        eng.submit_many(list(wave))
        eng.serve_pending()
        eng.refresh_cache()                       # swap mid-stream
    got = np.concatenate([eng.predict(ids)])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-2)
    assert store.stats.refreshes == 2
    assert eng.stats.cache_misses == 1            # compiled exactly once
    assert len(eng.cached_plans) == 1


def test_engine_mirrors_quant_counters():
    _, eng, store = make_engine_pair(CachedStore)
    ids = zipf_stream(16)
    eng.submit_many(list(ids))
    eng.serve_pending()
    s = eng.stats
    assert s.emb_quant_rows > 0
    assert s.emb_gather_bytes == store.stats.gather_bytes > 0
    assert s.emb_quant_bytes_saved == store.stats.quant_bytes_saved > 0
    # wire accounting: every gathered row moved d + 4 bytes, and the
    # saving per row is exactly 4·d − (d + 4)
    wire = store.wire_row_bytes
    assert s.emb_gather_bytes % wire == 0
    rows = s.emb_gather_bytes // wire
    assert s.emb_quant_bytes_saved == rows * (store.spec.dim * 4 - wire)


def test_host_resolved_wire_bytes_quarter_at_d32():
    """Same traffic, fp32 vs int8 host store at d=32: host→device wire
    traffic per resolved row shrinks by exactly 128/36. Uses the
    deterministic resolved count (staged + prefetched — the split between
    the two is a thread race, their union is the distinct miss set once
    staging exceeds it, mirroring the benchmark protocol)."""
    ids = zipf_stream(24, seed=5)
    out = {}
    for rd in (None, "int8"):
        spec = ctr_spec("widedeep", "criteo", **dict(SPEC_KW, embed_dim=32))
        emb = spec.embedding_spec()
        distinct = np.unique(ids + np.asarray(emb.offsets)[None, :]).size
        model = CTR_MODELS["widedeep"](spec)
        params = model.init(jax.random.PRNGKey(0))
        store = HostBackedStore(emb, capacity=64,
                                staging_capacity=distinct + 8 * emb.k,
                                row_dtype=rd)
        eng = InferenceEngine(model, params, policy=FixedBatch(8),
                              store=store)
        eng.submit_many(list(ids))
        eng.serve_pending()
        st = store.stats
        assert st.h2d_bytes % store.wire_row_bytes == 0
        resolved = st.staged_rows + st.prefetched_rows
        out[rd] = (resolved, resolved * store.wire_row_bytes)
    rows_fp, bytes_fp = out[None]
    rows_q8, bytes_q8 = out["int8"]
    assert rows_fp == rows_q8 > 0                 # tier choice is value-blind
    assert bytes_fp * 36 == bytes_q8 * 128        # exactly (d+4) vs 4·d


# --- mesh -------------------------------------------------------------------

@needs(8)
@pytest.mark.parametrize("shape,axes", [((2,), ("data",)),
                                        ((4, 2), ("data", "model"))])
def test_quantized_store_on_mesh_parity_with_dense(shape, axes):
    """int8 CachedStore on a real mesh: scores within tolerance of the
    fp32 dense engine on the same mesh, scale leaves replicated like
    slot_of_row, refresh recompile-free."""
    mesh = make_mesh(shape, axes)
    dense, eng, store = make_engine_pair(CachedStore, mesh=mesh)
    ids = zipf_stream(24, exponent=1.05)
    want = dense.predict(ids)
    eng.submit_many(list(ids))
    np.testing.assert_allclose(eng.serve_pending(), want, rtol=0, atol=1e-2)
    eng.refresh_cache()
    np.testing.assert_allclose(eng.predict(ids), want, rtol=0, atol=1e-2)
    assert eng.stats.cache_misses == 1            # refresh never recompiled
    key = eng.model.main_embedding_key
    for leaf in ("cache_scale", "backing_scale", "slot_of_row"):
        spec_t = tuple(eng.params[key][leaf].sharding.spec)
        assert all(ax is None for ax in spec_t), (leaf, spec_t)


@needs(8)
def test_quantized_partition_spec_replicates_scales():
    store = CachedStore(SPEC, capacity=32, row_dtype="int8")
    ps = store.partition_spec("model")
    assert {"cache_scale", "backing_scale"} <= set(ps)
    assert tuple(ps["cache_scale"]) == () == tuple(ps["backing_scale"])
