"""Tiered EmbeddingStore tests — the cache-aware parameter-server subsystem.

Acceptance surface of the tiered-store refactor: ``CachedStore`` lookups
bit-exact with ``DenseStore`` (uniform and zipf traffic, one-hot and
multi-hot, single-device and 1×1 mesh, before and after refresh), traffic
counters behaving (hit-rate/cached-fraction grow with skew), and the
placement regression — sharding is derived from the store's
``partition_spec()``, not from ``"mega" in names``, so renamed/nested
embedding params still shard correctly.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.configs import ctr_spec
from repro.core import compile_plan
from repro.core.plan import _shard_params
from repro.data.synthetic import CRITEO, synthetic_batch, zipf_ids
from repro.embedding import (CachedStore, DenseStore,
                             FusedEmbeddingCollection, FusedEmbeddingSpec,
                             HostBackedStore)
from repro.models.ctr import CTR_MODELS

SPEC = FusedEmbeddingSpec(field_sizes=(60, 7, 350, 90), dim=8)
SCHEMA = CRITEO.scaled(2_000)
SPEC_KW = dict(embed_dim=8, hidden=64, max_field=2_000)


def make_pair(capacity=48, spec=SPEC):
    """Dense and cached collections over the *same* table values."""
    dense = FusedEmbeddingCollection(spec)
    params_d = dense.init(jax.random.PRNGKey(0))
    store = CachedStore(spec, capacity=capacity)
    cached = FusedEmbeddingCollection(spec, store=store)
    params_c = store.from_dense(params_d)
    return dense, params_d, cached, params_c, store


def traffic(batch=128, exponent=None, seed=0):
    """(b, k) ids — zipf when an exponent is given, else uniform."""
    key = jax.random.PRNGKey(seed)
    if exponent is not None:
        return zipf_ids(key, batch, SPEC.field_sizes, exponent=exponent)
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack([rng.integers(0, s, size=batch)
                                 for s in SPEC.field_sizes], axis=1),
                       dtype=jnp.int32)


# --- bit-exactness ----------------------------------------------------------

@pytest.mark.parametrize("exponent", [None, 1.3])
def test_cached_store_bit_exact_onehot(exponent):
    dense, pd, cached, pc, _ = make_pair()
    ids = traffic(exponent=exponent)
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    got = np.asarray(cached.apply(pc, ids, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    # kernel-body validation of the Pallas two-level gather
    got_pl = np.asarray(cached.apply(pc, ids[:16], strategy="pallas",
                                     interpret=True))
    np.testing.assert_array_equal(got_pl, want[:16])


@pytest.mark.parametrize("exponent", [None, 1.3])
def test_cached_store_bit_exact_multihot(exponent):
    h = 3
    spec = dataclasses.replace(SPEC, hotness=(h,) * SPEC.k)
    dense, pd, cached, pc, _ = make_pair(spec=spec)
    rng = np.random.default_rng(1)
    if exponent is None:
        ids = np.stack([rng.integers(0, s, size=(64, h))
                        for s in SPEC.field_sizes], axis=1)
    else:
        ids = np.stack([np.asarray(zipf_ids(jax.random.PRNGKey(t), 64,
                                            SPEC.field_sizes, exponent))
                        for t in range(h)], axis=-1)
    # each field's h ids side by side: the hotness spec's slot layout
    ids = jnp.asarray(ids.reshape(64, -1), dtype=jnp.int32)
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    got = np.asarray(cached.apply(pc, ids, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    want_pl = np.asarray(dense.apply(pd, ids[:8], strategy="pallas",
                                     interpret=True))
    got_pl = np.asarray(cached.apply(pc, ids[:8], strategy="pallas",
                                     interpret=True))
    np.testing.assert_array_equal(got_pl, want_pl)


@pytest.mark.parametrize("kind,row_dtype", [
    ("dense", None), ("cached", None), ("cached", "int8"),
    ("host", None), ("host", "int8")])
def test_lookup_pallas_matches_jnp(kind, row_dtype):
    """Each store's one lookup — its ``_route`` into ``gather_tiers`` —
    reads the same rows whichever gather runs: the Pallas kernel
    (interpret mode) equals the jnp twin bitwise for fp32 rows, and within
    rtol 1e-6 for int8 rows (dequantized in the kernel)."""
    params = DenseStore(SPEC).init(jax.random.PRNGKey(5))
    store = {"dense": lambda: DenseStore(SPEC),
             "cached": lambda: CachedStore(SPEC, capacity=48,
                                           row_dtype=row_dtype),
             "host": lambda: HostBackedStore(SPEC, capacity=48,
                                             staging_capacity=256,
                                             row_dtype=row_dtype)}[kind]()
    params = store.adopt(params)
    ids = traffic(batch=32, exponent=1.3)
    if store.needs_staging:
        params = store.stage(params, np.asarray(ids))
    offsets = jnp.asarray(SPEC.offsets)
    got = np.asarray(store.lookup(params, ids, offsets, strategy="pallas",
                                  interpret=True))
    want = np.asarray(store.lookup(params, ids, offsets, strategy="jnp"))
    assert got.shape == (32, SPEC.k * SPEC.dim)
    if row_dtype is None:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_cached_store_bit_exact_after_refresh():
    dense, pd, cached, pc, store = make_pair()
    ids = traffic(exponent=1.5)
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    cached.observe(np.asarray(ids))
    pc = store.refresh(pc)
    got = np.asarray(cached.apply(pc, ids, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    assert store.stats.refreshes == 1


def test_cached_capacity_clamps_to_rows():
    store = CachedStore(SPEC, capacity=10 * SPEC.rows)
    assert store.capacity == SPEC.rows
    coll = FusedEmbeddingCollection(SPEC, store=store)
    params = coll.init(jax.random.PRNGKey(0))
    ids = traffic(batch=32)
    dense = FusedEmbeddingCollection(SPEC)
    want = dense.apply(dense.init(jax.random.PRNGKey(0)), ids)
    np.testing.assert_array_equal(np.asarray(coll.apply(params, ids)),
                                  np.asarray(want))
    with pytest.raises(ValueError):
        CachedStore(SPEC, capacity=0)


# --- traffic counters -------------------------------------------------------

def test_hit_rate_and_cached_fraction_grow_with_skew():
    """At fixed capacity, zipfier traffic -> higher post-refresh hit rate
    and higher cached-traffic fraction (the HugeCTR premise)."""
    results = {}
    for exponent in (0.0, 1.1, 1.6):
        _, _, cached, pc, store = make_pair(capacity=32)
        for t in range(4):
            cached.observe(np.asarray(
                zipf_ids(jax.random.PRNGKey(t), 256, SPEC.field_sizes,
                         exponent=exponent)))
        pc = store.refresh(pc)
        h0, n0 = store.stats.hits, store.stats.lookups
        for t in range(4, 8):
            cached.observe(np.asarray(
                zipf_ids(jax.random.PRNGKey(t), 256, SPEC.field_sizes,
                         exponent=exponent)))
        rate = (store.stats.hits - h0) / (store.stats.lookups - n0)
        results[exponent] = (rate, store.cached_traffic_fraction)
    rates = [results[e][0] for e in (0.0, 1.1, 1.6)]
    fracs = [results[e][1] for e in (0.0, 1.1, 1.6)]
    assert rates == sorted(rates) and rates[0] < rates[-1], results
    assert fracs == sorted(fracs) and fracs[0] < fracs[-1], results


def test_refresh_admits_hot_rows_deterministically():
    _, _, cached, pc, store = make_pair(capacity=4)
    # all traffic on one id per field -> refresh must cache exactly those
    hot = np.array([[3, 2, 17, 5]] * 50, dtype=np.int64)
    cached.observe(hot)
    pc = store.refresh(pc)
    hot_rows = hot[0] + SPEC.offsets
    assert set(np.flatnonzero(np.asarray(pc["slot_of_row"]) >= 0)) \
        == set(hot_rows.tolist())
    h0 = store.stats.hits
    cached.observe(hot[:1])
    assert store.stats.hits - h0 == SPEC.k      # every lookup now hits
    assert store.cached_traffic_fraction == 1.0


def test_dense_store_counters_stay_zero():
    dense = FusedEmbeddingCollection(SPEC)
    dense.init(jax.random.PRNGKey(0))
    dense.observe(np.asarray(traffic(batch=8)))
    assert dense.store.stats.lookups == 0
    assert dense.store.cached_traffic_fraction == 1.0


# --- placement regression (the "mega" in names heuristic is gone) -----------

def test_partition_spec_shards_store_tables_by_structure():
    """Placement is derived from the store's partition_spec — the cached
    layout's leaves (backing/cache/slot_of_row) contain no "mega" yet the
    backing table still row-shards; cache tiers replicate."""
    spec = ctr_spec("dcnv2", "criteo", **SPEC_KW)
    model = CTR_MODELS["dcnv2"](
        spec, store=CachedStore(spec.embedding_spec(), capacity=64))
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    placed = _shard_params(params, mesh, "model",
                           model.partition_spec(params, "model"))
    backing_spec = placed["emb"]["backing"].sharding.spec
    assert tuple(backing_spec)[:1] == ("model",)
    assert tuple(placed["emb"]["cache"].sharding.spec) == ()
    assert tuple(placed["emb"]["slot_of_row"].sharding.spec) == ()
    assert tuple(placed["head"]["w"].sharding.spec) == ()


@pytest.mark.parametrize("model_name", ["dcnv2", "widedeep"])
def test_cached_model_on_mesh_matches_dense(model_name):
    spec = ctr_spec(model_name, "criteo", **SPEC_KW)
    dense_model = CTR_MODELS[model_name](spec)
    params = dense_model.init(jax.random.PRNGKey(0))
    ids = np.asarray(synthetic_batch(SCHEMA, 0, 16)["ids"])
    want = compile_plan(dense_model, params, "dual", 16).predict(ids)

    cmodel = CTR_MODELS[model_name](
        spec, store=CachedStore(spec.embedding_spec(), capacity=128))
    cparams = cmodel.init(jax.random.PRNGKey(0))
    got = compile_plan(cmodel, cparams, "dual", 16).predict(ids)
    # same rows, but two different programs: the dense plan compiles its
    # table in as a constant, the cached plan takes it as an argument, and
    # XLA fuses the dcnv2 dense net differently around each — scores may
    # differ in the last bit or two (seen: 1 ulp = 5.96e-8 on 2 of 16)
    np.testing.assert_allclose(got, want, rtol=4e-7, atol=0)

    mesh = make_mesh((1, 1), ("data", "model"))
    got_mesh = compile_plan(cmodel, cparams, "dual", 16,
                            mesh=mesh).predict(ids)
    np.testing.assert_allclose(got_mesh, want, rtol=1e-6, atol=1e-6)


def test_plan_key_distinguishes_stores():
    spec = ctr_spec("dcn", "criteo", **SPEC_KW)
    dense_model = CTR_MODELS["dcn"](spec)
    cmodel = CTR_MODELS["dcn"](
        spec, store=CachedStore(spec.embedding_spec(), capacity=64))
    params = dense_model.init(jax.random.PRNGKey(0))
    pk_dense = compile_plan(dense_model, params, "dual", 8).key
    pk_cached = compile_plan(cmodel, cmodel.init(jax.random.PRNGKey(0)),
                             "dual", 8).key
    assert pk_dense != pk_cached
    assert pk_dense.store.startswith("dense")
    assert pk_cached.store.startswith("cached")


def test_executor_stats_carry_store_identity():
    spec = ctr_spec("dcn", "criteo", **SPEC_KW)
    model = CTR_MODELS["dcn"](
        spec, store=CachedStore(spec.embedding_spec(), capacity=64))
    plan = compile_plan(model, model.init(jax.random.PRNGKey(0)), "dual", 8)
    assert plan.stats.embedding_store.startswith("cached(C=64")


# --- store adoption ---------------------------------------------------------

def test_use_store_converts_params_bit_exactly():
    spec = ctr_spec("deepfm", "criteo", **SPEC_KW)
    model = CTR_MODELS["deepfm"](spec)
    params = model.init(jax.random.PRNGKey(0))
    ids = np.asarray(synthetic_batch(SCHEMA, 0, 8)["ids"])
    want = compile_plan(model, params, "dual", 8).predict(ids)
    store = CachedStore(spec.embedding_spec(), capacity=64)
    params2 = model.use_store(store, params)
    assert set(params2["emb"]) == {"backing", "cache", "slot_of_row"}
    assert isinstance(model.embedding.store, CachedStore)
    got = compile_plan(model, params2, "dual", 8).predict(ids)
    np.testing.assert_array_equal(got, want)
    # round-trip back to dense
    params3 = model.use_store(DenseStore(spec.embedding_spec()), params2)
    np.testing.assert_array_equal(
        np.asarray(params3["emb"]["mega_table"]),
        np.asarray(params["emb"]["mega_table"]))


def test_observe_clips_malformed_ids():
    """One out-of-range or negative id must not wedge the serving loop —
    observe clips exactly like the gather (jnp.take clamps) does."""
    _, _, cached, pc, store = make_pair()
    bad = np.array([[10**9, -5, 2, 1]], dtype=np.int64)
    cached.observe(bad)                          # must not raise
    assert store.stats.lookups == SPEC.k


def test_dense_engine_refresh_every_is_a_noop():
    """A dense engine with refresh_every set must never drop its plans
    (DenseStore has no cache tier to rebuild)."""
    from repro.serving import FixedBatch, InferenceEngine
    spec = ctr_spec("dcn", "criteo", **SPEC_KW)
    model = CTR_MODELS["dcn"](spec)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, policy=FixedBatch(8),
                          refresh_every=1)
    rng = np.random.default_rng(0)
    rows = [np.array([rng.integers(0, s) for s in spec.field_sizes],
                     dtype=np.int32) for _ in range(16)]
    eng.submit_many(rows)
    eng.serve_pending()
    assert len(eng.cached_plans) == 1            # plans survive
    assert eng.stats.cache_misses == 1           # compiled exactly once
    assert eng.stats.emb_cache_refreshes == 0
    assert eng.stats.emb_cached_traffic_fraction == 0.0


def _mesh_or_skip(shape):
    n = int(np.prod(shape))
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices (run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    return make_mesh(shape, ("data", "model"))


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("kind", ["dense", "cached", "cached_int8"])
def test_lookup_sharded_pallas_matches_single_device(kind, mesh_shape):
    """The vocab-parallel shard_map lookup (what a mesh plan runs, so the
    Pallas gather never sees a row-sharded operand) with the Pallas
    kernels in interpret mode equals the single-device lookup bitwise:
    each row comes from exactly one shard, the psum adds only zeros."""
    mesh = _mesh_or_skip(mesh_shape)
    dense = DenseStore(SPEC)
    params = dense.init(jax.random.PRNGKey(3))
    store = {"dense": dense,
             "cached": CachedStore(SPEC, capacity=48),
             "cached_int8": CachedStore(SPEC, capacity=48,
                                        row_dtype="int8")}[kind]
    params = store.adopt(params)
    offsets = jnp.asarray(SPEC.offsets)
    ids = jnp.asarray(np.stack(
        [np.random.default_rng(i).integers(0, n, size=8)
         for i, n in enumerate(SPEC.field_sizes)], axis=1), jnp.int32)
    want = store.lookup(params, ids, offsets, strategy="pallas",
                        interpret=True)
    placed = store.place(params, mesh)
    got = jax.jit(lambda p, i: store.lookup_sharded(
        p, i, offsets, mesh, strategy="pallas", interpret=True))(placed, ids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
