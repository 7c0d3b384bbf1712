"""HostBackedStore tests — the out-of-HBM embedding tier (ISSUE-6).

Acceptance surface: ``HostBackedStore`` scores bit-exact with
``DenseStore`` (one-hot + multi-hot, uniform + zipf, before and after
``refresh()``, cold-cache miss storm, single-device and 8-way simulated
mesh) with **zero plan recompiles** across refreshes; a staging-buffer
overflow falls back to a synchronous chunked host gather instead of wrong
scores; the mmap third tier round-trips through ``backing_path=``/
``HostBackedStore.open``; and a vocab larger than the device-table budget
serves end-to-end through ``InferenceEngine.submit`` with the backing
never uploaded wholesale.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.configs import ctr_spec
from repro.core import compile_plan
from repro.data.synthetic import CRITEO, zipf_ids
from repro.embedding import (DenseStore, FusedEmbeddingCollection,
                             FusedEmbeddingSpec, HostBackedStore,
                             PrefetchPipeline, StagingOverflowError)
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


def make_pair(capacity=48, staging_capacity=256, backing_path=None):
    """Dense and host-backed collections over the *same* table values."""
    dense = FusedEmbeddingCollection(SPEC)
    params_d = dense.init(jax.random.PRNGKey(0))
    store = HostBackedStore(SPEC, capacity=capacity,
                            staging_capacity=staging_capacity,
                            backing_path=backing_path)
    hosted = FusedEmbeddingCollection(SPEC, store=store)
    params_h = store.from_dense(params_d)
    return dense, params_d, hosted, params_h, store


def traffic(batch=128, exponent=None, seed=0):
    key = jax.random.PRNGKey(seed)
    if exponent is not None:
        return zipf_ids(key, batch, SPEC.field_sizes, exponent=exponent)
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack([rng.integers(0, s, size=batch)
                                 for s in SPEC.field_sizes], axis=1),
                       dtype=jnp.int32)


def make_engine_pair(model_name="widedeep", capacity=64,
                     staging_capacity=256, batch=8, mesh=None):
    # separate model instances: use_store rebinds the model's collection
    spec = ctr_spec(model_name, "criteo", **SPEC_KW)
    dense_model = CTR_MODELS[model_name](spec)
    dense = InferenceEngine(dense_model,
                            dense_model.init(jax.random.PRNGKey(0)),
                            policy=FixedBatch(batch), mesh=mesh)
    model = CTR_MODELS[model_name](spec)
    params = model.init(jax.random.PRNGKey(0))
    store = HostBackedStore(spec.embedding_spec(), capacity=capacity,
                            staging_capacity=staging_capacity)
    eng = InferenceEngine(model, params, policy=FixedBatch(batch),
                          store=store, mesh=mesh)
    return dense, eng, store


def zipf_stream(n, seed=0, exponent=1.1):
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                               SCHEMA.field_sizes, exponent=exponent))


# --- bit-exactness ----------------------------------------------------------

@pytest.mark.parametrize("exponent", [None, 1.3])
def test_host_store_bit_exact_onehot(exponent):
    dense, pd, hosted, ph, store = make_pair()
    ids = traffic(exponent=exponent)
    ph = store.stage(ph, np.asarray(ids))        # resolve misses first
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    got = np.asarray(hosted.apply(ph, ids, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    # kernel-body validation of the Pallas three-level gather
    got_pl = np.asarray(hosted.apply(ph, ids[:16], strategy="pallas",
                                     interpret=True))
    np.testing.assert_array_equal(got_pl, want[:16])


@pytest.mark.parametrize("exponent", [None, 1.3])
def test_host_store_bit_exact_multihot(exponent):
    dense, pd, hosted, ph, store = make_pair()
    h = 3
    rng = np.random.default_rng(1)
    if exponent is None:
        ids = np.stack([rng.integers(0, s, size=(64, h))
                        for s in SPEC.field_sizes], axis=1)
    else:
        ids = np.stack([np.asarray(zipf_ids(jax.random.PRNGKey(t), 64,
                                            SPEC.field_sizes, exponent))
                        for t in range(h)], axis=-1)
    ids = jnp.asarray(ids, dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=ids.shape), jnp.float32)
    ph = store.stage(ph, np.asarray(ids), np.asarray(mask))
    want = np.asarray(dense.apply_multihot(pd, ids, mask, strategy="jnp"))
    got = np.asarray(hosted.apply_multihot(ph, ids, mask, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    want_pl = np.asarray(dense.apply_multihot(pd, ids[:8], mask[:8],
                                              strategy="pallas",
                                              interpret=True))
    got_pl = np.asarray(hosted.apply_multihot(ph, ids[:8], mask[:8],
                                              strategy="pallas",
                                              interpret=True))
    np.testing.assert_array_equal(got_pl, want_pl)


def test_host_store_bit_exact_after_refresh():
    dense, pd, hosted, ph, store = make_pair()
    ids = traffic(exponent=1.5)
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    hosted.observe(np.asarray(ids))
    ph = store.refresh(ph)
    ph = store.stage(ph, np.asarray(ids))
    got = np.asarray(hosted.apply(ph, ids, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    assert store.stats.refreshes == 1


def test_cold_cache_miss_storm_is_bit_exact():
    """Every row uncached (capacity 1, distinct uniform ids): the staging
    path alone must carry the whole batch, bit-exactly."""
    dense, pd, hosted, ph, store = make_pair(capacity=1,
                                             staging_capacity=SPEC.rows)
    ids = traffic(batch=48, seed=3)
    ph = store.stage(ph, np.asarray(ids))
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    got = np.asarray(hosted.apply(ph, ids, strategy="jnp"))
    np.testing.assert_array_equal(got, want)
    assert store.stats.staged_rows > 0
    assert store.stats.h2d_bytes == (store.stats.staged_rows * SPEC.dim
                                     * np.dtype(SPEC.dtype).itemsize)


def test_unstaged_miss_gathers_zero_guard():
    """The three-way select's guard: an unresolved row reads zero, never
    garbage (correctness then rests on the serve path staging first)."""
    _, _, hosted, ph, store = make_pair(capacity=4)
    ids = traffic(batch=8, seed=5)
    out = np.asarray(hosted.apply(ph, ids, strategy="jnp"))  # no stage()
    rows = np.asarray(ids) + SPEC.offsets[None, :]
    uncached = np.asarray(ph["slot_of_row"])[rows] < 0
    got = out.reshape(len(ids), SPEC.k, SPEC.dim)
    assert np.all(got[uncached] == 0.0)
    assert np.any(uncached)


# --- staging overflow -------------------------------------------------------

def test_stage_overflow_raises_not_wrong():
    _, _, _, ph, store = make_pair(capacity=1, staging_capacity=SPEC.k)
    ids = traffic(batch=64, seed=7)
    with pytest.raises(StagingOverflowError):
        store.stage(ph, np.asarray(ids))
    assert store.stats.staging_overflows == 1
    chunks = store.split_for_staging(np.asarray(ids))
    assert sum(len(c) for c in chunks) == 64
    for c in chunks:
        assert store.miss_rows(c).size <= store.staging_capacity


def test_engine_overflow_falls_back_to_chunked_serving():
    """A miss storm through a tiny staging buffer serves correct scores
    via the synchronous chunked host gather — slower, never wrong."""
    k = len(SCHEMA.field_sizes)
    dense, eng, store = make_engine_pair(capacity=8, staging_capacity=k)
    ids = zipf_stream(24, exponent=1.05)
    want = dense.predict(ids)
    eng.submit_many(list(ids))
    got = eng.serve_pending()
    np.testing.assert_array_equal(got, want)
    assert store.stats.staging_overflows > 0
    assert eng.stats.emb_staging_overflows == store.stats.staging_overflows


def test_staging_capacity_must_cover_one_sample():
    with pytest.raises(ValueError, match="staging_capacity"):
        HostBackedStore(SPEC, capacity=8, staging_capacity=SPEC.k - 1)


# --- prefetch pipeline ------------------------------------------------------

def test_prefetch_worker_resolves_hinted_misses():
    _, _, _, ph, store = make_pair(capacity=4, staging_capacity=128)
    ids = np.asarray(traffic(batch=16, seed=9))
    miss = store.miss_rows(ids)
    store.prefetch_hint(ids)
    assert store.pipeline.wait_idle(timeout=10.0)
    assert store.pipeline.staged_rows() >= min(miss.size, 128)
    # serve-time stage finds everything already resolved
    n0 = store.stats.staged_rows
    store.stage(ph, ids)
    assert store.stats.staged_rows == n0          # nothing left to gather
    assert store.stats.prefetched_rows >= miss.size


def test_prefetch_worker_counts_its_errors():
    """A failing speculative prefetch is not swallowed silently: the
    worker counts it (serving runs fail on the count) and keeps going."""
    _, _, _, _, store = make_pair(capacity=4, staging_capacity=128)

    def broken(rows):
        raise RuntimeError("backing unreadable")
    store.pipeline._prefetch = broken
    store.prefetch_hint(np.asarray(traffic(batch=4, seed=1)))
    assert store.pipeline.wait_idle(timeout=10.0)
    store.pipeline.stop()
    assert store.pipeline.n_errors == 1
    assert "backing unreadable" in str(store.pipeline.last_error)


def test_refresh_promotes_hot_staged_rows_out_of_staging():
    _, _, hosted, ph, store = make_pair(capacity=4, staging_capacity=64)
    # ids whose global rows all miss the seeded cache (rows 0..3)
    hot = np.array([[7, 2, 17, 5]] * 50, dtype=np.int64)
    ph = store.stage(ph, hot)                     # hot rows enter staging
    hot_rows = hot[0] + SPEC.offsets
    assert np.all(np.asarray(
        store.pipeline.snapshot()[2][hot_rows] >= 0))
    hosted.observe(hot)
    ph = store.refresh(ph)
    # promoted into the cache tier...
    assert set(np.flatnonzero(np.asarray(ph["slot_of_row"]) >= 0)) \
        == set(hot_rows.tolist())
    # ...and evicted from staging (slots freed for cold rows)
    assert np.all(np.asarray(ph["staging_slot_of_row"])[hot_rows] < 0)


# --- mmap third tier --------------------------------------------------------

def test_mmap_backing_round_trip(tmp_path):
    path = tmp_path / "backing.npy"
    dense, pd, hosted, ph, store = make_pair(backing_path=path)
    assert isinstance(store.host_view(), np.memmap)
    ids = traffic(batch=32, seed=11)
    ph = store.stage(ph, np.asarray(ids))
    want = np.asarray(dense.apply(pd, ids, strategy="jnp"))
    np.testing.assert_array_equal(
        np.asarray(hosted.apply(ph, ids, strategy="jnp")), want)

    # reopen from disk — no table in RAM, values identical
    store2 = HostBackedStore.open(SPEC, capacity=48, backing_path=path,
                                  staging_capacity=256)
    hosted2 = FusedEmbeddingCollection(SPEC, store=store2)
    ph2 = store2.device_params()
    ph2 = store2.stage(ph2, np.asarray(ids))
    np.testing.assert_array_equal(
        np.asarray(hosted2.apply(ph2, ids, strategy="jnp")), want)
    np.testing.assert_array_equal(store2.host_view(), store.host_view())


# --- engine end-to-end ------------------------------------------------------

def test_engine_serves_bit_exact_with_zero_recompiles():
    dense, eng, store = make_engine_pair()
    ids = zipf_stream(40)
    want = dense.predict(ids)
    for wave in np.array_split(ids, 2):
        eng.submit_many(list(wave))
        eng.serve_pending()
        eng.refresh_cache()                       # swap mid-stream
    futs = eng.submit_many(list(ids))
    eng.flush()
    got = np.array([f.result(timeout=60.0) for f in futs])
    np.testing.assert_array_equal(got, want)
    assert store.stats.refreshes == 2
    assert eng.stats.cache_misses == 1            # compiled exactly once
    assert len(eng.cached_plans) == 1
    assert eng.stats.emb_staged_rows + eng.stats.emb_prefetched_rows > 0


def test_vocab_beyond_device_budget_serves_end_to_end():
    """The scale unlock: total rows exceed cache+staging, yet the engine
    serves through submit() with device-resident embedding bytes bounded
    by the cache+staging budget — the backing is never uploaded."""
    dense, eng, store = make_engine_pair(capacity=64, staging_capacity=256)
    spec = store.spec
    budget = ((store.capacity + store.staging_capacity) * spec.dim
              * np.dtype(spec.dtype).itemsize
              + 2 * spec.rows * 4)                # the two int32 maps
    assert spec.rows > store.capacity + store.staging_capacity
    ids = zipf_stream(30, seed=2)
    futs = eng.submit_many(list(ids))
    eng.flush()
    got = np.array([f.result(timeout=60.0) for f in futs])
    np.testing.assert_array_equal(got, dense.predict(ids))
    key = eng.model.main_embedding_key
    assert store.device_bytes(eng.params[key]) <= budget
    full_table = spec.rows * spec.dim * np.dtype(spec.dtype).itemsize
    assert (store.capacity + store.staging_capacity) * spec.dim * \
        np.dtype(spec.dtype).itemsize < full_table
    with pytest.raises(NotImplementedError):
        store.dense_view(eng.params[key])


# --- mesh (tier1-hostmem: XLA_FLAGS=--xla_force_host_platform_device_count=8)

@needs(8)
@pytest.mark.parametrize("shape,axes", [((2,), ("data",)),
                                        ((4, 2), ("data", "model"))])
def test_host_store_on_mesh_bit_exact_with_dense(shape, axes):
    """zipf traffic through a HostBackedStore engine on a real mesh equals
    the DenseStore engine on the same mesh bit-for-bit, pre and post
    refresh, with zero recompiles — backing host-side, all four device
    leaves replicated per partition_spec."""
    mesh = make_mesh(shape, axes)
    dense, eng, store = make_engine_pair(capacity=64, staging_capacity=256,
                                         mesh=mesh)
    ids = zipf_stream(24, exponent=1.05)
    want = dense.predict(ids)
    eng.submit_many(list(ids))
    np.testing.assert_array_equal(eng.serve_pending(), want)
    eng.refresh_cache()
    np.testing.assert_array_equal(eng.predict(ids), want)
    assert eng.stats.cache_misses == 1            # refresh never recompiled
    key = eng.model.main_embedding_key
    for leaf in store.runtime_keys:
        spec_t = tuple(eng.params[key][leaf].sharding.spec)
        assert all(ax is None for ax in spec_t), (leaf, spec_t)


@needs(8)
def test_host_partition_spec_replicates_all_device_leaves():
    spec = ctr_spec("dcnv2", "criteo", **SPEC_KW)
    store = HostBackedStore(spec.embedding_spec(), capacity=32)
    ps = store.partition_spec("model")
    assert set(ps) == set(store.runtime_keys)
    assert all(tuple(s) == () for s in ps.values())
