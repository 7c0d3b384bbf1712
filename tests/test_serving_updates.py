"""Online model updates — versioned delta-stream refresh.

Acceptance surface of the live-trainer intake path: ``push_update`` /
``pull_updates`` apply ``(row_id, new_row)`` deltas through the
double-buffered publish with **zero plan recompiles**, every publish
stamps the next monotonic ``emb_version`` (torn or backward reads are
impossible — hard-asserted inside ``_runtime_env`` on every compiled
step, exercised here under concurrent serve+push), int8 tiers
re-quantize incoming fp32 rows onto the same grid a cold store would
produce, two engines sharing one ``CachedStore`` stay version-pinned
independently (the A/B scenario), staleness gauges measure the attached
source's real backlog, and ``DenseStore`` — whose tensors are baked plan
constants — refuses the whole surface loudly.
"""

import threading

import numpy as np
import pytest
import jax

from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO, zipf_ids
from repro.embedding import CachedStore, DenseStore, HostBackedStore
from repro.embedding.store import validate_deltas
from repro.kernels.multi_table_lookup import pack_rows
from repro.models.ctr import CTR_MODELS
from repro.serving import (DeltaBuffer, FixedBatch, InferenceEngine,
                           ServingRuntime, SyntheticTrainer)

SCHEMA = CRITEO.scaled(2_000)
SPEC = ctr_spec("widedeep", "criteo", embed_dim=8, hidden=64,
                max_field=2_000)
ESPEC = SPEC.embedding_spec()


def fresh_model():
    """One model instance per engine: an engine binds its store to the
    model's collection at construction."""
    model = CTR_MODELS["widedeep"](SPEC)
    return model, model.init(jax.random.PRNGKey(0))


def traffic(n=64, seed=1):
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                               SCHEMA.field_sizes, exponent=1.1))


def make_engine(store, batch=16):
    model, params = fresh_model()
    return InferenceEngine(model, params, policy=FixedBatch(batch),
                           store=store)


def deltas(n_rows=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.choice(ESPEC.zero_row, size=n_rows, replace=False)
    rows = (rng.standard_normal((n_rows, ESPEC.dim)) * 0.1).astype(
        np.float32)
    return ids, rows


# --- validate_deltas: the shared intake contract -----------------------------

def test_validate_deltas_rejects_zero_and_padding_rows():
    ids = np.array([0, ESPEC.zero_row])      # second id IS the zero row
    rows = np.zeros((2, ESPEC.dim), np.float32)
    with pytest.raises(ValueError, match="zero row"):
        validate_deltas(ESPEC, ids, rows)
    with pytest.raises(ValueError, match="out of range"):
        validate_deltas(ESPEC, np.array([-1]), rows[:1])


def test_validate_deltas_duplicates_keep_last_occurrence():
    ids = np.array([5, 9, 5])
    rows = np.stack([np.full(ESPEC.dim, v, np.float32)
                     for v in (1.0, 2.0, 3.0)])
    out_ids, out_rows = validate_deltas(ESPEC, ids, rows)
    got = dict(zip(out_ids.tolist(), out_rows[:, 0].tolist()))
    assert got == {5: 3.0, 9: 2.0}            # stream order wins


def test_validate_deltas_shape_mismatch_and_empty():
    with pytest.raises(ValueError, match="shape"):
        validate_deltas(ESPEC, np.array([1, 2]),
                        np.zeros((2, ESPEC.dim + 1), np.float32))
    out_ids, out_rows = validate_deltas(ESPEC, np.array([], np.int64),
                                        np.zeros((0, ESPEC.dim)))
    assert out_ids.size == 0 and out_rows.shape == (0, ESPEC.dim)


# --- engine push path --------------------------------------------------------

def test_dense_store_rejects_online_deltas():
    eng = make_engine(None)                   # default DenseStore semantics
    ids, rows = deltas(4)
    with pytest.raises(ValueError, match="refreshable"):
        eng.push_update(ids, rows)
    with pytest.raises(NotImplementedError, match="constants"):
        DenseStore(ESPEC).apply_deltas({}, ids, rows)


@pytest.mark.parametrize("store_cls", [CachedStore, HostBackedStore])
def test_push_update_changes_scores_with_zero_recompiles(store_cls):
    eng = make_engine(store_cls(ESPEC, capacity=64))
    ids = traffic(32)
    before = eng.predict(ids)
    compiles = eng.stats.cache_misses
    plans = set(eng.cached_plans)

    d_ids, d_rows = deltas(48, seed=3)
    applied = eng.push_update(d_ids, d_rows)
    assert applied == 48
    assert eng.stats.emb_version == 1
    assert eng.stats.emb_delta_pushes == 1
    assert eng.stats.emb_delta_rows == 48

    after = eng.predict(ids)
    assert eng.stats.cache_misses == compiles
    assert set(eng.cached_plans) == plans
    assert before.shape == after.shape
    # value parity is pinned bit-exactly against a rebuilt reference in
    # test_pushed_scores_bitexact_with_rebuilt_dense_reference


def test_empty_push_applies_nothing_and_keeps_version():
    eng = make_engine(CachedStore(ESPEC, capacity=64))
    assert eng.push_update(np.array([], np.int64),
                           np.zeros((0, ESPEC.dim), np.float32)) == 0
    assert eng.stats.emb_version == 0 and eng.stats.emb_delta_pushes == 0


@pytest.mark.parametrize("store_cls", [CachedStore, HostBackedStore])
def test_pushed_scores_bitexact_with_rebuilt_dense_reference(store_cls):
    """fp32 contract: serving after N pushes == a cold engine built from
    a table with the same deltas applied (numpy fancy assignment keeps
    the last duplicate, matching ``validate_deltas``)."""
    eng = make_engine(store_cls(ESPEC, capacity=64))
    ids = traffic(32)
    eng.predict(ids)                          # pin the plan first

    ref_model, ref_params = fresh_model()
    table = np.array(ref_model.embedding.dense_view(
        ref_params[ref_model.main_embedding_key]))
    for seed in range(3):
        d_ids, d_rows = deltas(32, seed=seed)
        eng.push_update(d_ids, d_rows)
        table[d_ids] = d_rows
    key = ref_model.main_embedding_key
    ref_params = {**ref_params,
                  key: {**ref_params[key], "mega_table": pack_rows(table)}}
    ref = InferenceEngine(ref_model, ref_params, policy=FixedBatch(16))
    np.testing.assert_array_equal(eng.predict(ids), ref.predict(ids))
    assert eng.stats.emb_version == 3


@pytest.mark.parametrize("store_cls", [CachedStore, HostBackedStore])
def test_int8_requant_parity_with_cold_store(store_cls):
    """Re-quantization contract: pushing fp32 rows through an int8 tier
    lands on the identical int8 grid as loading the delta-applied table
    into a cold int8 store — bit-exact scores, not just close."""
    eng = make_engine(store_cls(ESPEC, capacity=64, row_dtype="int8"))
    ids = traffic(32)
    eng.predict(ids)
    quant_before = eng.store.stats.quant_rows

    ref_model, ref_params = fresh_model()
    table = np.array(ref_model.embedding.dense_view(
        ref_params[ref_model.main_embedding_key]))
    d_ids, d_rows = deltas(48, seed=7)
    eng.push_update(d_ids, d_rows)
    table[d_ids] = d_rows
    assert eng.store.stats.quant_rows == quant_before + 48

    key = ref_model.main_embedding_key
    ref_params = {**ref_params,
                  key: {**ref_params[key], "mega_table": pack_rows(table)}}
    ref = InferenceEngine(ref_model, ref_params, policy=FixedBatch(16),
                          store=store_cls(ESPEC, capacity=64,
                                          row_dtype="int8"))
    np.testing.assert_array_equal(eng.predict(ids), ref.predict(ids))


def test_shared_cached_store_pins_ab_versions_independently():
    """Two engines over ONE CachedStore object: a push through ``prod``
    must not leak into ``shadow`` — its published subtree pins the
    pre-push tensors (device tensors are immutable) — and replaying the
    identical stream into ``shadow`` reconverges bit-exactly."""
    shared = CachedStore(ESPEC, capacity=64)
    prod = make_engine(shared)
    shadow = make_engine(shared)
    ids = traffic(32)
    np.testing.assert_array_equal(prod.predict(ids), shadow.predict(ids))
    baseline = shadow.predict(ids)

    stream = SyntheticTrainer(ESPEC, rows_per_batch=32, n_batches=2, seed=5)
    while (batch := stream.next_batch()) is not None:
        prod.push_update(*batch)
    assert prod.stats.emb_version == 2 and shadow.stats.emb_version == 0
    np.testing.assert_array_equal(shadow.predict(ids), baseline)

    replay = stream.replay()
    while (batch := replay.next_batch()) is not None:
        shadow.push_update(*batch)
    np.testing.assert_array_equal(shadow.predict(ids), prod.predict(ids))
    assert shadow.stats.emb_version == 2


def test_version_monotonic_under_concurrent_serve_and_push():
    """The torn-update test: a serving thread hammers ``predict`` while
    the main thread streams pushes. ``_runtime_env`` hard-asserts the
    version floor on every compiled step, so any backward or torn read
    raises out of the serving thread."""
    eng = make_engine(CachedStore(ESPEC, capacity=64))
    ids = traffic(16)
    eng.predict(ids)                          # compile outside the race
    errors = []
    stop = threading.Event()

    def serve():
        try:
            while not stop.is_set():
                eng.predict(ids)
        except BaseException as e:            # noqa: BLE001 — the assert IS the test
            errors.append(e)

    t = threading.Thread(target=serve)
    t.start()
    try:
        for seed in range(30):
            eng.push_update(*deltas(16, seed=seed))
    finally:
        stop.set()
        t.join()
    assert not errors, errors
    assert eng.stats.emb_version == 30
    assert eng._version_floor <= 30           # floor only ever chases pushes


# --- delta sources and staleness ---------------------------------------------

def test_delta_buffer_is_fifo_and_validates_lengths():
    buf = DeltaBuffer()
    with pytest.raises(ValueError, match="row ids"):
        buf.feed([1, 2], np.zeros((3, ESPEC.dim), np.float32))
    buf.feed([1], np.full(ESPEC.dim, 1.0, np.float32))
    buf.feed([2], np.full(ESPEC.dim, 2.0, np.float32))
    assert buf.pending_rows() == 2
    first = buf.next_batch()
    assert first[0].tolist() == [1] and float(first[1][0, 0]) == 1.0
    assert buf.next_batch()[0].tolist() == [2]
    assert buf.next_batch() is None and buf.pending_rows() == 0


def test_staleness_gauges_with_injected_clock():
    now = [100.0]
    buf = DeltaBuffer(clock=lambda: now[0])
    eng = make_engine(CachedStore(ESPEC, capacity=64))
    eng.attach_delta_source(buf)
    assert eng.stats.rows_behind == 0 and eng.stats.seconds_behind == 0.0

    d_ids, d_rows = deltas(8, seed=2)
    buf.feed(d_ids, d_rows)
    now[0] += 4.0
    eng.poll_staleness()
    assert eng.stats.rows_behind == 8
    assert eng.stats.seconds_behind == pytest.approx(4.0)

    assert eng.pull_updates() == 8
    assert eng.stats.rows_behind == 0 and eng.stats.seconds_behind == 0.0
    assert eng.stats.emb_version == 1


def test_synthetic_trainer_is_finite_seeded_and_replayable():
    tr = SyntheticTrainer(ESPEC, rows_per_batch=8, n_batches=3, seed=11)
    batches = []
    while (b := tr.next_batch()) is not None:
        batches.append(b)
    assert len(batches) == 3 and tr.pending_rows() == 0
    again = tr.replay()
    for ids, rows in batches:
        r_ids, r_rows = again.next_batch()
        np.testing.assert_array_equal(ids, r_ids)
        np.testing.assert_array_equal(rows, r_rows)
    assert all(ids.max() < ESPEC.zero_row for ids, _ in batches)


# --- host backing persistence ------------------------------------------------

def test_host_open_readonly_rejects_deltas_rplus_persists(tmp_path):
    path = tmp_path / "backing.bin"
    seeded = HostBackedStore(ESPEC, capacity=64, backing_path=path)
    seeded.init(jax.random.PRNGKey(0))

    ro = HostBackedStore.open(ESPEC, capacity=64, backing_path=path)
    params = ro.device_params()
    d_ids, d_rows = deltas(8, seed=4)
    with pytest.raises(ValueError, match="mode='r\\+'"):
        ro.apply_deltas(params, d_ids, d_rows)

    rw = HostBackedStore.open(ESPEC, capacity=64, backing_path=path,
                              mode="r+")
    _, n = rw.apply_deltas(rw.device_params(), d_ids, d_rows)
    assert n == 8
    # deltas landed on disk: a third, read-only open sees the new values
    check = HostBackedStore.open(ESPEC, capacity=64, backing_path=path)
    np.testing.assert_array_equal(check.host_view()[d_ids], d_rows)


# --- runtime surface ---------------------------------------------------------

def test_runtime_routes_pushes_and_aggregates_versions():
    rt = ServingRuntime()
    m_a, p_a = fresh_model()
    m_b, p_b = fresh_model()
    rt.add_model("a", m_a, p_a, policy=FixedBatch(16),
                 store=CachedStore(ESPEC, capacity=64))
    rt.add_model("b", m_b, p_b, policy=FixedBatch(16),
                 store=CachedStore(ESPEC, capacity=64))
    rt.warmup()
    for seed in range(3):
        rt.push_update("a", *deltas(16, seed=seed))
    rt.push_update("b", *deltas(16, seed=9))
    st = rt.stats()
    assert rt.engine("a").stats.emb_version == 3
    assert rt.engine("b").stats.emb_version == 1
    assert st.emb_version == 3                # MAX across engines, not sum
    assert st.emb_delta_pushes == 4           # counters DO sum
    assert st.emb_delta_rows == 64


def test_runtime_delta_every_drains_stream_under_live_traffic():
    """The ``delta_every`` cadence: background pulls ride admission
    counting; by stream end the trainer is fully drained, versions
    accounted, with zero recompiles — the benchmark's contract, in
    miniature."""
    model, params = fresh_model()
    rt = ServingRuntime(delta_every=8)
    rt.add_model("m", model, params, policy=FixedBatch(1),
                 store=CachedStore(ESPEC, capacity=64), worker_tick_ms=1.0)
    trainer = SyntheticTrainer(ESPEC, rows_per_batch=16, n_batches=2,
                               seed=0)
    rt.attach_delta_stream("m", trainer)
    rt.warmup()
    eng = rt.engine("m")
    compiles = eng.stats.cache_misses

    rt.start()
    try:
        futs = [rt.submit("m", row) for row in traffic(32)]
        for f in futs:
            f.result(timeout=60.0)
    finally:
        rt.stop()
    rt.pull_updates()                         # leftovers, deterministically
    st = rt.stats()
    assert st.emb_version == 2 and st.emb_delta_rows == 32
    assert st.rows_behind == 0 and st.seconds_behind == 0.0
    assert eng.stats.cache_misses == compiles
