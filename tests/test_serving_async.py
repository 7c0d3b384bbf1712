"""Async serving runtime tests (ISSUE-3 acceptance surface).

Covers: futures-based intake (resolution values, submit order, latency
stamps, the future's own contract under racing threads), the background
worker draining a ``TimeoutBatch`` SLO without caller polling,
refresh-without-recompile (plan-cache keys identical, zero new compiles,
bit-exact vs ``DenseStore`` across ≥2 refreshes under zipf traffic),
thread-safe stats with ``queue_depth``, the multi-model
``ServingRuntime`` router, and the absence of the removed deprecated
surfaces (``core.fused_embedding``, ``CTRServingEngine``).
"""

import importlib
import sys
import threading
import time

import numpy as np
import pytest
import jax

from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO, zipf_ids
from repro.core.plan import InferencePlan
from repro.embedding import CachedStore, HostBackedStore
from repro.models.ctr import CTR_MODELS
from repro.serving import (BucketedBatch, DeviceScheduler, FixedBatch,
                           InferenceEngine, RequestFuture, ServingRuntime,
                           TimeoutBatch)

SCHEMA = CRITEO.scaled(2_000)
SPEC_KW = dict(embed_dim=8, hidden=64, max_field=2_000)


def make(model_name="widedeep"):
    spec = ctr_spec(model_name, "criteo", **SPEC_KW)
    model = CTR_MODELS[model_name](spec)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def rows_of(n, seed=0):
    rng = np.random.default_rng(seed)
    return [np.array([rng.integers(0, s) for s in SCHEMA.field_sizes],
                     dtype=np.int32) for _ in range(n)]


def zipf_rows(n, seed=0, exponent=1.1):
    return list(np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                                    SCHEMA.field_sizes, exponent=exponent)))


def direct_scores(model, params, rows):
    import jax.numpy as jnp
    return np.asarray(model.predict_proba(params,
                                          jnp.asarray(np.stack(rows))))


# --- futures ------------------------------------------------------------------

def test_submit_returns_future_resolved_by_sync_drain():
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(8))
    rows = rows_of(8)
    futs = eng.submit_many(rows)
    assert all(isinstance(f, RequestFuture) and not f.done() for f in futs)
    drained = eng.serve_pending()
    assert all(f.done() for f in futs)
    got = np.array([f.result() for f in futs])
    np.testing.assert_array_equal(got, drained)
    np.testing.assert_allclose(got, direct_scores(model, params, rows),
                               rtol=1e-5, atol=1e-5)
    assert all(f.latency_ms is not None and f.latency_ms >= 0 for f in futs)


def test_future_result_times_out_when_unserved():
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(8))
    fut = eng.submit(rows_of(1)[0])
    with pytest.raises(TimeoutError):
        fut.result(timeout=0.01)


def test_futures_resolve_in_submit_order_under_worker():
    """ISSUE-3 satellite: the worker resolves futures FIFO — within each
    batch and across batches — observed via done-callbacks."""
    model, params = make()
    eng = InferenceEngine(model, params, policy=BucketedBatch((8, 16)))
    eng.warmup()
    rows = rows_of(43)
    resolved = []
    lock = threading.Lock()
    eng.start()
    try:
        futs = eng.submit_many(rows)
        for i, f in enumerate(futs):
            f.add_done_callback(
                lambda fut, _i=i: (lock.acquire(), resolved.append(_i),
                                   lock.release()))
        got = np.array([f.result(timeout=60.0) for f in futs])
    finally:
        eng.stop()
    # every request resolved exactly once, in submit order
    assert sorted(resolved) == list(range(43))
    within_batch_sorted = all(resolved[i] < resolved[i + 1]
                              for i in range(len(resolved) - 1))
    assert within_batch_sorted, resolved
    np.testing.assert_allclose(got, direct_scores(model, params, rows),
                               rtol=1e-5, atol=1e-5)


def _run_in_thread(fn):
    """Start ``fn`` in a thread; returns the thread and a list that gets
    ``("ok", value)`` or ``("raised", exc)``."""
    out = []

    def body():
        try:
            out.append(("ok", fn()))
        except BaseException as exc:          # handed to the test
            out.append(("raised", exc))
    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t, out


def _callback_before_resolution():
    fut, seen = RequestFuture(), []
    fut.add_done_callback(lambda f: seen.append((f, threading.get_ident())))
    assert seen == [] and not fut.done()
    t, out = _run_in_thread(lambda: (fut._resolve(0.25, 1.5),
                                     threading.get_ident())[1])
    t.join(timeout=30.0)
    assert not t.is_alive()
    assert seen == [(fut, out[0][1])]                # on the resolving thread
    assert fut.done() and fut.result() == 0.25 and fut.latency_ms == 1.5


def _callback_after_resolution():
    fut, seen = RequestFuture(), []
    fut._resolve(0.5, 1.0)
    fut.add_done_callback(seen.append)               # runs at once
    assert seen == [fut]


def _callback_that_raises():
    fut, seen = RequestFuture(), []
    fut.add_done_callback(lambda f: 1 / 0)
    fut.add_done_callback(seen.append)
    fut._resolve(0.75, 1.0)                          # swallowed, not raised
    fut.add_done_callback(lambda f: 1 / 0)           # nor when done
    assert seen == [fut] and fut.result() == 0.75


def _timeout_zero_on_pending():
    fut = RequestFuture()
    with pytest.raises(TimeoutError, match=r"not served within 0s"):
        fut.result(timeout=0)
    assert not fut.done()
    fut._resolve(0.5, 1.0)                          # still resolvable
    assert fut.result(timeout=0) == 0.5


def _blocked_result_wakes_on_resolve():
    fut = RequestFuture()
    t, out = _run_in_thread(lambda: fut.result(timeout=30.0))
    time.sleep(0.02)                                 # let it block
    fut._resolve(0.125, 1.0)
    t.join(timeout=30.0)
    assert not t.is_alive() and out == [("ok", 0.125)]


def _blocked_result_wakes_on_fail():
    fut, err = RequestFuture(), ValueError("batch failed")
    t, out = _run_in_thread(lambda: fut.result(timeout=30.0))
    time.sleep(0.02)
    fut._fail(err)
    t.join(timeout=30.0)
    assert not t.is_alive() and out == [("raised", err)]


def _fail_reraises():
    fut, err = RequestFuture(), KeyError("boom")
    fut._fail(err)
    assert fut.done()
    for timeout in (None, 0):
        with pytest.raises(KeyError) as got:
            fut.result(timeout=timeout)
        assert got.value is err


@pytest.mark.parametrize("case", [
    _callback_before_resolution, _callback_after_resolution,
    _callback_that_raises, _timeout_zero_on_pending,
    _blocked_result_wakes_on_resolve, _blocked_result_wakes_on_fail,
    _fail_reraises], ids=lambda f: f.__name__.strip("_"))
def test_request_future_contract(case):
    """``RequestFuture`` keeps one contract however it synchronises:
    callbacks run once, on the resolving thread or at once if already
    done, their exceptions swallowed; ``result`` returns, raises the
    batch's error, or times out."""
    case()


def test_request_future_races_resolver_callbacks_and_waiters():
    """2,000 futures resolved by one thread while two others race
    ``add_done_callback`` and ``result(timeout=5)`` on each: every
    callback runs exactly once and no waiter hangs."""
    n = 2_000
    futs = [RequestFuture() for _ in range(n)]
    calls = [[] for _ in range(n)]
    start = threading.Barrier(3)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def resolver():
        start.wait(timeout=30.0)
        for i, f in enumerate(futs):
            time.sleep(0)            # let the racers reach a pending future
            f._resolve(float(i), 0.0)

    def racer(tag):
        start.wait(timeout=30.0)
        got = []
        for i, f in enumerate(futs):
            f.add_done_callback(lambda fut, i=i: calls[i].append(tag))
            got.append(f.result(timeout=5.0))
        return got

    try:
        threads = [_run_in_thread(resolver)] + [
            _run_in_thread(lambda tag=tag: racer(tag)) for tag in "ab"]
        for t, _ in threads:
            t.join(timeout=60.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t, _ in threads)
    want = [float(i) for i in range(n)]
    assert [out for _, out in threads] == [
        [("ok", None)], [("ok", want)], [("ok", want)]]
    assert all(sorted(c) == ["a", "b"] for c in calls)


# --- background worker --------------------------------------------------------

def test_worker_fires_timeout_slo_without_polling():
    """ISSUE-3 satellite: a partial batch inside a TimeoutBatch window is
    drained by the worker once the oldest request ages past the SLO —
    no serve_pending/flush call anywhere."""
    model, params = make()
    eng = InferenceEngine(
        model, params,
        policy=TimeoutBatch(FixedBatch(8), max_wait_ms=25.0),
        worker_tick_ms=1.0)
    eng.warmup()
    eng.start()
    try:
        rows = rows_of(3)
        futs = eng.submit_many(rows)           # partial: below the bucket
        got = np.array([f.result(timeout=60.0) for f in futs])
    finally:
        eng.stop()
    st = eng.stats
    assert st.n_batches == 1 and st.batches_per_bucket == {8: 1}
    assert st.n_requests == 3 and eng.pending() == 0
    np.testing.assert_allclose(got, direct_scores(model, params, rows),
                               rtol=1e-5, atol=1e-5)
    # queued → served latency must cover the SLO wait the policy imposed
    assert st.p50_ms >= 25.0


def test_worker_drains_full_buckets_immediately():
    model, params = make()
    eng = InferenceEngine(
        model, params,
        policy=TimeoutBatch(FixedBatch(8), max_wait_ms=60_000.0))
    eng.warmup()
    eng.start()
    try:
        futs = eng.submit_many(rows_of(16))    # two full buckets: no SLO wait
        for f in futs:
            f.result(timeout=60.0)
    finally:
        eng.stop(flush=False)
    assert eng.stats.n_batches == 2
    assert eng.stats.queue_depth == 0


def test_start_stop_lifecycle_idempotent_and_flushing():
    model, params = make()
    eng = InferenceEngine(
        model, params,
        policy=TimeoutBatch(FixedBatch(8), max_wait_ms=60_000.0))
    eng.start()
    eng.start()                                 # idempotent
    assert eng.running
    futs = eng.submit_many(rows_of(3))          # held by the SLO window
    eng.stop()                                  # join + flush leftovers
    assert not eng.running
    assert all(f.done() for f in futs)
    assert eng.pending() == 0
    eng.stop()                                  # idempotent after stop


def test_sync_surface_still_works_alongside_worker_api():
    """serve_pending/flush/predict remain the sync surface when no worker
    is started — exact pre-async behaviour."""
    model, params = make()
    eng = InferenceEngine(model, params, policy=BucketedBatch((8, 16)))
    eng.submit_many(rows_of(20))
    scores = np.concatenate([eng.serve_pending(), eng.flush()])
    assert scores.shape == (20,)
    assert eng.stats.queue_depth == 0


# --- stats thread-safety (ISSUE-3 satellite) ---------------------------------

def test_stats_expose_queue_depth():
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(8))
    eng.submit_many(rows_of(5))
    assert eng.stats.queue_depth == 5
    eng.flush()
    assert eng.stats.queue_depth == 0


def test_concurrent_submitters_with_worker_lose_no_request():
    """Counters stay consistent with many submitter threads racing the
    worker: every request served exactly once, totals add up."""
    model, params = make()
    eng = InferenceEngine(model, params, policy=BucketedBatch((8, 16)),
                          worker_tick_ms=0.2)
    eng.warmup()
    eng.start()
    futs_per_thread = {}

    def submitter(tid):
        futs_per_thread[tid] = eng.submit_many(rows_of(24, seed=tid))

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(4)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        all_futs = [f for fs in futs_per_thread.values() for f in fs]
        for f in all_futs:
            f.result(timeout=60.0)
    finally:
        eng.stop()
    st = eng.stats
    assert st.n_requests == 4 * 24
    assert st.queue_depth == 0 and eng.pending() == 0
    assert sum(st.batches_per_bucket.values()) == st.n_batches
    assert eng.worker_error is None
    # per-thread scores match the direct forward (routing never mixed rows)
    for tid, futs in futs_per_thread.items():
        got = np.array([f.result() for f in futs])
        np.testing.assert_allclose(
            got, direct_scores(model, params, rows_of(24, seed=tid)),
            rtol=1e-5, atol=1e-5)


def test_malformed_row_fails_batch_futures_instead_of_hanging():
    """A ragged row in a batch must fail that batch's futures (stack
    raises before compute) — never strand them unresolved."""
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(4))
    futs = eng.submit_many(rows_of(3))
    bad = eng.submit(np.zeros(len(SCHEMA.field_sizes) + 1, dtype=np.int32))
    with pytest.raises(ValueError):
        eng.flush()
    for f in futs + [bad]:
        assert f.done()
        with pytest.raises(ValueError):
            f.result(timeout=0)


def test_raising_done_callback_does_not_strand_other_futures():
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(8))
    futs = eng.submit_many(rows_of(8))
    futs[0].add_done_callback(lambda f: 1 / 0)     # hostile callback
    seen = []
    futs[1].add_done_callback(lambda f: seen.append(f.result()))
    eng.serve_pending()
    assert all(f.done() for f in futs)             # nobody left hanging
    assert seen == [futs[1].result()]


# --- the drain's one-deep pipeline -------------------------------------------

def _store(kind, model):
    if kind == "cached":
        return CachedStore(model.spec.embedding_spec(), capacity=128)
    if kind == "host":
        return HostBackedStore(model.spec.embedding_spec(), capacity=64,
                               staging_capacity=1024)
    return None


@pytest.mark.parametrize("drain,store", [
    ("serve_pending", None), ("flush", None), ("worker", None),
    ("serve_pending", "cached"), ("serve_pending", "host")])
def test_queued_buckets_overlap_in_submit_order_bit_exact(drain, store):
    """Three full buckets queued: the drain launches each one while it
    holds the one before (two overlapped), futures resolve in submit
    order, scores equal the plan's own. A staging store keeps the serial
    path and never overlaps."""
    model, params = make()
    eng = InferenceEngine(model, params, store=_store(store, model),
                          policy=TimeoutBatch(FixedBatch(8),
                                              max_wait_ms=60_000.0))
    eng.warmup()
    rows = rows_of(24)
    order = []
    futs = eng.submit_many(rows)
    for i, f in enumerate(futs):
        f.add_done_callback(lambda _, i=i: order.append(i))
    if drain == "worker":
        eng.start()
        try:
            got = np.array([f.result(timeout=60.0) for f in futs])
        finally:
            eng.stop()
    else:
        got = (eng.serve_pending(allow_partial=False)
               if drain == "serve_pending" else eng.flush())
    assert order == list(range(24))
    assert eng._held is None
    st = eng.stats
    assert st.n_batches == 3
    assert st.n_overlapped_batches == (0 if store == "host" else 2)
    np.testing.assert_array_equal([f.result() for f in futs], got)
    if store == "host":                        # staged misses: the engine's
        want = eng.predict(np.stack(rows))     # own one-shot path
    else:
        plan = eng.plan_for(8)
        want = np.concatenate([plan.predict(np.stack(rows[i:i + 8]))
                               for i in range(0, 24, 8)])
    np.testing.assert_array_equal(got, want)


def _held_engine():
    """An engine holding one launched full bucket, 3 more requests
    queued behind it (a partial under a long hold)."""
    model, params = make()
    eng = InferenceEngine(model, params,
                          policy=TimeoutBatch(FixedBatch(8),
                                              max_wait_ms=60_000.0))
    futs = eng.submit_many(rows_of(11))
    assert eng._serve_step(allow_partial=False, force=False) == (True, None)
    assert eng._held is not None and not futs[0].done()
    return eng, futs


@pytest.mark.parametrize("drain", ["serve_pending", "flush", "stop",
                                   "stop_no_flush", "scheduler_stop"])
def test_drains_leave_nothing_held(drain):
    eng, futs = _held_engine()
    if drain == "serve_pending":
        eng.serve_pending(allow_partial=False)
    elif drain == "flush":
        eng.flush()
    elif drain == "stop":
        eng.stop()
    elif drain == "stop_no_flush":
        eng.stop(flush=False)
    else:
        DeviceScheduler(pool_size=1).attach("m", eng)
        eng._scheduler.stop()
    assert eng._held is None
    assert all(f.done() for f in futs[:8])    # the held batch read back
    flushed = drain in ("flush", "stop")
    assert all(f.done() == flushed for f in futs[8:])
    assert eng.pending() == (0 if flushed else 3)
    assert eng.stats.n_batches == (2 if flushed else 1)


@pytest.mark.parametrize("failing", ["launch", "readback"])
def test_a_failing_batch_fails_only_its_own_futures(failing, monkeypatch):
    """Batch N is held when batch N+1 launches. A launch that fails
    fails N+1's futures alone, after N resolved; a readback that fails
    fails N's alone, and N+1 is still held and resolves."""
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(4))
    plan = eng.plan_for(4)
    good = rows_of(8, seed=1)
    n = eng.submit_many(good[:4])
    eng._serve_step(allow_partial=False, force=False)      # N held
    if failing == "launch":
        n1 = eng.submit_many(good[4:7]) + [eng.submit(
            np.zeros(len(SCHEMA.field_sizes) + 1, dtype=np.int32))]
        err = ValueError
    else:
        n1 = eng.submit_many(good[4:])
        err = RuntimeError
        real = InferencePlan.wait
        calls = []

        def flaky(scores):
            calls.append(scores)
            if len(calls) == 1:
                raise RuntimeError("readback lost")
            real(scores)
        monkeypatch.setattr(InferencePlan, "wait", staticmethod(flaky))
    order = []
    for tag, futs in (("n", n), ("n1", n1)):
        for f in futs:
            f.add_done_callback(lambda _, tag=tag: order.append(tag))
    with pytest.raises(err):
        eng._serve_step(allow_partial=False, force=False)
    failed, served = (n1, n) if failing == "launch" else (n, n1)
    if failing == "readback":
        assert eng._held is not None          # N+1 launched and still held
        eng.flush()
    assert eng._held is None
    assert order == ["n"] * 4 + ["n1"] * 4    # N first, either way
    for f in failed:
        with pytest.raises(err):
            f.result(timeout=0)
    rows = good[:4] if failing == "launch" else good[4:]
    np.testing.assert_array_equal([f.result(timeout=0) for f in served],
                                  plan.predict(np.stack(rows)))


# --- refresh-without-recompile (ISSUE-3 satellite + acceptance) ---------------

def test_refresh_without_recompile_bit_exact_zipf():
    """≥2 refreshes under zipf traffic: plan-cache keys identical, zero new
    compiles, scores bit-exact vs DenseStore throughout."""
    model_d, params_d = make()
    dense = InferenceEngine(model_d, params_d, policy=BucketedBatch((8, 16)))

    model_c, params_c = make()
    store = CachedStore(model_c.spec.embedding_spec(), capacity=128)
    eng = InferenceEngine(model_c, params_c, policy=BucketedBatch((8, 16)),
                          store=store)
    eng.warmup()
    keys0 = set(eng.cached_plans)
    compiles0 = eng.stats.cache_misses

    for round_ in range(3):
        rows = zipf_rows(24, seed=round_)
        want = dense.predict(np.stack(rows))
        eng.submit_many(rows)
        got = eng.serve_pending()
        np.testing.assert_array_equal(got, want)   # bit-exact, every round
        eng.refresh_cache()                        # swap tensors, keep plans
        assert set(eng.cached_plans) == keys0      # identical cache keys
        assert eng.stats.cache_misses == compiles0  # zero new compiles

    assert store.stats.refreshes >= 2
    assert eng.stats.emb_cache_refreshes >= 2
    # after refreshes the index map tracks the zipf head: hot traffic mass
    # should be covered by the cache
    assert eng.stats.emb_cached_traffic_fraction > 0.0


def test_plan_runtime_inputs_exposed():
    """Plans compiled against a refreshable store advertise the store
    tensors they take per call; dense plans advertise none."""
    from repro.core import compile_plan
    model_d, params_d = make()
    assert compile_plan(model_d, params_d, "dual", 8).runtime_inputs == ()

    model_c, params_c = make()
    store = CachedStore(model_c.spec.embedding_spec(), capacity=64)
    params_c = model_c.use_store(store, params_c)
    plan = compile_plan(model_c, params_c, "dual", 8)
    assert plan.runtime_inputs == ("emb:backing", "emb:cache",
                                   "emb:slot_of_row")


def test_refresh_under_running_worker_stays_exact():
    """Refresh concurrently with a draining worker: the double-buffered
    publish means every batch reads a consistent (old or new) tensor set
    — scores stay bit-exact with the dense reference."""
    model_d, params_d = make()
    dense = InferenceEngine(model_d, params_d, policy=FixedBatch(8))
    rows = zipf_rows(64, seed=7)
    want = dense.predict(np.stack(rows))

    model_c, params_c = make()
    store = CachedStore(model_c.spec.embedding_spec(), capacity=128)
    eng = InferenceEngine(model_c, params_c, policy=FixedBatch(8),
                          store=store, refresh_every=2)  # refresh mid-stream
    eng.warmup()
    eng.start()
    try:
        futs = eng.submit_many(rows)
        got = np.array([f.result(timeout=60.0) for f in futs])
    finally:
        eng.stop()
    np.testing.assert_array_equal(got, want)
    assert store.stats.refreshes >= 2
    assert eng.stats.cache_misses == 1             # the single warmed bucket


# --- multi-model runtime (acceptance) ----------------------------------------

def test_runtime_routes_two_models_async_bit_exact():
    """Acceptance: ServingRuntime serves 2 models concurrently through the
    async intake with per-model stats and bit-exact scores vs the
    synchronous path."""
    rt = ServingRuntime()
    built = {}
    for name in ("widedeep", "dcn"):
        model, params = make(name)
        built[name] = (model, params)
        rt.add_model(name, model, params,
                     policy=TimeoutBatch(BucketedBatch((8, 16)),
                                         max_wait_ms=5.0),
                     worker_tick_ms=1.0)
    assert rt.models == ("widedeep", "dcn")
    rt.warmup()
    rt.start()
    try:
        futs = {n: rt.submit_many(n, rows_of(21, seed=i))
                for i, n in enumerate(rt.models)}
        got = {n: np.array([f.result(timeout=60.0) for f in fs])
               for n, fs in futs.items()}
    finally:
        rt.stop()
    for i, name in enumerate(rt.models):
        model, params = built[name]
        # bit-exact vs the synchronous engine path on the same rows
        sync_eng = InferenceEngine(model, params,
                                   policy=BucketedBatch((8, 16)))
        sync_eng.submit_many(rows_of(21, seed=i))
        want = np.concatenate([sync_eng.serve_pending(), sync_eng.flush()])
        np.testing.assert_array_equal(got[name], want)
        # per-model stats kept separately
        assert rt.engine(name).stats.n_requests == 21
    agg = rt.stats()
    assert agg.n_models == 2 and agg.n_requests == 42
    assert agg.queue_depth == 0
    # per_model is a consistent snapshot, not the live (mutating) object
    snap = agg.per_model["widedeep"]
    live = rt.engine("widedeep").stats
    assert snap is not live
    assert snap.n_requests == live.n_requests == 21
    rt.engine("widedeep").predict(rows_of(1)[0])
    assert snap.n_requests == 21          # later traffic never mutates it
    assert agg.p99_ms >= agg.p50_ms >= 0.0


def test_runtime_rejects_unknown_and_duplicate_models():
    rt = ServingRuntime()
    model, params = make()
    rt.add_model("widedeep", model, params, policy=FixedBatch(8))
    with pytest.raises(ValueError, match="already registered"):
        rt.add_engine("widedeep",
                      InferenceEngine(model, params, policy=FixedBatch(8)))
    with pytest.raises(KeyError, match="widedeep"):
        rt.submit("nope", rows_of(1)[0])


def test_runtime_shared_admission_refreshes_all_stores():
    """refresh_every counts submitted traffic across models and swaps
    every refreshable store's cache (asynchronously — the crossing submit
    never pays the rebuild) — without dropping any plans."""
    import time as _time

    def wait_refreshes(stores, n, deadline_s=30.0):
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < deadline_s:
            if all(s.stats.refreshes >= n for s in stores.values()):
                return
            _time.sleep(0.005)
        raise AssertionError(
            f"stores never reached {n} refreshes: "
            f"{[s.stats.refreshes for s in stores.values()]}")

    rt = ServingRuntime(refresh_every=16)
    stores = {}
    for name in ("widedeep", "dcn"):
        model, params = make(name)
        stores[name] = CachedStore(model.spec.embedding_spec(), capacity=64)
        rt.add_model(name, model, params, policy=FixedBatch(8),
                     store=stores[name])
    rt.warmup()
    plans = {n: set(rt.engine(n).cached_plans) for n in rt.models}
    for i in range(2):                       # 2×16 submits → 2 shared refreshes
        for name in rt.models:
            rt.submit_many(name, rows_of(8, seed=i))
        rt.flush()
        wait_refreshes(stores, i + 1)        # refresh runs off-thread
    assert all(s.stats.refreshes == 2 for s in stores.values())
    for n in rt.models:                      # plan caches survived both swaps
        assert set(rt.engine(n).cached_plans) == plans[n]
        assert rt.engine(n).stats.cache_misses == 1


# --- removed deprecated surfaces (ISSUE-6 satellite) -------------------------

def test_deprecated_surfaces_are_gone():
    """The fused_embedding shim and the CTRServingEngine alias were removed
    — only the real surfaces (repro.embedding, InferenceEngine + policies)
    remain importable."""
    sys.modules.pop("repro.core.fused_embedding", None)
    with pytest.raises(ImportError):
        importlib.import_module("repro.core.fused_embedding")
    import repro.serving as serving
    assert not hasattr(serving, "CTRServingEngine")
    assert not hasattr(serving, "ServeStats")
