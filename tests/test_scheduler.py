"""Cross-engine continuous batching (ISSUE-9 acceptance surface).

Covers: the shared-pool thread budget (N=8 models, pool=2 → at most
pool_size + 1 new threads, hard-asserted) with scores bit-exact vs
per-engine-worker mode, SLO-slack scheduling (a starved low-traffic
model behind a high-traffic one still meets its ``TimeoutBatch``
deadline), per-engine backpressure under the shared pool, the
``next_ready`` readiness view semantics, cross-intake-stream request
coalescing, per-model device-time accounting, the worker-error
surfacing contract (``n_worker_errors`` + re-raise from ``stop()``), and
the edge-triggered pool wake (a submit wakes the pool only on a first
request or a full bucket of an unclaimed engine).
"""

import collections
import sys
import threading
import time

import numpy as np
import pytest
import jax

from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO
from repro.models.ctr import CTR_MODELS
from repro.serving import (BucketedBatch, DeviceScheduler, FixedBatch,
                           InferenceEngine, QueueFullError, ServingRuntime,
                           TimeoutBatch)
from repro.serving import scheduler as scheduler_mod

SCHEMA = CRITEO.scaled(2_000)
SPEC_KW = dict(embed_dim=8, hidden=64, max_field=2_000)


def make(model_name="widedeep", seed=0):
    spec = ctr_spec(model_name, "criteo", **SPEC_KW)
    model = CTR_MODELS[model_name](spec)
    return model, model.init(jax.random.PRNGKey(seed))


def rows_of(n, seed=0):
    rng = np.random.default_rng(seed)
    return [np.array([rng.integers(0, s) for s in SCHEMA.field_sizes],
                     dtype=np.int32) for _ in range(n)]


def build_runtime(n_models, scheduler, pool_size=2, max_wait_ms=3.0,
                  ladder=(8, 16)):
    rt = ServingRuntime(scheduler=scheduler, pool_size=pool_size)
    for i in range(n_models):
        model, params = make(seed=i)
        rt.add_model(f"m{i}", model, params,
                     policy=TimeoutBatch(BucketedBatch(ladder),
                                         max_wait_ms=max_wait_ms),
                     worker_tick_ms=1.0)
    rt.warmup()
    return rt


def drive(rt, rows):
    names = rt.models
    futs = [rt.submit(names[i % len(names)], row)
            for i, row in enumerate(rows)]
    return np.array([f.result(timeout=120.0) for f in futs])


# --- acceptance: thread budget + bit-exactness --------------------------------

def test_eight_models_two_threads_bit_exact():
    """Acceptance: hosting N=8 models on a pool of 2 spawns at most
    pool_size + 1 threads (instead of 8 per-engine workers), and every
    score is bit-exact with per-engine-worker mode on the same traffic."""
    rows = rows_of(96)

    shared = build_runtime(8, "shared", pool_size=2)
    before = threading.active_count()
    shared.start()
    got, peak = None, threading.active_count()
    try:
        got = drive(shared, rows)
        peak = max(peak, threading.active_count())
    finally:
        shared.stop()
    assert peak - before <= 2 + 1, (peak, before)     # pool_size + 1, not N
    agg = shared.stats()
    assert agg.n_requests == 96 and agg.queue_depth == 0

    per_engine = build_runtime(8, "per-engine")
    before = threading.active_count()
    per_engine.start()
    try:
        want = drive(per_engine, rows)
        workers = threading.active_count() - before
    finally:
        per_engine.stop()
    assert workers >= 8                                # the old cost: N threads
    np.testing.assert_array_equal(got, want)           # bit-exact across modes


def test_dispatch_wall_share_and_dispatch_counters():
    rt = build_runtime(3, "shared", pool_size=2)
    rt.start()
    try:
        drive(rt, rows_of(48))
    finally:
        rt.stop()
    agg = rt.stats()
    assert agg.sched_dispatches >= 3                   # every model dispatched
    assert abs(agg.dispatch_wall_share - 1.0) < 1e-9   # shares sum to 1
    for name in rt.models:
        st = agg.per_model[name]
        assert st.sched_dispatches >= 1
        assert 0.0 < st.dispatch_wall_share < 1.0
        assert st.sched_preempted_slack_ms >= 0.0
    sched = rt.scheduler
    assert sched is not None and not sched.running     # stopped with the rt
    assert sched.n_dispatches == agg.sched_dispatches
    assert abs(sum(sched.shares.values()) - 1.0) < 1e-9


# --- SLO-slack fairness -------------------------------------------------------

def test_starved_model_meets_slo_behind_heavy_traffic():
    """A low-traffic model's due TimeoutBatch partial outranks the heavy
    model's endless full buckets: its 3 requests must resolve promptly
    (least-slack pick), not starve behind the high-traffic stream."""
    rt = ServingRuntime(pool_size=2)
    heavy_model, heavy_params = make(seed=0)
    rt.add_model("heavy", heavy_model, heavy_params,
                 policy=TimeoutBatch(FixedBatch(16), max_wait_ms=50.0),
                 worker_tick_ms=1.0)
    starved_model, starved_params = make(seed=1)
    rt.add_model("starved", starved_model, starved_params,
                 policy=TimeoutBatch(FixedBatch(16), max_wait_ms=10.0),
                 worker_tick_ms=1.0)
    rt.warmup()
    rt.start()
    stop_flag = threading.Event()

    def hammer():
        while not stop_flag.is_set():
            for f in [rt.submit("heavy", r) for r in rows_of(32)]:
                f.result(timeout=120.0)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        time.sleep(0.05)                       # heavy stream in full swing
        futs = [rt.submit("starved", r) for r in rows_of(3, seed=9)]
        t0 = time.perf_counter()
        for f in futs:
            f.result(timeout=30.0)
        waited_ms = (time.perf_counter() - t0) * 1e3
    finally:
        stop_flag.set()
        t.join()
        rt.stop()
    # SLO is 10ms; generous CI headroom, but nowhere near starvation
    assert waited_ms < 5_000.0, waited_ms
    st = rt.stats().per_model["starved"]
    assert st.n_requests == 3 and st.sched_dispatches >= 1


def test_backpressure_stays_per_engine_under_shared_pool():
    """max_queue_depth keeps rejecting per engine with the shared pool
    running: one bounded engine sheds load while its neighbour accepts."""
    rt = ServingRuntime(pool_size=2)
    m0, p0 = make(seed=0)
    # a policy that never dispatches on its own: partial held ~forever
    rt.add_model("bounded", m0, p0,
                 policy=TimeoutBatch(FixedBatch(64), max_wait_ms=60_000.0),
                 max_queue_depth=4)
    m1, p1 = make(seed=1)
    rt.add_model("free", m1, p1,
                 policy=TimeoutBatch(FixedBatch(8), max_wait_ms=2.0),
                 worker_tick_ms=1.0)
    rt.warmup()
    rt.start()
    try:
        kept = [rt.submit("bounded", r) for r in rows_of(4)]
        rejected = rt.submit("bounded", rows_of(1, seed=5)[0])
        assert rejected.done()
        with pytest.raises(QueueFullError):
            rejected.result(timeout=0)
        # the neighbour engine is unaffected by the bounded one's shedding
        ok = [rt.submit("free", r) for r in rows_of(6, seed=7)]
        for f in ok:
            f.result(timeout=60.0)
    finally:
        rt.stop()                              # flush resolves the kept 4
    assert all(f.done() for f in kept)
    st = rt.stats()
    assert st.n_rejected == 1
    assert st.per_model["bounded"].n_rejected == 1
    assert st.per_model["free"].n_rejected == 0


# --- readiness view -----------------------------------------------------------

def test_next_ready_full_bucket_due_now():
    model, params = make()
    eng = InferenceEngine(model, params, policy=BucketedBatch((8, 16)))
    assert eng.next_ready() is None            # empty queue
    eng.submit_many(rows_of(19))
    c = eng.next_ready()
    assert (c.take, c.bucket, c.partial) == (16, 16, False)
    assert c.slack_ms == 0.0                   # full buckets are due now
    eng.flush()
    assert eng.next_ready() is None


def test_next_ready_timeout_partial_carries_slo_slack():
    model, params = make()
    eng = InferenceEngine(model, params,
                          policy=TimeoutBatch(FixedBatch(8),
                                              max_wait_ms=200.0))
    eng.submit(rows_of(1)[0])
    c = eng.next_ready()
    assert c.partial and (c.take, c.bucket) == (1, 8)
    assert 0.0 < c.slack_ms <= 200.0           # deadline minus queue age
    later = eng.next_ready(time.perf_counter() + 1.0)
    assert later.slack_ms < 0.0                # past the deadline: overdue
    eng.flush()


def test_next_ready_default_grace_for_deadline_free_policies():
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(8),
                          worker_tick_ms=5.0)
    eng.submit_many(rows_of(3))
    c = eng.next_ready()
    assert c.partial and c.slack_ms <= 8 * 5.0  # the worker-loop grace
    eng.flush()


def test_scheduler_picks_least_slack_candidate():
    sched = DeviceScheduler(pool_size=1)
    model_a, params_a = make(seed=0)
    a = InferenceEngine(model_a, params_a,
                        policy=TimeoutBatch(FixedBatch(8), max_wait_ms=5.0))
    model_b, params_b = make(seed=1)
    b = InferenceEngine(model_b, params_b,
                        policy=TimeoutBatch(FixedBatch(8), max_wait_ms=500.0))
    sched.attach("a", a)
    sched.attach("b", b)
    b.submit(rows_of(1, seed=1)[0])            # due much later
    a.submit(rows_of(1, seed=0)[0])            # due in 5ms
    name, cand, _ = sched._pick(time.perf_counter() + 0.05)
    assert name == "a" and cand.partial        # most overdue deadline first
    a.flush()
    b.flush()


def test_attach_rejects_conflicts():
    sched = DeviceScheduler(pool_size=1)
    model, params = make()
    eng = InferenceEngine(model, params, policy=FixedBatch(8))
    sched.attach("m", eng)
    sched.attach("m", eng)                     # idempotent
    other_model, other_params = make(seed=1)
    other = InferenceEngine(other_model, other_params, policy=FixedBatch(8))
    with pytest.raises(ValueError, match="already attached"):
        sched.attach("m", other)
    with pytest.raises(ValueError, match="another scheduler"):
        DeviceScheduler(pool_size=1).attach("m", eng)
    with pytest.raises(ValueError, match="pool_size"):
        DeviceScheduler(pool_size=0)


# --- coalescing ---------------------------------------------------------------

def test_coalesces_requests_across_intake_streams():
    """Two submitter threads feed one model; the scheduler serves their
    union as one full device batch (n_batches == 1) — same-model
    requests coalesce across intake streams before dispatch."""
    model, params = make()
    eng = InferenceEngine(model, params,
                          policy=TimeoutBatch(FixedBatch(8),
                                              max_wait_ms=60_000.0))
    eng.warmup()
    sched = DeviceScheduler(pool_size=2)
    sched.attach("m", eng)
    sched.start()
    futs, lock = [], threading.Lock()

    def intake(seed):
        for f in eng.submit_many(rows_of(4, seed=seed)):
            with lock:
                futs.append(f)

    threads = [threading.Thread(target=intake, args=(s,)) for s in (1, 2)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for f in futs:
            f.result(timeout=60.0)
    finally:
        sched.stop()
    # the full bucket only exists as the union of both streams' submits
    assert eng.stats.n_batches == 1
    assert eng.stats.batches_per_bucket == {8: 1}
    assert eng.stats.sched_dispatches == 1


# --- worker-error surfacing (ISSUE-9 satellite) -------------------------------

def test_worker_error_counted_and_reraised_from_stop():
    """A background-drain failure (ragged row) fails its batch's futures
    immediately, bumps n_worker_errors, and stop() re-raises the last
    error instead of swallowing it forever."""
    model, params = make()
    eng = InferenceEngine(model, params,
                          policy=TimeoutBatch(FixedBatch(8), max_wait_ms=5.0),
                          worker_tick_ms=1.0)
    eng.warmup()
    eng.start()
    futs = eng.submit_many(rows_of(2))
    bad = eng.submit(np.zeros(len(SCHEMA.field_sizes) + 1, dtype=np.int32))
    for f in futs + [bad]:
        with pytest.raises(ValueError):
            f.result(timeout=60.0)             # batch failed, not stranded
    assert eng.stats.n_worker_errors == 1
    with pytest.raises(ValueError):
        eng.stop()                             # surfaces the swallowed error
    eng.stop()                                 # idempotent once drained


def test_worker_error_surfaced_through_shared_pool_and_runtime_stop():
    rt = ServingRuntime(pool_size=2)
    model, params = make()
    rt.add_model("m", model, params,
                 policy=TimeoutBatch(FixedBatch(8), max_wait_ms=5.0),
                 worker_tick_ms=1.0)
    rt.warmup()
    rt.start()
    futs = rt.submit_many("m", rows_of(2))
    bad = rt.submit("m", np.zeros(len(SCHEMA.field_sizes) + 1,
                                  dtype=np.int32))
    for f in futs + [bad]:
        with pytest.raises(ValueError):
            f.result(timeout=60.0)
    with pytest.raises(ValueError):
        rt.stop()                              # pool error resurfaces here
    assert rt.stats().n_worker_errors == 1
    rt.stop()                                  # idempotent once drained


# --- edge-triggered pool wake -------------------------------------------------

@pytest.fixture
def no_backstop(monkeypatch):
    """Stretch the pool's capped sleep to far past every test timeout, so
    a request only a lost wake-up would strand fails its ``result``."""
    monkeypatch.setattr(scheduler_mod, "_MAX_WAIT_S", 600.0)


def _pooled(*policies, pool_size=2):
    sched = DeviceScheduler(pool_size=pool_size)
    engines = []
    for i, policy in enumerate(policies):
        model, params = make(seed=i)
        eng = InferenceEngine(model, params, policy=policy)
        eng.warmup()
        engines.append(sched.attach(f"m{i}", eng))
    return sched, engines


def test_lone_request_served_after_the_hold(no_backstop):
    sched, (eng,) = _pooled(TimeoutBatch(BucketedBatch((8, 16)),
                                         max_wait_ms=5.0))
    sched.start()
    try:
        time.sleep(0.05)                       # the pool is asleep
        fut = eng.submit(rows_of(1)[0])
        fut.result(timeout=30.0)
    finally:
        sched.stop()
    assert fut.latency_ms >= 5.0               # held for the deadline
    assert eng.stats.pool_wakes == 1           # the 0 -> 1 edge
    assert eng.stats.batches_per_bucket == {8: 1}


def test_lone_partial_read_back_by_a_pool_of_one(no_backstop):
    """One pool thread launches a lone partial and holds it; nothing else
    is ever due, and the same thread comes back to read it back."""
    sched, (eng,) = _pooled(TimeoutBatch(BucketedBatch((8, 16)),
                                         max_wait_ms=5.0), pool_size=1)
    sched.start()
    try:
        fut = eng.submit(rows_of(1)[0])
        fut.result(timeout=30.0)
    finally:
        sched.stop()
    assert fut.latency_ms >= 5.0
    assert eng._held is None
    assert (eng.stats.n_batches, eng.stats.n_overlapped_batches) == (1, 0)
    assert eng.stats.sched_dispatches == 1     # the readback is no dispatch


def test_held_engine_is_due_now_behind_overdue_partials():
    """An engine holding a launched batch is due now (slack 0, nothing to
    take), so an overdue partial elsewhere still goes first; once that is
    served, the held engine is picked and read back."""
    sched = DeviceScheduler(pool_size=1)
    model_a, params_a = make(seed=0)
    a = InferenceEngine(model_a, params_a, policy=FixedBatch(8))
    model_b, params_b = make(seed=1)
    b = InferenceEngine(model_b, params_b,
                        policy=TimeoutBatch(FixedBatch(8), max_wait_ms=5.0))
    sched.attach("a", a)
    sched.attach("b", b)
    futs = a.submit_many(rows_of(8))
    assert a._serve_step(allow_partial=False, force=False) == (True, None)
    c = a.next_ready()
    assert (c.take, c.slack_ms, c.partial) == (0, 0.0, False)
    b.submit(rows_of(1, seed=1)[0])
    name, cand, _ = sched._pick(time.perf_counter() + 0.05)
    assert name == "b" and cand.partial and cand.slack_ms < 0.0
    b.flush()
    name, cand, _ = sched._pick(time.perf_counter())
    assert name == "a" and cand.take == 0
    launched, scores = a._serve_step(allow_partial=cand.partial, force=False)
    assert not launched and len(scores) == 8
    assert all(f.done() for f in futs) and a.next_ready() is None


def test_pipelined_drain_under_thread_churn(no_backstop):
    """Two engines, a pool of three and a 10 us switch interval, one
    submitter thread each: every future resolves once, in its engine's
    submit order, with the plan's own score, and nothing stays held."""
    sched, engines = _pooled(
        *[TimeoutBatch(FixedBatch(8), max_wait_ms=1.0) for _ in range(2)],
        pool_size=3)
    n = 200
    rows = [np.stack(rows_of(n, seed=s)) for s in range(2)]
    order = [[], []]
    futs = [[], []]

    def submitter(k):
        for i in range(n):
            f = engines[k].submit(rows[k][i])
            f.add_done_callback(lambda _, i=i: order[k].append(i))
            futs[k].append(f)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    sched.start()
    try:
        threads = [threading.Thread(target=submitter, args=(k,))
                   for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
        got = [np.array([f.result(timeout=60.0) for f in fs])
               for fs in futs]
    finally:
        sched.stop()
        sys.setswitchinterval(old)
    for k, eng in enumerate(engines):
        assert order[k] == list(range(n))
        assert eng._held is None and eng.stats.n_requests == n
        assert eng.stats.n_overlapped_batches < eng.stats.n_batches
        # a score depends on its own row only, not on the batch's other
        # rows or its place there (padding rows are zeros)
        plan = eng.plan_for(8)
        want = np.concatenate([plan.predict(rows[k][i:i + 8])
                               for i in range(0, n, 8)])
        np.testing.assert_array_equal(got[k], want)


def test_burst_reaching_a_bucket_is_served_before_the_hold(no_backstop):
    sched, (eng,) = _pooled(TimeoutBatch(BucketedBatch((8, 16)),
                                         max_wait_ms=60_000.0))
    sched.start()
    try:
        time.sleep(0.05)
        futs = eng.submit_many(rows_of(8))
        for f in futs:
            f.result(timeout=30.0)             # not after the 60 s hold
    finally:
        sched.stop()
    assert eng.stats.pool_wakes == 2           # first request, full bucket
    assert eng.stats.batches_per_bucket == {8: 1}


def test_idle_engine_served_while_another_is_claimed(no_backstop):
    """Engine 0's batch holds its pool thread (a blocking done-callback);
    a submit to engine 1 wakes the other thread, which serves it."""
    sched, (busy, idle) = _pooled(
        TimeoutBatch(FixedBatch(1), max_wait_ms=5.0),
        TimeoutBatch(FixedBatch(8), max_wait_ms=5.0))
    release = threading.Event()
    held = busy.submit(rows_of(1)[0])
    held.add_done_callback(lambda f: release.wait(60.0))
    sched.start()                              # the callback runs in a claim
    try:
        deadline = time.perf_counter() + 30.0
        while not (busy._claimed and held.done()):
            assert time.perf_counter() < deadline
            time.sleep(0.001)
        fut = idle.submit(rows_of(1, seed=1)[0])
        fut.result(timeout=30.0)
        assert busy._claimed                   # still inside its batch
    finally:
        release.set()
        sched.stop()
    assert idle.stats.pool_wakes == 1 and idle.stats.n_requests == 1


def test_closed_loop_wakes_rarely_and_scores_bit_exact(no_backstop):
    """Pool 2, 2,048 outstanding, each resolved request replaced from
    the submitting thread: nearly every submit finds the engine claimed
    or mid-bucket, and every score equals the plan's own on its row."""
    rt = ServingRuntime(pool_size=2)
    model, params = make()
    eng = rt.add_model("m", model, params,
                       policy=TimeoutBatch(BucketedBatch((512,)),
                                           max_wait_ms=5.0))
    rt.warmup()
    pool = np.stack(rows_of(1_024, seed=3))
    outstanding, total = 2_048, 4 * 2_048
    done_q = collections.deque()
    futs = []

    def send():
        i = len(futs)
        fut = rt.submit("m", pool[i % len(pool)])
        fut.add_done_callback(lambda f: done_q.append(i))
        futs.append(fut)

    rt.start()
    try:
        for _ in range(outstanding):
            send()
        deadline = time.perf_counter() + 120.0
        while len(futs) < total:
            assert time.perf_counter() < deadline
            if done_q:
                done_q.popleft()
                send()
            else:
                time.sleep(0.0001)
        got = np.array([f.result(timeout=60.0) for f in futs],
                       dtype=np.float32)
    finally:
        rt.stop()
    st = rt.stats()
    assert st.n_requests == total
    assert st.pool_wakes <= 0.02 * st.n_requests, st.pool_wakes
    # 2,048 queued at the start: the second bucket launched while the
    # first was held, at least
    assert 0 < st.n_overlapped_batches < st.n_batches
    plan = eng.plan_for(512)
    want = np.concatenate([plan.predict(pool[i:i + 512])
                           for i in range(0, len(pool), 512)])
    idx = np.arange(total) % len(pool)
    np.testing.assert_array_equal(got, want[idx])
