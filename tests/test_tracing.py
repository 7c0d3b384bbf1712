"""Host spans and stage counters of the serving path, on the CPU.

One small engine, on a ``HostBackedStore`` so that the staging stage
runs too, is compiled and served under the shared scheduler while the
profiler traces; the trace is read with the benchmark's own reader
(``chipbench.trace``), as a chip run's would be.
"""

import collections
import time
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from chipbench import trace
from repro.configs import ctr_spec
from repro.core.plan import compile_plan
from repro.data.synthetic import CRITEO
from repro.embedding import CachedStore, HostBackedStore
from repro.models.ctr import CTR_MODELS
from repro.serving import (BucketedBatch, FixedBatch, InferenceEngine,
                           ServingRuntime, TimeoutBatch)

SCHEMA = CRITEO.scaled(2_000)
SPEC_KW = dict(embed_dim=8, hidden=64, max_field=2_000)
POOL = 2
SPANS = ("engine.batch", "engine.stack", "engine.observe", "engine.stage",
         "plan.dispatch", "plan.wait", "plan.readback", "engine.resolve",
         "engine.refresh", "sched.pick", "sched.wait", "plan.compile")
#: the spans opened once for every served batch
PER_BATCH = ("engine.batch", "engine.stack", "engine.observe",
             "engine.stage", "plan.dispatch", "plan.wait", "plan.readback",
             "engine.resolve")
STAGES = ("stack_ms_total", "observe_ms_total", "dispatch_ms_total",
          "device_wait_ms_total", "readback_ms_total", "resolve_ms_total")


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, SCHEMA.field_sizes)
                     for _ in range(n)]).astype(np.int32)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Compile, serve 96 requests and re-admit the cache, all traced."""
    spec = ctr_spec("widedeep", "criteo", **SPEC_KW)
    model = CTR_MODELS["widedeep"](spec)
    params = model.init(jax.random.PRNGKey(0))
    # staging holds a whole 16-row batch's misses: one stage per batch
    store = HostBackedStore(spec.embedding_spec(), capacity=64,
                            staging_capacity=1024)
    rows = _rows(96)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    rt = ServingRuntime(pool_size=POOL)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        eng = rt.add_model("m", model, params, store=store,
                           policy=TimeoutBatch(BucketedBatch((8, 16)),
                                               max_wait_ms=2.0))
        rt.warmup()
        rt.start()
        futs = [rt.submit("m", r) for r in rows]
        scores = np.array([f.result(timeout=120.0) for f in futs])
        wall_ms = (time.perf_counter() - t0) * 1e3
        rt.refresh_all()
    finally:
        jax.profiler.stop_trace()
        rt.stop()
    host = [e for e in trace.load(trace_dir) if e.plane == trace.HOST_PLANE]
    return types.SimpleNamespace(host=host, eng=eng, rows=rows,
                                 scores=scores, wall_ms=wall_ms,
                                 stats=eng.stats.snapshot())


def _named(host, name):
    return [e for e in host if e.name == name]


def test_every_span_is_on_the_host_plane(served):
    names = {e.name for e in served.host}
    assert set(SPANS) <= names, set(SPANS) - names


def test_spans_are_per_batch_not_per_request(served):
    counts = collections.Counter(e.name for e in served.host)
    st = served.stats
    assert 0 < st.n_batches < st.n_requests
    for name in PER_BATCH:
        assert counts[name] == st.n_batches, name
    # a pool thread opens one pick span a claim and at most one wait span
    # before it, however many submits woke it meanwhile
    assert counts["sched.pick"] <= st.sched_dispatches + POOL
    assert counts["sched.wait"] <= counts["sched.pick"]
    assert counts["plan.compile"] == 2          # one plan a bucket


def test_plan_wait_nests_in_engine_batch_and_labels_a_gap(served):
    host = served.host
    batches = _named(host, "engine.batch")
    waits = _named(host, "plan.wait")
    assert waits
    for w in waits:
        assert any(b.line == w.line and b.start_ns <= w.start_ns
                   and w.end_ns <= b.end_ns for b in batches)
        # an idle gap of the device inside the wait is put down to it,
        # or to a host event the wait itself opened
        t = w.start_ns + 0.5 * w.dur_ns
        label = trace.label_gap(host, t)
        gen, inner = label.split("; ")
        assert gen == "gen.none"
        assert inner == "plan.wait" or any(
            e.name == inner and w.start_ns <= e.start_ns <= t < e.end_ns
            <= w.end_ns for e in host)


def test_stage_counters_add_up(served):
    st = served.stats
    stages = sum(getattr(st, k) for k in STAGES)
    assert 0 < stages <= st.batch_ms_total <= served.wall_ms
    assert st.queue_wait_ms_total > 0
    plan_ms = (st.dispatch_ms_total + st.device_wait_ms_total
               + st.readback_ms_total)
    # the plan call's three stages and nothing else, summed per batch
    assert plan_ms > 0
    assert st.compute_ms_total == pytest.approx(plan_ms, rel=1e-9)


def test_scores_bit_identical_to_a_plain_predict(served):
    np.testing.assert_array_equal(served.scores,
                                  served.eng.predict(served.rows))


def test_predict_is_launch_then_fetch_bit_for_bit():
    """The split plan call computes what the one-piece call computed:
    pad, step, sigmoid of the flattened logits, read back, slice; a
    launched batch stands for its rows, and ``predict`` reads it back."""
    spec = ctr_spec("dcnv2", "criteo", **SPEC_KW)
    model = CTR_MODELS["dcnv2"](spec)
    plan = compile_plan(model, model.init(jax.random.PRNGKey(1)), "dual", 16)
    ids = _rows(11, seed=3)
    padded = np.concatenate([ids, np.zeros((5, ids.shape[1]), np.int32)])
    logits = plan.step(jnp.asarray(padded))
    want = np.asarray(
        jax.nn.sigmoid(jnp.reshape(jnp.asarray(logits), (-1,))))[:11]
    np.testing.assert_array_equal(plan.predict(ids), want)
    launched = plan.launch(ids)
    assert launched.scores.shape == (16,)
    np.testing.assert_array_equal(np.asarray(launched), ids)
    plan.wait(launched)
    np.testing.assert_array_equal(plan.predict(launched), want)


def test_overlapped_batches_nest_their_spans_in_two_batch_spans(tmp_path):
    """A pipelined drain (a cached store, three full buckets queued):
    each batch opens ``engine.batch`` twice, around its launch and around
    its readback, every stage span nests in one of them on its thread,
    and batch 2 is dispatched before batch 1 is waited for."""
    spec = ctr_spec("widedeep", "criteo", **SPEC_KW)
    model = CTR_MODELS["widedeep"](spec)
    eng = InferenceEngine(model, model.init(jax.random.PRNGKey(0)),
                          policy=FixedBatch(8),
                          store=CachedStore(spec.embedding_spec(),
                                            capacity=64))
    eng.warmup()
    eng.submit_many(list(_rows(24)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.serve_pending()
    finally:
        jax.profiler.stop_trace()
    st = eng.stats
    assert (st.n_batches, st.n_overlapped_batches) == (3, 2)
    host = [e for e in trace.load(str(tmp_path))
            if e.plane == trace.HOST_PLANE]
    batches = _named(host, "engine.batch")
    assert len(batches) == 2 * st.n_batches
    launch_side = ("engine.stack", "engine.observe", "plan.dispatch")
    read_side = ("plan.wait", "plan.readback", "engine.resolve")
    for name in launch_side + read_side:
        spans = _named(host, name)
        assert len(spans) == st.n_batches, name
        for e in spans:
            assert any(b.line == e.line and b.start_ns <= e.start_ns
                       and e.end_ns <= b.end_ns for b in batches), name
    dispatch = sorted(e.start_ns for e in _named(host, "plan.dispatch"))
    wait = sorted(e.start_ns for e in _named(host, "plan.wait"))
    assert dispatch[1] < wait[0] and dispatch[2] < wait[1]
