"""Async serving runtime benchmark — sync drain vs futures intake,
plan-cache survival across embedding-cache refreshes, and the many-model
shared-scheduler sweep.

Measurements on the same zipf request stream:

  1. **sync**: the caller submits a wave then drains it (`serve_pending`)
     — the pre-runtime serving loop, intake blocked on compute.
  2. **async**: the background worker drains the queue through the same
     policy while the caller keeps submitting; per-request futures
     resolve as batches complete (PCDF's full-link-parallel loop).
  3. **refresh survival**: a `CachedStore` engine refreshes its hot-row
     cache repeatedly under traffic; because the store tensors are
     runtime inputs of every compiled plan, the plan cache must survive
     each refresh with zero new compiles (`survived=True` in the derived
     column — the HugeCTR online-refresh property).
  4. **many-model sweep** (models × offered load): the same round-robin
     traffic served twice — through one shared ``DeviceScheduler`` pool
     and through per-engine worker threads. Reports p99, thread-count
     delta, and per-model dispatch wall-time share; hard-asserts the shared
     mode's thread budget (≤ pool_size + 1 new threads however many
     models are hosted) and score bit-exactness across modes.

Throughput deltas on CPU are modest (compute dominates); the structural
counters (batches formed without caller polling, compiles across
refreshes, thread budgets, cross-mode exactness) are the point — each
sweep cell's ``structural`` sub-dict holds only traffic-deterministic
values and is pinned by ``BENCH_serving.json`` via
``benchmarks/diff_baseline.py`` (timing fields live in ``timing`` and are
ignored).
"""

from __future__ import annotations

import threading
import time

import numpy as np
import jax

from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO, zipf_ids
from repro.embedding import CachedStore
from repro.models.ctr import CTR_MODELS
from repro.serving import (BucketedBatch, InferenceEngine, ServingRuntime,
                           TimeoutBatch)

from .common import emit

MAX_FIELD = 100_000


def _stream(schema, n, exponent=1.1, seed=0):
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                               schema.field_sizes, exponent=exponent))


def _build(model_name, max_field, store=None, **eng_kwargs):
    spec = ctr_spec(model_name, "criteo", 16, 256, max_field=max_field)
    model = CTR_MODELS[model_name](spec)
    params = model.init(jax.random.PRNGKey(0))
    return spec, InferenceEngine(model, params, store=store, **eng_kwargs)


def _sync(eng, ids, waves):
    t0 = time.perf_counter()
    for wave in np.array_split(ids, waves):
        eng.submit_many(list(wave))
        eng.serve_pending()
    eng.flush()
    return time.perf_counter() - t0


def _async(eng, ids):
    eng.start()
    t0 = time.perf_counter()
    futs = eng.submit_many(list(ids))
    for f in futs:
        f.result(timeout=300.0)
    dt = time.perf_counter() - t0
    eng.stop()
    return dt


def _sweep_cell(n_models: int, n_requests: int, ladder, max_field: int,
                pool_size: int = 2) -> dict:
    """One (models × offered load) cell: shared scheduler vs per-engine
    workers on identical traffic. Small dims (embed 8, hidden 64) keep
    the N-model compile cost bounded; the serving-loop behaviour under
    test doesn't depend on model width."""
    schema = CRITEO.scaled(max_field)
    ids = _stream(schema, n_requests, seed=1)

    def build_rt(mode):
        rt = ServingRuntime(scheduler=mode, pool_size=pool_size)
        for i in range(n_models):
            spec = ctr_spec("widedeep", "criteo", 8, 64,
                            max_field=max_field)
            model = CTR_MODELS["widedeep"](spec)
            rt.add_model(f"m{i}", model,
                         model.init(jax.random.PRNGKey(i)),
                         policy=TimeoutBatch(BucketedBatch(ladder),
                                             max_wait_ms=2.0),
                         worker_tick_ms=1.0)
        rt.warmup()
        return rt

    def drive(rt):
        t0 = time.perf_counter()
        futs = [rt.submit(rt.models[i % n_models], row)
                for i, row in enumerate(ids)]
        scores = np.array([f.result(timeout=600.0) for f in futs])
        return scores, time.perf_counter() - t0

    rt_s = build_rt("shared")
    before = threading.active_count()
    rt_s.start()
    scores_s, dt_s = drive(rt_s)
    delta_s = threading.active_count() - before
    rt_s.stop()
    agg_s = rt_s.stats()
    share_sum = sum(rt_s.scheduler.shares.values())
    shares = {n: round(s, 3) for n, s in sorted(
        rt_s.scheduler.shares.items())}

    rt_p = build_rt("per-engine")
    before = threading.active_count()
    rt_p.start()
    scores_p, dt_p = drive(rt_p)
    delta_p = threading.active_count() - before
    rt_p.stop()
    agg_p = rt_p.stats()

    # the acceptance property, asserted where the sweep runs (CI dry
    # included): thread count must not scale with model count
    assert delta_s <= pool_size + 1, (
        f"shared scheduler spawned {delta_s} threads for {n_models} "
        f"models; budget is pool_size + 1 = {pool_size + 1}")
    bitexact = bool(np.array_equal(scores_s, scores_p))
    tag = f"sweep_m{n_models}_r{n_requests}"
    emit(f"serving_async/{tag}/shared", dt_s / n_requests * 1e6,
         f"req_s={n_requests/dt_s:.0f} p99_ms={agg_s.p99_ms:.1f} "
         f"threads=+{delta_s} dispatches={agg_s.sched_dispatches} "
         f"bitexact={bitexact}")
    emit(f"serving_async/{tag}/per_engine", dt_p / n_requests * 1e6,
         f"req_s={n_requests/dt_p:.0f} p99_ms={agg_p.p99_ms:.1f} "
         f"threads=+{delta_p}")
    return {
        "structural": {
            # deterministic for fixed traffic: pinned by BENCH_serving.json
            "n_models": n_models,
            "n_requests_per_mode": int(agg_s.n_requests),
            "pool_size": pool_size,
            "thread_budget_ok": True,        # the assert above enforces it
            "bitexact_vs_per_engine": bitexact,
            "share_sum_ok": bool(abs(share_sum - 1.0) < 1e-6),
            "compiles_total": int(agg_s.cache_misses),
            "worker_errors": int(agg_s.n_worker_errors
                                 + agg_p.n_worker_errors),
        },
        "timing": {
            "p99_ms_shared": agg_s.p99_ms,
            "p99_ms_per_engine": agg_p.p99_ms,
            "req_s_shared": n_requests / dt_s,
            "req_s_per_engine": n_requests / dt_p,
            "threads_shared": delta_s,
            "threads_per_engine": delta_p,
            "sched_dispatches": int(agg_s.sched_dispatches),
            "preempted_slack_ms": agg_s.sched_preempted_slack_ms,
            "dispatch_wall_share": shares,
        },
    }


def run(quick: bool = False, dry: bool = False) -> dict:
    n = 64 if dry else (400 if quick else 2000)
    ladder = (8, 16) if dry else (32, 64, 128, 256)
    max_field = 2_000 if dry else MAX_FIELD
    models = ["widedeep"] if (dry or quick) else ["deepfm", "dcnv2"]
    schema = CRITEO.scaled(max_field)
    ids = _stream(schema, n)
    results = {}

    # --- sync drain vs async futures intake -------------------------------
    for model_name in models:
        policy = TimeoutBatch(BucketedBatch(ladder), max_wait_ms=1.0)
        _, eng_s = _build(model_name, max_field, policy=policy)
        eng_s.warmup()
        dt_s = _sync(eng_s, ids, waves=4 if dry else 10)
        _, eng_a = _build(model_name, max_field, policy=policy)
        eng_a.warmup()
        dt_a = _async(eng_a, ids)
        ss, sa = eng_s.stats, eng_a.stats
        emit(f"serving_async/{model_name}/sync", dt_s / n * 1e6,
             f"req_s={n/dt_s:.0f} p99_ms={ss.p99_ms:.1f} "
             f"batches={ss.n_batches}")
        emit(f"serving_async/{model_name}/async", dt_a / n * 1e6,
             f"req_s={n/dt_a:.0f} p99_ms={sa.p99_ms:.1f} "
             f"batches={sa.n_batches} worker_drained=True")
        results[f"{model_name}/speedup"] = dt_s / dt_a

    # --- refresh-without-recompile under zipf traffic ----------------------
    store = CachedStore(
        ctr_spec(models[0], "criteo", 16, 256,
                 max_field=max_field).embedding_spec(),
        capacity=max(64, max_field // 50))
    _, eng = _build(models[0], max_field, store=store,
                    policy=BucketedBatch(ladder),
                    refresh_every=2)                 # refresh every 2 batches
    eng.warmup()
    compiles_before = eng.stats.cache_misses
    plans_before = set(eng.cached_plans)
    for wave in np.array_split(ids, 4):
        eng.submit_many(list(wave))
        eng.serve_pending()
    eng.flush()
    st = eng.stats
    survived = (eng.stats.cache_misses == compiles_before
                and set(eng.cached_plans) == plans_before)
    emit(f"serving_async/{models[0]}/refresh_survival",
         st.compute_ms_total / max(st.n_batches, 1) * 1e3,
         f"refreshes={st.emb_cache_refreshes} "
         f"compiles={st.cache_misses} survived={survived} "
         f"emb_hit={st.emb_cache_hit_rate:.2f} "
         f"cached_traffic={st.emb_cached_traffic_fraction:.2f}")
    results["refresh_survived"] = survived

    # --- two-model runtime through one async intake -------------------------
    if not dry:
        rt = ServingRuntime()
        for m in (models if len(models) > 1 else models + ["dcn"]):
            spec = ctr_spec(m, "criteo", 16, 256, max_field=max_field)
            model = CTR_MODELS[m](spec)
            rt.add_model(m, model, model.init(jax.random.PRNGKey(0)),
                         policy=TimeoutBatch(BucketedBatch(ladder),
                                             max_wait_ms=1.0))
        rt.warmup()
        rt.start()
        t0 = time.perf_counter()
        futs = [rt.submit(rt.models[i % len(rt.models)], row)
                for i, row in enumerate(ids)]
        for f in futs:
            f.result(timeout=300.0)
        dt = time.perf_counter() - t0
        rt.stop()
        agg = rt.stats()
        emit("serving_async/runtime/2models", dt / n * 1e6,
             f"req_s={n/dt:.0f} p99_ms={agg.p99_ms:.1f} "
             f"models={agg.n_models} batches={agg.n_batches}")
        results["runtime/req_s"] = n / dt

    # --- many-model sweep: shared scheduler vs per-engine workers ----------
    # cell names are part of the pinned baseline: the CI dry run must
    # produce exactly the dry list below (diff_baseline compares cell sets)
    cells = ([(2, 64), (6, 96)] if dry else
             ([(4, 256)] if quick else [(8, 2000), (8, 8000)]))
    for n_models, n_requests in cells:
        results[f"sweep_m{n_models}_r{n_requests}"] = _sweep_cell(
            n_models, n_requests, ladder, max_field)
    return results


if __name__ == "__main__":
    run()
