"""Online model-updates benchmark — trainer delta streams applied under
live batch traffic.

Each cell serves a zipf request stream through a ``ServingRuntime`` while
a ``SyntheticTrainer`` delta stream drains on the runtime's
``delta_every`` cadence (background pulls off the intake hot path), then
hard-asserts the online-update contract where the sweep runs (CI dry
included):

  * **zero recompiles** — the plan-cache compile count and plan set are
    identical before and after the whole stream applied (deltas publish
    through the versioned double-buffered swap, never through XLA);
  * **version accounting** — ``emb_version`` ends exactly at the number
    of pushed batches (every push bumps once, nothing else does);
  * **value correctness** — post-stream scores are bit-exact with a
    dense engine rebuilt from a table with the same deltas applied
    (fp32 cells), or with a fresh int8 tier built from that delta-applied
    table (int8 cells — the re-quantization parity contract: pushing
    fp32 rows through ``push_update`` lands on the same int8 grid as
    loading them cold);
  * **staleness drained** — ``rows_behind`` reads 0 once the stream is
    consumed.

CPU timings are noise-bound; each cell's ``structural`` sub-dict holds
only traffic-deterministic values (push/row/version counts and the
assertion outcomes above) and is pinned by ``BENCH_serving.json`` via
``benchmarks/diff_baseline.py``. Timing fields live in ``timing`` and
are ignored by the diff.
"""

from __future__ import annotations

import time

import numpy as np
import jax

from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO, zipf_ids
from repro.embedding import CachedStore, HostBackedStore
from repro.kernels.multi_table_lookup import pack_rows
from repro.models.ctr import CTR_MODELS
from repro.serving import (BucketedBatch, InferenceEngine, ServingRuntime,
                           SyntheticTrainer, TimeoutBatch)

from .common import emit

MAX_FIELD = 100_000


def _stream(schema, n, seed=1):
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                               schema.field_sizes, exponent=1.1))


def _make_store(kind, espec, capacity, row_dtype):
    cls = {"cached": CachedStore, "host": HostBackedStore}[kind]
    return cls(espec, capacity=capacity, row_dtype=row_dtype)


def _cell(store_kind: str, row_dtype: str | None, n_requests: int,
          delta_every: int, delta_rows: int, n_pushes: int,
          ladder, max_field: int) -> dict:
    """One (store tier × delta stream × traffic) cell. Small dims keep
    the compile cost bounded; the update path under test is
    width-independent."""
    schema = CRITEO.scaled(max_field)
    spec = ctr_spec("widedeep", "criteo", 8, 64, max_field=max_field)
    espec = spec.embedding_spec()
    # separate instances: an engine binds its store to the model's
    # collection, so serving and reference must not share one model
    model = CTR_MODELS["widedeep"](spec)
    ref_model = CTR_MODELS["widedeep"](spec)
    ids = _stream(schema, n_requests)
    probe = ids[:ladder[-1]]
    trainer = SyntheticTrainer(espec, rows_per_batch=delta_rows,
                               n_batches=n_pushes, seed=0)

    rt = ServingRuntime(delta_every=delta_every)
    rt.add_model("m", model, model.init(jax.random.PRNGKey(0)),
                 policy=TimeoutBatch(BucketedBatch(ladder), max_wait_ms=2.0),
                 store=_make_store(store_kind, espec,
                                   capacity=max(64, max_field // 50),
                                   row_dtype=row_dtype),
                 worker_tick_ms=1.0)
    rt.attach_delta_stream("m", trainer)
    rt.warmup()
    eng = rt.engine("m")
    eng.predict(probe)                        # pin the probe plan too
    compiles_before = eng.stats.cache_misses
    plans_before = set(eng.cached_plans)

    rt.start()
    t0 = time.perf_counter()
    futs = [rt.submit("m", row) for row in ids]
    for f in futs:
        f.result(timeout=600.0)
    dt = time.perf_counter() - t0
    rt.stop()                                 # joins the background pull
    rt.pull_updates()                         # leftovers, deterministically
    st = rt.stats()

    # --- the contract, hard-asserted ---------------------------------------
    assert eng.stats.cache_misses == compiles_before \
        and set(eng.cached_plans) == plans_before, (
            f"online deltas recompiled: {compiles_before} -> "
            f"{eng.stats.cache_misses} compiles")
    assert st.emb_version == n_pushes, (
        f"version drift: {n_pushes} pushes but emb_version={st.emb_version}")
    assert st.rows_behind == 0, f"stream not drained: {st.rows_behind} rows"

    # reference: the same delta stream applied to a dense fp32 table
    # (numpy fancy assignment keeps the last duplicate — the store's
    # dedupe rule), then served through a cold engine of the same tier
    ref_params = ref_model.init(jax.random.PRNGKey(0))
    key = ref_model.main_embedding_key
    table = np.array(ref_model.embedding.dense_view(ref_params[key]))
    replay = trainer.replay()
    while (batch := replay.next_batch()) is not None:
        b_ids, b_rows = batch
        table[b_ids] = b_rows
    ref_params = dict(ref_params)
    ref_params[key] = {**ref_params[key], "mega_table": pack_rows(table)}
    ref_store = (None if row_dtype is None else
                 _make_store(store_kind, espec,
                             capacity=max(64, max_field // 50),
                             row_dtype=row_dtype))
    ref_eng = InferenceEngine(ref_model, ref_params,
                              policy=BucketedBatch(ladder), store=ref_store)
    exact = bool(np.array_equal(eng.predict(probe), ref_eng.predict(probe)))
    assert exact, "post-stream scores diverge from the rebuilt reference"

    dtype_tag = row_dtype or "fp32"
    tag = f"{store_kind}_{dtype_tag}_r{delta_rows}"
    emit(f"serving_updates/{tag}", dt / n_requests * 1e6,
         f"req_s={n_requests/dt:.0f} pushes={st.emb_delta_pushes} "
         f"delta_rows={st.emb_delta_rows} version=v{st.emb_version} "
         f"delta_rows_s={st.emb_delta_rows/dt:.0f} exact={exact}")
    return {
        "structural": {
            # deterministic for fixed traffic + trainer seed: pinned by
            # BENCH_serving.json
            "store": store_kind,
            "row_dtype": dtype_tag,
            "n_requests": n_requests,
            "delta_every": delta_every,
            "n_pushes": int(st.emb_delta_pushes),
            "delta_rows_applied": int(st.emb_delta_rows),
            "emb_version": int(st.emb_version),
            "zero_recompiles": True,          # asserted above
            "bitexact_after_deltas": exact,   # requant parity on int8 cells
            "staleness_drained": True,        # asserted above
        },
        "timing": {
            "req_s": n_requests / dt,
            "delta_rows_per_s": st.emb_delta_rows / dt,
            "p99_ms": st.p99_ms,
        },
    }


def run(quick: bool = False, dry: bool = False) -> dict:
    n = 64 if dry else (256 if quick else 2000)
    ladder = (8, 16) if (dry or quick) else (32, 64, 128, 256)
    max_field = 2_000 if (dry or quick) else MAX_FIELD
    # cell names are part of the pinned baseline: the CI dry run must
    # produce exactly the dry list below (diff_baseline compares cell sets)
    if dry or quick:
        cells = [("cached", None, 32, 2), ("cached", "int8", 32, 2),
                 ("host", None, 32, 2)]
    else:
        cells = [("cached", None, 256, 8), ("cached", "int8", 256, 8),
                 ("host", None, 256, 8), ("host", "int8", 256, 8)]
    results = {}
    for store_kind, row_dtype, delta_rows, n_pushes in cells:
        tag = f"{store_kind}_{row_dtype or 'fp32'}_r{delta_rows}"
        results[tag] = _cell(store_kind, row_dtype, n, delta_every=n // 4,
                             delta_rows=delta_rows, n_pushes=n_pushes,
                             ladder=ladder, max_field=max_field)
    return results


if __name__ == "__main__":
    run()
