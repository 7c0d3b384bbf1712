"""HostBackedStore sweep: vocab × capacity × skew, out-of-HBM serving.

The scale question behind the host tier (HugeCTR hierarchical parameter
server, arXiv:2210.08804): with only ``C`` cache rows and ``S`` staging
slots on device, how does the traffic skew govern the hit rate and the
host→device row traffic as the vocabulary grows past the device budget?

Per (vocab, capacity, skew) cell, a ``HostBackedStore`` engine and a
``DenseStore`` engine serve the *same* zipf stream (warm-up wave, one
mid-stream ``refresh_cache``, then the measured waves) and the cell
**hard-asserts** the acceptance contract rather than merely reporting it:

  * bit-exact scores vs the dense engine (``assert_array_equal``, not
    allclose), and
  * whenever ``rows > C + S``, device-resident embedding bytes stay within
    the cache + staging budget (``store.device_bytes``) — the backing is
    never uploaded wholesale.

CSV: ``emb_host/V{vocab}/C{cap}/{skew}/host`` with hit rate, resolved
(staged + prefetched) rows, h2d bytes per batch and p50/p99 in the derived
column. The returned dict separates a ``structural`` sub-dict — counters
that are deterministic for fixed traffic (hit rate, refreshes, overflows,
resolved rows, byte budgets, the assertion outcomes) — from noise-bound
``timing`` numbers; the committed ``BENCH_embedding.json`` baseline and
``benchmarks/diff_baseline.py`` compare only the structural part.

A final fp32-vs-int8 pair at d=32 (same stream, same capacity) pins the
quantized tier's bytes-moved claim: ``gather_bytes`` and
``resolved_wire_bytes`` must drop by >= 3.5x (exactly 128/36 B/row),
hard-asserted; the int8 cell's scores gate at ``atol=1e-2`` instead of
bit-exactness (the model-level contract is ``accuracy_parity --quant``).

Determinism notes baked into the protocol: the refresh happens only after
``pipeline.wait_idle()`` (no hint race across the epoch boundary), and the
staging buffer is sized above each cell's worst-case distinct miss set so
LRU evictions — whose order depends on which thread staged a row — never
fire. Within an epoch the *union* of resolved rows is then exactly the
distinct miss set, whichever side of the hint race resolves each row.
"""

from __future__ import annotations

import time

import numpy as np
import jax

from repro.configs import ctr_spec
from repro.data.synthetic import CRITEO, zipf_ids
from repro.embedding import HostBackedStore
from repro.kernels.multi_table_lookup import rows_per_line
from repro.models.ctr import CTR_MODELS
from repro.serving import FixedBatch, InferenceEngine

from .common import emit

MODEL = "widedeep"


def _stream(vocab: int, n: int, exponent: float, seed: int = 1):
    schema = CRITEO.scaled(vocab)
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed), n,
                               schema.field_sizes, exponent=exponent))


def _build_pair(spec, capacity: int, staging: int, batch: int,
                row_dtype: str | None = None):
    # separate model instances: use_store rebinds the model's collection
    dense_model = CTR_MODELS[MODEL](spec)
    dense = InferenceEngine(dense_model,
                            dense_model.init(jax.random.PRNGKey(0)),
                            policy=FixedBatch(batch))
    model = CTR_MODELS[MODEL](spec)
    params = model.init(jax.random.PRNGKey(0))
    store = HostBackedStore(spec.embedding_spec(), capacity=capacity,
                            staging_capacity=staging, row_dtype=row_dtype)
    eng = InferenceEngine(model, params, policy=FixedBatch(batch),
                          store=store)
    return dense, eng, store


def _cell(vocab: int, capacity: int, exponent: float, n: int, batch: int,
          tag: str, *, dim: int = 16, row_dtype: str | None = None) -> dict:
    ids = _stream(vocab, n, exponent)
    spec = ctr_spec(MODEL, "criteo", dim, 256, max_field=vocab)
    emb = spec.embedding_spec()
    # staging must absorb the stream's full distinct row set so eviction
    # order (thread-dependent) never perturbs the structural counters
    distinct = np.unique(ids + emb.offsets[None, :]).size
    staging = int(min(distinct + batch * emb.k, emb.rows))
    dense, eng, store = _build_pair(spec, capacity, staging, batch, row_dtype)
    want = dense.predict(ids)

    waves = np.array_split(ids, 4)
    t0 = time.perf_counter()
    got = []
    for w, wave in enumerate(waves):
        eng.submit_many(list(wave))
        got.append(eng.serve_pending())
        if w == 1:                                # mid-stream cache rebuild
            store.pipeline.wait_idle(timeout=10.0)
            eng.refresh_cache()
    got.append(eng.flush())
    dt = time.perf_counter() - t0
    got = np.concatenate([g for g in got if g.size])

    # --- the acceptance contract, hard-asserted ---------------------------
    if row_dtype is None:
        np.testing.assert_array_equal(got, want)  # bit-exact, not allclose
    else:
        # int8 rows are lossy by design; here the contract is score parity
        # (the model-level gate lives in accuracy_parity --quant)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-2)
    st, es = store.stats, eng.stats
    key = eng.model.main_embedding_key
    dev_bytes = store.device_bytes(eng.params[key])
    row_bytes = store.wire_row_bytes              # dtype-aware (d+4 for int8)
    # cache and staging rows are stored in whole 128-lane packed lines
    rpl = rows_per_line(store.spec.dim, store.wire_dtype)
    slots = sum(-(-n // rpl) * rpl
                for n in (store.capacity, store.staging_capacity))
    budget = slots * row_bytes + 2 * store.spec.rows * 4   # + the int32 maps
    out_of_hbm = store.spec.rows > store.capacity + store.staging_capacity
    if out_of_hbm:
        assert dev_bytes <= budget, (dev_bytes, budget)

    resolved = st.staged_rows + st.prefetched_rows
    n_batches = max(es.n_batches, 1)
    emit(f"emb_host/{tag}/host", dt / n * 1e6,
         f"hit_rate={es.emb_cache_hit_rate:.3f},resolved={resolved},"
         f"h2d_per_batch={st.h2d_bytes // n_batches}B,"
         f"p50={es.p50_ms:.1f}ms,p99={es.p99_ms:.1f}ms,"
         f"overflows={st.staging_overflows},out_of_hbm={out_of_hbm}")
    return {
        "structural": {
            "rows": int(store.spec.rows),
            "capacity": int(store.capacity),
            "staging_capacity": int(store.staging_capacity),
            "hit_rate": round(float(es.emb_cache_hit_rate), 6),
            "resolved_rows": int(resolved),
            "refreshes": int(st.refreshes),
            "overflows": int(st.staging_overflows),
            "device_bytes": int(dev_bytes),
            "budget_bytes": int(budget),
            "out_of_hbm": bool(out_of_hbm),
            "row_dtype": row_dtype or "fp32",
            "wire_row_bytes": int(store.wire_row_bytes),
            "gather_bytes": int(st.gather_bytes),
            "resolved_wire_bytes": int(resolved * store.wire_row_bytes),
            "bit_exact": row_dtype is None,       # the assert above gates us
        },
        "timing": {
            "us_per_req": dt / n * 1e6,
            "p50_ms": float(es.p50_ms),
            "p99_ms": float(es.p99_ms),
            "h2d_bytes": int(st.h2d_bytes),
            "staged_rows": int(st.staged_rows),
            "prefetched_rows": int(st.prefetched_rows),
        },
    }


def run(quick: bool = False, dry: bool = False) -> dict:
    if dry:
        n, batch = 48, 8
        vocabs, capacities, exponents = [2_000], [64], [1.05, 1.3]
    elif quick:
        n, batch = 200, 16
        vocabs, capacities = [20_000], [256, 2_048]
        exponents = [1.05, 1.3]
    else:
        n, batch = 1_000, 64
        vocabs, capacities = [100_000, 1_000_000], [4_096, 65_536]
        exponents = [1.05, 1.2, 1.4]
    out = {}
    for vocab in vocabs:
        for cap in capacities:
            for e in exponents:
                tag = f"V{vocab}/C{cap}/zipf{e}"
                out[f"V{vocab}_C{cap}_zipf{e}"] = _cell(
                    vocab, cap, e, n, batch, tag)

    # quantized wire-format pair: the same stream and capacity served at
    # d=32 with fp32 rows (128 B/row) vs int8+scale rows (36 B/row). Both
    # cells resolve the identical row set (tier choice is value-blind), so
    # the bytes-moved counters must show exactly 128/36 ~ 3.56x; the >=3.5x
    # floor is the acceptance contract, hard-asserted here.
    pv, pc, pe = vocabs[0], capacities[0], exponents[-1]
    pair = {}
    for rd in (None, "int8"):
        mode = rd or "fp32"
        pair[mode] = _cell(pv, pc, pe, n, batch,
                           f"V{pv}/C{pc}/zipf{pe}/d32/{mode}",
                           dim=32, row_dtype=rd)
        out[f"q8_pair_d32_{mode}"] = pair[mode]
    ratios = {}
    for key in ("gather_bytes", "resolved_wire_bytes"):
        f32b = pair["fp32"]["structural"][key]
        q8b = pair["int8"]["structural"][key]
        assert f32b / q8b >= 3.5, (key, f32b, q8b)
        ratios[f"{key}_ratio"] = round(f32b / q8b, 6)
    out["q8_pair_d32"] = {"structural": ratios}
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick, dry=args.dry)
