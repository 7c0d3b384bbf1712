#!/usr/bin/env python3
"""Chip smoke test: serve DCNv2 at the paper's widths on one TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the vocab-parallel mesh phase

One chip runs four phases, each through the entry points a user calls
(``ServingRuntime`` -> ``InferenceEngine`` -> compiled plans), except the
first:

  kernels      every main-path Pallas kernel once at small shapes against
               its jnp twin, on the chip;
  dense        ``dcnv2`` on the full synthetic Criteo schema (39 fields,
               6,648,547 rows, d=32, hidden 3x1024, 3 cross layers) with the
               default fp32 ``DenseStore``, scores checked against a plain
               float32 forward written from the params dict;
  cached_int8  the same model through an int8 ``CachedStore`` and int8
               dense layers, under the int8 parity gate;
  host         the same model through a ``HostBackedStore`` (backing in
               host memory, misses staged per batch), same gate; its
               scores must also equal the dense phase's bitwise.

``--chips 4`` runs only the mesh phase: a ``CachedStore`` served through
``ServingRuntime(mesh=data=2,model=2)``, compared with the same requests
on a one-device plan and with the reference.

The script exits non-zero, printing no result, unless JAX's first device
is a TPU; it never picks a platform itself. Weights are random, made from
``--seed``. Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: bucket ladder and request count: 700 = 512 + 128 + a partial batch
BUCKETS = (128, 512)
N_REQUESTS = 700
#: fp32 plans vs the HIGHEST-precision reference. The plan's f32 matmuls
#: run at the chip's default precision, one bf16 pass (operands rounded to
#: 8 mantissa bits). Emulating exactly that at these widths (operands cast
#: to bf16, f32 accumulation) moved the 700 scores by at most 1.2e-4, and
#: the chip read 7.1e-5. Serving int8 rows instead (the cached_int8 phase)
#: read 3.8e-4: the limit sits between, so a store that silently lost
#: precision fails
FP32_TOL = 2.5e-4
#: the repo's int8 parity gate on |Δscore| (accuracy_parity.py)
INT8_TOL = 1e-2
CACHE_ROWS = 65_536
STAGING_ROWS = 16_384


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# reference: DCNv2 written from the paper's equations, float32, HIGHEST
# ---------------------------------------------------------------------------

def reference_scores(params: dict, ids, offsets, dim: int):
    """Plain float32 DCNv2 forward from the params dict (``jnp.take`` on
    the table, Wang et al. 2021 eq. (1) cross layers, ReLU MLP, linear
    head), every matmul at ``precision=HIGHEST``. Returns sigmoid scores."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    # the DenseStore keeps its (rows, d) table packed as 128-lane lines in
    # row-major order, so a reshape recovers the rows
    table = params["emb"]["mega_table"].reshape(-1, dim)

    # the table is an argument: closed over, it would be compiled in as a
    # 0.79 GiB constant
    @jax.jit
    def forward(table, ids):
        rows = ids + offsets[None, :]
        x0 = jnp.take(table, rows, axis=0).reshape(ids.shape[0], -1)
        x = x0
        for layer in params["cross"]:
            x = x0 * (jnp.dot(x, layer["w"], precision=hi) + layer["b"]) + x
        h = x0
        for layer in params["mlp"]:
            h = jnp.maximum(jnp.dot(h, layer["w"], precision=hi)
                            + layer["b"], 0.0)
        z = jnp.concatenate([x, h], axis=1)
        logit = jnp.dot(z, params["head"]["w"], precision=hi) \
            + params["head"]["b"]
        return jax.nn.sigmoid(logit[:, 0])

    import numpy as np
    out = [np.asarray(forward(table, jnp.asarray(ids[i:i + 512])))
           for i in range(0, len(ids), 512)]
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kernels(seed: int) -> None:
    """Every main-path Pallas kernel once at small shapes, on the chip,
    against its jnp twin: the gathers bitwise (one-hot) or to 1e-6
    (pooled: the kernel and the twin sum slots in different orders), the
    dense kernels to their test tolerances."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro import quant
    from repro.kernels import ops, ref
    from repro.kernels.multi_table_lookup import pack_rows

    rng = np.random.default_rng(seed)
    worst = 0.0
    for d in (16, 32):
        sizes = [3000, 700, 41, 1200]
        n = sum(sizes) + 1
        offsets = jnp.asarray(np.cumsum([0] + sizes[:-1]), jnp.int32)
        table = rng.normal(size=(n, d)).astype(np.float32) * 0.05
        table[-1] = 0.0
        q, qs = quant.quantize_rows(table)
        q, qs = np.asarray(q), np.asarray(qs)
        hot_rows = rng.choice(n, size=512, replace=False)
        cmap = np.full(n, -1, np.int32)
        cmap[hot_rows] = np.arange(512, dtype=np.int32)
        warm = np.setdiff1d(np.arange(n), hot_rows)[:2000]
        smap = np.full(n, -1, np.int32)
        smap[warm] = np.arange(warm.size, dtype=np.int32)
        fp = dict(lines=pack_rows(jnp.asarray(table)),
                  cache=pack_rows(jnp.asarray(table[hot_rows])),
                  staging=pack_rows(jnp.asarray(table[warm])))
        i8 = dict(lines=pack_rows(jnp.asarray(q)),
                  cache=pack_rows(jnp.asarray(q[hot_rows])),
                  staging=pack_rows(jnp.asarray(q[warm])))
        cs, bs, ss = (jnp.asarray(qs[hot_rows]), jnp.asarray(qs),
                      jnp.asarray(qs[warm]))
        cm, sm = jnp.asarray(cmap), jnp.asarray(smap)
        for hot in (1, 3):
            b = 1024 // hot
            ids = jnp.asarray(np.stack(
                [rng.integers(0, s, size=(b, hot)) for s in sizes], axis=1),
                jnp.int32)
            mask = jnp.asarray(rng.integers(0, 2, size=ids.shape),
                               jnp.float32)
            one = ids[:, :, 0]
            cases = {
                "dense": lambda s: ops.multi_table_lookup(
                    one, fp["lines"], offsets, strategy=s, dim=d)
                if hot == 1 else ops.multi_table_lookup_multihot(
                    ids, mask, fp["lines"], offsets, strategy=s, dim=d),
                "two_level": lambda s: ops.multi_table_lookup_cached(
                    one, fp["cache"], fp["lines"], cm, offsets,
                    strategy=s, dim=d)
                if hot == 1 else ops.multi_table_lookup_cached_multihot(
                    ids, mask, fp["cache"], fp["lines"], cm, offsets,
                    strategy=s, dim=d),
                "two_level_q8": lambda s: ops.multi_table_lookup_cached_q8(
                    one, i8["cache"], cs, i8["lines"], bs, cm, offsets,
                    strategy=s, dim=d)
                if hot == 1 else ops.multi_table_lookup_cached_q8_multihot(
                    ids, mask, i8["cache"], cs, i8["lines"], bs, cm,
                    offsets, strategy=s, dim=d),
                "three_level": lambda s: ops.multi_table_lookup_host(
                    one, fp["cache"], fp["staging"], cm, sm, offsets,
                    strategy=s, dim=d)
                if hot == 1 else ops.multi_table_lookup_host_multihot(
                    ids, mask, fp["cache"], fp["staging"], cm, sm, offsets,
                    strategy=s, dim=d),
                "three_level_q8": lambda s: ops.multi_table_lookup_host_q8(
                    one, i8["cache"], cs, i8["staging"], ss, cm, sm,
                    offsets, strategy=s, dim=d)
                if hot == 1 else ops.multi_table_lookup_host_q8_multihot(
                    ids, mask, i8["cache"], cs, i8["staging"], ss, cm, sm,
                    offsets, strategy=s, dim=d),
            }
            for name, run in cases.items():
                got = np.asarray(run("pallas"))
                want = np.asarray(run("jnp"))
                diff = float(np.abs(got - want).max())
                ok = (np.array_equal(got, want) if hot == 1
                      else diff <= 1e-6)
                log(f"[kernels] {name:15s} d={d} hot={hot} "
                    f"max|Δ|={diff:.3g} {'ok' if ok else 'FAIL'}")
                if not ok:
                    raise AssertionError(f"{name} d={d} hot={hot}: pallas "
                                         f"vs jnp max|Δ| {diff}")
                worst = max(worst, diff)

    ip = not ops.on_tpu()          # interpret only in a CPU rehearsal
    x0 = jnp.asarray(rng.normal(size=(1024, 1248)), jnp.float32)
    xw = jnp.asarray(rng.normal(size=(1024, 1248)), jnp.float32)
    checks = {
        "fused_cross_v2": (ops.fused_cross_v2(x0, xw, x0, interpret=ip),
                           ref.ref_cross_v2_elementwise(x0, xw, x0)),
        "fused_cross_v1": (
            ops.fused_cross_v1(x0, xw[:, :1], xw[0], x0, interpret=ip),
            ref.ref_cross_v1_elementwise(x0, xw[:, :1], xw[0], x0)),
        "fused_fm_second_order": (
            ops.fused_fm_second_order(x0.reshape(1024, 39, 32),
                                      interpret=ip)[:, 0],
            ref.ref_fm_second_order(x0.reshape(1024, 39, 32))),
    }
    w = rng.normal(size=(1248, 1024)).astype(np.float32) / 35.0
    wq, wscale = quant.quantize_channels(jnp.asarray(w))
    bias = jnp.zeros((1024,), jnp.float32)
    checks["dense_matmul_q8"] = (
        ops.dense_matmul_q8(x0, wq, wscale, bias, strategy="pallas"),
        ops.dense_matmul_q8(x0, wq, wscale, bias, strategy="jnp"))
    for name, (got, want) in checks.items():
        got, want = np.asarray(got), np.asarray(want)
        diff = float(np.abs(got - want).max())
        ok = np.allclose(got, want, rtol=1e-5, atol=1e-4)
        log(f"[kernels] {name:22s} max|Δ|={diff:.3g} "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            raise AssertionError(f"{name}: pallas vs jnp max|Δ| {diff}")


def _device_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _has_kernel(executable) -> bool:
    """True when a compiled step runs a Pallas (Mosaic) kernel."""
    return "tpu_custom_call" in executable.as_text()


def _collectives(hlo: str) -> tuple[dict, int]:
    """Collective op counts of a compiled step's HLO text, and the byte
    size of its largest all-gather result."""
    import re
    counts: dict = {}
    largest = 0
    nbytes = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "s8": 1, "pred": 1}
    for line in hlo.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\][^ ]* (all-gather|all-reduce|"
                      r"reduce-scatter|all-to-all|collective-permute)"
                      r"(-start)?\(", line)
        if not m:
            continue
        counts[m.group(3)] = counts.get(m.group(3), 0) + 1
        if m.group(3) == "all-gather":
            n = 1
            for dim in filter(None, m.group(2).split(",")):
                n *= int(dim)
            largest = max(largest, n * nbytes.get(m.group(1), 4))
    return counts, largest


def serve_phase(name: str, *, model, params, ids, ref, tol: float,
                mesh=None, **engine_kwargs) -> dict:
    """Serve ``ids`` through a ``ServingRuntime`` the way
    ``repro.launch.serve --async`` does and check the futures' scores
    against ``ref``. Returns the phase's numbers."""
    import numpy as np
    from repro.serving import BucketedBatch, ServingRuntime

    rt = ServingRuntime(mesh=mesh)
    eng = rt.add_model("dcnv2", model, params,
                       policy=BucketedBatch(BUCKETS), **engine_kwargs)
    try:
        t0 = time.perf_counter()
        rt.warmup()
        warm_s = time.perf_counter() - t0
        rt.start()
        futs = [rt.submit("dcnv2", row) for row in ids]
        scores = np.array([f.result(timeout=600.0) for f in futs])
    finally:
        rt.stop()
    st = rt.stats()
    prefetch_errors = 0
    if engine_kwargs.get("store") is not None and \
            hasattr(engine_kwargs["store"], "pipeline"):
        prefetch_errors = engine_kwargs["store"].pipeline.n_errors
    compile_s = {b: round(ms / 1e3, 3) for b, ms in
                 eng.stats.compile_ms_per_bucket.items()}
    kernel = {b: _has_kernel(eng.plan_for(b).executable) for b in BUCKETS}
    top = eng.plan_for(BUCKETS[-1]).executable
    ma = top.memory_analysis()
    collectives, largest_ag = _collectives(top.as_text())
    diff = np.abs(scores - ref)
    out = dict(phase=name, requests=st.n_requests, batches=st.n_batches,
               served=int(np.isfinite(scores).sum()),
               max_abs_dscore=float(diff.max()), tol=tol,
               compile_s=compile_s, warmup_s=round(warm_s, 3),
               tpu_custom_call=kernel,
               memory_analysis=dict(
                   argument_bytes=ma.argument_size_in_bytes,
                   output_bytes=ma.output_size_in_bytes,
                   temp_bytes=ma.temp_size_in_bytes,
                   generated_code_bytes=ma.generated_code_size_in_bytes),
               peak_bytes_in_use=_device_peak(),
               collectives=collectives,
               largest_all_gather_bytes=largest_ag,
               n_worker_errors=st.n_worker_errors,
               prefetch_errors=prefetch_errors)
    log(f"[{name}] " + json.dumps(out))
    problems = []
    if out["served"] != len(ids):
        problems.append(f"served {out['served']} of {len(ids)} requests")
    if not all(kernel.values()):
        problems.append("a compiled step has no tpu_custom_call (the jnp "
                        "twins ran instead of the Pallas kernels)")
    if st.n_worker_errors or prefetch_errors:
        problems.append(f"{st.n_worker_errors} worker errors, "
                        f"{prefetch_errors} prefetch errors")
    if not out["max_abs_dscore"] <= tol:
        problems.append(f"max |Δscore| {out['max_abs_dscore']} > {tol}")
    if problems:
        raise AssertionError(f"[{name}] " + "; ".join(problems))
    out["scores"] = scores
    return out


def build():
    """``dcnv2`` at the widest point of the paper's §V-A grid on the full
    synthetic Criteo schema (no ``max_field``)."""
    from repro.configs import ctr_spec
    from repro.models.ctr import CTR_MODELS
    spec = ctr_spec("dcnv2", "criteo", 32, 1024)
    return spec, CTR_MODELS["dcnv2"](spec)


def traffic(spec, seed: int):
    import numpy as np
    import jax
    from repro.data.synthetic import zipf_ids
    return np.asarray(zipf_ids(jax.random.PRNGKey(seed + 1), N_REQUESTS,
                               spec.field_sizes, exponent=1.1))


def run_one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.embedding import CachedStore, HostBackedStore

    t0 = time.perf_counter()
    phase_kernels(seed)
    log(f"[kernels] done in {time.perf_counter() - t0:.1f}s")

    spec, model = build()
    params = model.init(jax.random.PRNGKey(seed))
    ids = traffic(spec, seed)
    offsets = jnp.asarray(spec.embedding_spec().offsets)
    ref = reference_scores(params, ids, offsets, spec.embed_dim)
    log(f"[reference] {len(ref)} scores, mean {ref.mean():.6f}")
    dense = serve_phase("dense", model=model, params=params, ids=ids,
                        ref=ref, tol=FP32_TOL)

    espec = spec.embedding_spec()
    for name, store, kw in (
            ("cached_int8",
             CachedStore(espec, capacity=CACHE_ROWS, row_dtype="int8"),
             dict(compute_dtype="int8")),
            ("host",
             HostBackedStore(espec, capacity=CACHE_ROWS,
                             staging_capacity=STAGING_ROWS), {})):
        gc.collect()
        _, model_i = build()
        out = serve_phase(name, model=model_i, params=params, ids=ids,
                          ref=ref, tol=INT8_TOL, store=store, **kw)
        vs_dense = float(abs(out["scores"] - dense["scores"]).max())
        log(f"[{name}] max |Δscore| vs the dense phase: {vs_dense:.3g}")
        # the host tiers hold verbatim fp32 rows and the gathers are
        # bit-exact across tiers: its scores equal the dense phase's
        if name == "host" and vs_dense != 0.0:
            raise AssertionError(f"[host] scores differ from the dense "
                                 f"phase by up to {vs_dense}")
        del model_i, store, out


def run_mesh(seed: int) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.core.plan import compile_plan
    from repro.embedding import CachedStore

    if len(jax.devices()) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    spec, model = build()
    params = model.init(jax.random.PRNGKey(seed))
    ids = traffic(spec, seed)
    offsets = jnp.asarray(spec.embedding_spec().offsets)
    ref = reference_scores(params, ids, offsets, spec.embed_dim)
    espec = spec.embedding_spec()

    # one-device plan over the same store and requests
    _, single = build()
    sparams = single.use_store(CachedStore(espec, capacity=CACHE_ROWS),
                               params)
    plan = compile_plan(single, sparams, "dual", BUCKETS[-1])
    one = np.concatenate([plan.predict(ids[i:i + BUCKETS[-1]])
                          for i in range(0, len(ids), BUCKETS[-1])])
    del plan, sparams, single
    gc.collect()

    mesh = make_mesh((2, 2), ("data", "model"))
    _, model_m = build()
    out = serve_phase("mesh", model=model_m, params=params, ids=ids,
                      ref=ref, tol=FP32_TOL, mesh=mesh,
                      store=CachedStore(espec, capacity=CACHE_ROWS))
    vs_one = float(np.abs(out["scores"] - one).max())
    log(f"[mesh] max |Δscore| vs one-device plan: {vs_one:.3g}; one-device "
        f"vs reference: {float(np.abs(one - ref).max()):.3g}")
    if out["largest_all_gather_bytes"] >= 64 << 20:
        raise AssertionError("[mesh] the step all-gathers "
                             f"{out['largest_all_gather_bytes']} bytes: the "
                             "row-sharded table is being replicated")
    if vs_one > 1e-5:
        raise AssertionError(f"[mesh] mesh vs one-device max |Δscore| "
                             f"{vs_one} > 1e-5")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 2
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {cache}")
    if args.chips == 4:
        run_mesh(args.seed)
    else:
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
