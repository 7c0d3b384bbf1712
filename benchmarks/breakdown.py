"""Paper Fig. 8 — component-wise breakdown on Criteo.

  PyTorch-A   naive + per-field host transfer & dtype-conversion overhead
  PyTorch-B   consolidated transfer/conversion, still serial + eager
  DPIFrame-A  + fused multi-table embedding (C2/C3)
  DPIFrame-B  + non-GEMM operator fusion (C5)
  DPIFrame-C  + breadth-first inter-module schedule, whole-graph program (C4)
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import ctr_spec
from repro.core import compile_plan
from repro.data.synthetic import CRITEO, synthetic_batch
from repro.models.ctr import CTR_MODELS

from .common import emit, time_fn

BATCH = 2048
MAX_FIELD = 100_000

LEVEL_OF = {"pytorch_b": "naive", "dpiframe_a": "fused_emb",
            "dpiframe_b": "fused_all", "dpiframe_c": "dual"}


def run(quick: bool = False) -> dict:
    schema = CRITEO.scaled(MAX_FIELD)
    batch = synthetic_batch(schema, 0, BATCH)
    ids = batch["ids"]
    # PyTorch-A's extra sin: fields arrive as separate float32 columns and
    # are converted + stacked per inference call
    float_cols = [np.asarray(ids[:, i], dtype=np.float32)
                  for i in range(schema.k)]
    results = {}
    # the paper's four models (DLRM-DCNv2's rows hold numeric counts too)
    for model_name in (["dcnv2"] if quick else
                       ["dcn", "dcnv2", "widedeep", "deepfm"]):
        spec = ctr_spec(model_name, "criteo", 16, 256, max_field=MAX_FIELD)
        model = CTR_MODELS[model_name](spec)
        params = model.init(jax.random.PRNGKey(0))
        times = {}
        # PyTorch-A: per-field conversion + naive eager execution
        plan_naive = compile_plan(model, params, "naive", BATCH)

        def pytorch_a(cols):
            converted = [jnp.asarray(c).astype(jnp.int32) for c in cols]
            return plan_naive.step(jnp.stack(converted, axis=1))

        times["pytorch_a"] = time_fn(pytorch_a, float_cols, reps=3, warmup=1)
        for tag, level in LEVEL_OF.items():
            plan = compile_plan(model, params, level, BATCH)
            times[tag] = time_fn(plan.step, ids, reps=3, warmup=1)
        base = times["pytorch_a"]
        for tag, t in times.items():
            emit(f"breakdown/{model_name}/{tag}", t,
                 f"speedup_vs_pytorch_a={base/t:.2f}x")
        results[model_name] = times
    return results


if __name__ == "__main__":
    run()
