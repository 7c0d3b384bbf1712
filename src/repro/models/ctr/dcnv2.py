"""DCNv2 (Wang et al. 2021): cross network with full-matrix projection.

Explicit branch: x_{l+1} = x0 ⊙ (W_l x_l + b_l) + x_l — the W_l GEMM feeds
the elementwise tail fused by C5 into the ``cross_v2_tail`` Pallas kernel
(bias lives inside the GEMM op, so one global hint serves every layer).
Implicit branch: deep MLP. Head: concat → linear → logit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import Op, OpGraph

from .common import (CTRModel, emit_cross_v2_ops, emit_embedding_ops,
                     emit_mlp_ops, init_dense, mlp_init)


class DCNv2(CTRModel):
    def init(self, key: jax.Array) -> dict:
        spec = self.spec
        dtype = jnp.dtype(spec.dtype)
        keys = jax.random.split(key, 3 + spec.cross_layers)
        d_in = spec.input_dim
        params: dict = {
            "emb": self.embedding.init(keys[0]),
            "mlp": mlp_init(keys[1], (d_in, *spec.hidden), dtype),
            "head": init_dense(keys[2], d_in + spec.hidden[-1], 1, dtype),
            "cross": [init_dense(keys[3 + li], d_in, d_in, dtype)
                      for li in range(spec.cross_layers)],
        }
        return params

    def build_graph(self, params: dict, level: str,
                    compute_dtype: str = "fp32") -> OpGraph:
        g = OpGraph(["ids"])
        emit_embedding_ops(g, self.embedding, params, level)

        # explicit: cross network v2
        p = self.spec.matmul_precision
        emit_cross_v2_ops(g, params["cross"], "x_embed", precision=p)

        # implicit: deep MLP
        deep_out = emit_mlp_ops(g, params["mlp"], "x_embed", "implicit",
                                prefix="deep", final_act=True,
                                compute_dtype=compute_dtype, precision=p)

        # head
        hw, hb = params["head"]["w"], params["head"]["b"]
        g.add(Op("head_concat",
                 lambda a, b_: jnp.concatenate([a, b_], axis=1),
                 ("explicit_out", deep_out), "stacked", module="head"))
        g.add(Op("head_gemm",
                 lambda h: jnp.matmul(h, hw, precision=p) + hb, ("stacked",),
                 "logit", is_gemm=True, module="head"))
        return g
