"""Wide&Deep (Cheng et al. 2016).

Explicit (wide) branch: per-field linear weights — a d=1 fused lookup plus a
reduce-sum (pure embedding work, which is why the paper sees its largest
speedups here). Implicit branch: deep MLP. Head: wide_logit + deep_logit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import FusedEmbeddingCollection, Op, OpGraph

from .common import (CTRModel, emit_embedding_ops, emit_mlp_ops, init_dense,
                     mlp_init)


class WideDeep(CTRModel):
    def __init__(self, spec, store=None):
        super().__init__(spec, store=store)
        # wide d=1 tables are tiny — always dense, never worth tiering
        self.wide_embedding = FusedEmbeddingCollection(spec.wide_spec())

    def init(self, key: jax.Array) -> dict:
        spec = self.spec
        dtype = jnp.dtype(spec.dtype)
        keys = jax.random.split(key, 4)
        return {
            "emb": self.embedding.init(keys[0]),
            "wide": self.wide_embedding.init(keys[1]),
            "wide_bias": jnp.zeros((1,), dtype=dtype),
            "mlp": mlp_init(keys[2], (spec.input_dim, *spec.hidden), dtype),
            "deep_head": init_dense(keys[3], spec.hidden[-1], 1, dtype),
        }

    def embedding_collections(self) -> dict:
        return {self.main_embedding_key: self.embedding,
                "wide": self.wide_embedding}

    def build_graph(self, params: dict, level: str,
                    compute_dtype: str = "fp32") -> OpGraph:
        g = OpGraph(["ids"])
        emit_embedding_ops(g, self.embedding, params, level)

        # explicit (wide): d=1 lookup + sum — entirely embedding-style work.
        # naive level keeps it per-field; fused levels use the mega-table.
        wb = params["wide_bias"]
        if level == "naive":
            offs = self.wide_embedding.spec.offsets
            k = self.spec.k
            wide_table = self.wide_embedding.dense_view(params["wide"])
            for i in range(k):
                g.add(Op(f"wide_lookup_{i}",
                         lambda ids, _i=i, _o=int(offs[i]):
                             jnp.take(wide_table, ids[:, _i] + _o, axis=0),
                         ("ids",), f"wide_f{i}", module="explicit"))
            g.add(Op("wide_concat",
                     lambda *cols: jnp.concatenate(cols, axis=1),
                     tuple(f"wide_f{i}" for i in range(k)),
                     "wide_terms", module="explicit"))
        else:
            g.add(Op("wide_fused",
                     lambda ids: self.wide_embedding.apply(params["wide"],
                                                           ids),
                     ("ids",), "wide_terms", module="explicit"))
        g.add(Op("wide_sum",
                 lambda t, _b=wb: jnp.sum(t, axis=1, keepdims=True) + _b,
                 ("wide_terms",), "explicit_out", module="explicit"))

        # implicit: deep MLP + its own head GEMM to a logit
        p = self.spec.matmul_precision
        deep_out = emit_mlp_ops(g, params["mlp"], "x_embed", "implicit",
                                prefix="deep", final_act=True,
                                compute_dtype=compute_dtype, precision=p)
        hw, hb = params["deep_head"]["w"], params["deep_head"]["b"]
        g.add(Op("deep_head",
                 lambda h: jnp.matmul(h, hw, precision=p) + hb, (deep_out,),
                 "implicit_out", is_gemm=True, module="implicit"))

        # head: sum of branch logits
        g.add(Op("head_add", lambda a, b: a + b,
                 ("explicit_out", "implicit_out"), "logit", module="head"))
        return g
