"""DeepFM (Guo et al. 2017).

Explicit branch: factorization machine — first-order d=1 lookup-sum plus the
second-order term 0.5·Σ_d[(Σ_k v)²−Σ_k v²] emitted as a fine-grained
non-GEMM chain (square/sum/sub/scale) that C5 fuses into the fused_fm
Pallas kernel. Implicit branch: deep MLP sharing the same embeddings.
Head: fm_linear + fm_second + deep_logit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import FusedEmbeddingCollection, Op, OpGraph

from .common import (CTRModel, emit_embedding_ops, emit_mlp_ops, init_dense,
                     mlp_init)


class DeepFM(CTRModel):
    def __init__(self, spec, store=None):
        super().__init__(spec, store=store)
        # FM first-order d=1 tables are tiny — always dense
        self.wide_embedding = FusedEmbeddingCollection(spec.wide_spec())

    def init(self, key: jax.Array) -> dict:
        spec = self.spec
        dtype = jnp.dtype(spec.dtype)
        keys = jax.random.split(key, 4)
        return {
            "emb": self.embedding.init(keys[0]),
            "fm_w": self.wide_embedding.init(keys[1]),
            "fm_bias": jnp.zeros((1,), dtype=dtype),
            "mlp": mlp_init(keys[2], (spec.input_dim, *spec.hidden), dtype),
            "deep_head": init_dense(keys[3], spec.hidden[-1], 1, dtype),
        }

    def embedding_collections(self) -> dict:
        return {self.main_embedding_key: self.embedding,
                "fm_w": self.wide_embedding}

    def build_graph(self, params: dict, level: str,
                    compute_dtype: str = "fp32") -> OpGraph:
        spec = self.spec
        g = OpGraph(["ids"])
        emit_embedding_ops(g, self.embedding, params, level)

        # explicit (FM): first-order linear term
        fb = params["fm_bias"]
        g.add(Op("fm_lin_lookup",
                 lambda ids: self.wide_embedding.apply(params["fm_w"], ids),
                 ("ids",), "fm_lin_terms", module="explicit"))
        g.add(Op("fm_lin_sum",
                 lambda t, _b=fb: jnp.sum(t, axis=1, keepdims=True) + _b,
                 ("fm_lin_terms",), "fm_linear", module="explicit"))

        # second-order term as a fine-grained non-GEMM chain (fused by C5
        # into the fused_fm Pallas kernel — all ops share one hint)
        k, d = spec.k, spec.embed_dim
        # (reshape is deliberately *not* hinted: the fused_fm kernel's
        # signature is (b, k, d), so the hinted group starts at fm_sum_k)
        g.add(Op("fm_reshape",
                 lambda x: x.reshape(x.shape[0], k, d),
                 ("x_embed",), "v", module="explicit"))
        g.add(Op("fm_sum_k", lambda v: jnp.sum(v, axis=1),
                 ("v",), "s", module="explicit",
                 fused_hint="fm_second_order"))
        g.add(Op("fm_sq_s", lambda s: s * s, ("s",), "ss",
                 module="explicit", fused_hint="fm_second_order"))
        g.add(Op("fm_sq_v", lambda v: v * v, ("v",), "v2",
                 module="explicit", fused_hint="fm_second_order"))
        g.add(Op("fm_sum_v2", lambda v2: jnp.sum(v2, axis=1),
                 ("v2",), "sv2", module="explicit",
                 fused_hint="fm_second_order"))
        g.add(Op("fm_final",
                 lambda ss, sv2: 0.5 * jnp.sum(ss - sv2, axis=-1,
                                               keepdims=True),
                 ("ss", "sv2"), "fm_second", module="explicit",
                 fused_hint="fm_second_order"))
        g.add(Op("fm_add", lambda a, b: a + b, ("fm_linear", "fm_second"),
                 "explicit_out", module="explicit"))

        # implicit: deep MLP
        p = self.spec.matmul_precision
        deep_out = emit_mlp_ops(g, params["mlp"], "x_embed", "implicit",
                                prefix="deep", final_act=True,
                                compute_dtype=compute_dtype, precision=p)
        hw, hb = params["deep_head"]["w"], params["deep_head"]["b"]
        g.add(Op("deep_head",
                 lambda h: jnp.matmul(h, hw, precision=p) + hb, (deep_out,),
                 "implicit_out", is_gemm=True, module="implicit"))

        # head
        g.add(Op("head_add", lambda a, b: a + b,
                 ("explicit_out", "implicit_out"), "logit", module="head"))
        return g
