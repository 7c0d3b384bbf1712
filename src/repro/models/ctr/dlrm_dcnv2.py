"""DLRM-DCNv2 (MLPerf's ``dlrm_v2`` recommendation model; DCN V2,
arXiv:2008.13535, for its cross).

A request row is ``[count_1 .. count_m | field 1's h_1 ids | ...]``
(``CTRModelSpec.row_width``):

    embedding    e_i = Σ_{j < h_i} E[id_ij]          pooled lookup, per field
    bottom       z = MLP_ReLU(log(count + 1))        13 -> 512 -> 256 -> 128
    interaction  x0 = [z; e_1; ...; e_k]             (b, bottom[-1] + k·d)
    explicit     x_{l+1} = x0 ⊙ (W_l (V_l x_l) + b_l) + x_l
                                                     low-rank cross, rank r
    top          logit = w_head · MLP_ReLU(x_L) + b_head

The ``embedding`` lookup and the ``bottom`` MLP depend only on the row, so
they are the graph's two parallel branches (``OpGraph.branches``): the
dual level interleaves them as it interleaves DCNv2's cross and deep
branches. ``V_l x_l`` and ``(·) W_l + b_l`` are GEMMs
(``emit_cross_v2_ops``, shared with DCNv2); the elementwise tail after
them is the ``cross_v2_tail`` fused kernel. Every product runs at
``spec.matmul_precision``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import Op, OpGraph

from .common import (CTRModel, emit_cross_v2_ops, emit_embedding_ops,
                     emit_mlp_ops, init_dense, mlp_init)


class DLRMDCNv2(CTRModel):
    wide_rows = True

    def __init__(self, spec, store=None):
        if not (spec.n_numeric and spec.bottom and spec.cross_rank):
            raise ValueError("DLRM-DCNv2 needs numeric features, bottom MLP "
                             "widths and a cross rank")
        super().__init__(spec, store=store)

    @property
    def cross_dim(self) -> int:
        """Width of the interaction the cross and the top MLP read."""
        return self.spec.bottom[-1] + self.spec.input_dim

    def init(self, key: jax.Array) -> dict:
        spec = self.spec
        dtype = jnp.dtype(spec.dtype)
        keys = jax.random.split(key, 4 + spec.cross_layers)
        d, r = self.cross_dim, spec.cross_rank
        cross = []
        for li in range(spec.cross_layers):
            kv, kw = jax.random.split(keys[4 + li])
            cross.append({**init_dense(kw, r, d, dtype),
                          "v": init_dense(kv, d, r, dtype)["w"]})
        return {
            "emb": self.embedding.init(keys[0]),
            "bottom": mlp_init(keys[1], (spec.n_numeric, *spec.bottom),
                               dtype),
            "cross": cross,
            "top": mlp_init(keys[2], (d, *spec.hidden), dtype),
            "head": init_dense(keys[3], spec.hidden[-1], 1, dtype),
        }

    def build_graph(self, params: dict, level: str,
                    compute_dtype: str = "fp32") -> OpGraph:
        spec = self.spec
        m, p = spec.n_numeric, spec.matmul_precision
        g = OpGraph(["ids"], branches=("embedding", "bottom"))
        emit_embedding_ops(g, self.embedding, params, level, n_numeric=m)
        g.add(Op("bottom_log1p",
                 lambda ids: jnp.log1p(ids[:, :m].astype(jnp.float32)),
                 ("ids",), "x_numeric", module="bottom"))
        z = emit_mlp_ops(g, params["bottom"], "x_numeric", "bottom",
                         prefix="bottom", final_act=True,
                         compute_dtype=compute_dtype, precision=p)
        g.add(Op("interaction_concat",
                 lambda z_, e: jnp.concatenate([z_, e], axis=1),
                 (z, "x_embed"), "x0", module="interaction"))
        cur = emit_cross_v2_ops(g, params["cross"], "x0", precision=p)
        top = emit_mlp_ops(g, params["top"], cur, "top", prefix="top",
                           final_act=True, compute_dtype=compute_dtype,
                           precision=p)
        hw, hb = params["head"]["w"], params["head"]["b"]
        g.add(Op("head_gemm",
                 lambda h: jnp.matmul(h, hw, precision=p) + hb, (top,),
                 "logit", is_gemm=True, module="top"))
        return g
