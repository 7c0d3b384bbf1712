"""DCN (Deep & Cross Network, Wang et al. 2017) — paper's Figure-1 example.

Explicit branch: cross network v1,  x_{l+1} = x0 · (x_l ⊤ w_l) + b_l + x_l
(the (x_l·w_l) contraction is the GEMM; the remaining elementwise chain is
the non-GEMM tail that C5 fuses — per-layer Pallas kernel fused_cross_v1).
Implicit branch: deep MLP. Head: concat → linear → logit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import Op, OpGraph
from repro.core.opgraph import register_fused_kernel
from repro.kernels import ops as kops
from repro.kernels import ref as kref

from .common import (CTRModel, CTRModelSpec, emit_embedding_ops, emit_mlp_ops,
                     init_dense, mlp_init)


class DCN(CTRModel):
    def init(self, key: jax.Array) -> dict:
        spec = self.spec
        dtype = jnp.dtype(spec.dtype)
        keys = jax.random.split(key, 4 + spec.cross_layers)
        d_in = spec.input_dim
        params: dict = {
            "emb": self.embedding.init(keys[0]),
            "mlp": mlp_init(keys[1], (d_in, *spec.hidden), dtype),
            "head": init_dense(keys[2], d_in + spec.hidden[-1], 1, dtype),
        }
        cross = []
        for li in range(spec.cross_layers):
            kw = keys[3 + li]
            cross.append({
                "w": jax.random.normal(kw, (d_in, 1), dtype=dtype)
                     * (1.0 / jnp.sqrt(d_in)),
                "b": jnp.zeros((d_in,), dtype=dtype),
            })
        params["cross"] = cross
        return params

    def build_graph(self, params: dict, level: str,
                    compute_dtype: str = "fp32") -> OpGraph:
        g = OpGraph(["ids"])
        emit_embedding_ops(g, self.embedding, params, level)

        # explicit: cross network v1
        cur = "x_embed"
        n_layers = len(params["cross"])
        for li, layer in enumerate(params["cross"]):
            w, b = layer["w"], layer["b"]
            g.add(Op(f"cross_gemm{li}", lambda x, _w=w: x @ _w,
                     (cur,), f"xlw{li}", is_gemm=True, module="explicit"))
            hint = f"dcn_v1_tail_{id(self)}_{li}"
            register_fused_kernel(hint, _make_v1_kernel(b, first=(li == 0)))
            out_edge = ("explicit_out" if li == n_layers - 1
                        else f"x_cross{li}")
            g.add(Op(f"cross_mul{li}",
                     lambda x0, xlw: x0 * xlw,
                     ("x_embed", f"xlw{li}"), f"cm{li}",
                     module="explicit", fused_hint=hint))
            g.add(Op(f"cross_addres{li}",
                     lambda m, x, _b=b: m + _b[None, :] + x,
                     (f"cm{li}", cur), out_edge,
                     module="explicit", fused_hint=hint))
            cur = out_edge

        # implicit: deep MLP
        deep_out = emit_mlp_ops(g, params["mlp"], "x_embed", "implicit",
                                prefix="deep", final_act=True,
                                compute_dtype=compute_dtype)

        # head
        hw, hb = params["head"]["w"], params["head"]["b"]
        g.add(Op("head_concat",
                 lambda a, b_: jnp.concatenate([a, b_], axis=1),
                 ("explicit_out", deep_out), "stacked", module="head"))
        g.add(Op("head_gemm", lambda h: h @ hw + hb, ("stacked",),
                 "logit", is_gemm=True, module="head"))
        return g


def _make_v1_kernel(bias, first: bool):
    """Per-layer closure (bias is a parameter, not a graph edge).

    Composed-subgraph signature after fusion: layer 0 receives (x0, xlw)
    because x_l == x0 is deduplicated; later layers receive (x0, xlw, x_l).
    """
    def f(x0, xlw, x=None):
        if x is None:
            x = x0
        if kops.use_pallas():
            return kops.fused_cross_v1(x0, xlw, bias, x)
        return kref.ref_cross_v1_elementwise(x0, xlw, bias, x)
    return f
