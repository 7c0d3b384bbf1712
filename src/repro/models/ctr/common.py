"""Shared pieces of the CTR model zoo: inits, MLP op emission, kernel hooks.

Every model exposes the same interface (``CTRModel``):

  spec             CTRModelSpec (embedding schema + net sizes)
  init(key)        -> params pytree
  build_graph(params, level) -> OpGraph   (consumed by DualParallelExecutor)
  apply(params, ids) -> logits (b, 1)     (differentiable forward = the
                                           graph at "dual" semantics; used
                                           by the trainer)
  loss(params, batch) -> scalar BCE

Graph modules: "embedding" -> ("explicit" ∥ "implicit") -> "head", matching
the paper's decomposition, with GEMMs flagged and non-GEMM tails carrying
``fused_hint`` so the C5 pass can swap in the Pallas kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import FusedEmbeddingCollection, FusedEmbeddingSpec, Op, OpGraph
from repro.core.opgraph import register_fused_kernel
from repro.embedding import runtime_edge
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.quant import quantize_channels

__all__ = ["CTRModelSpec", "CTRModel", "init_dense", "mlp_init",
           "emit_embedding_ops", "emit_mlp_ops", "bce_loss"]


@dataclasses.dataclass(frozen=True)
class CTRModelSpec:
    """Static CTR model description (paper §V-A configuration space)."""
    name: str
    field_sizes: tuple[int, ...]
    embed_dim: int = 16                      # paper: 16 / 32
    hidden: tuple[int, ...] = (256, 256, 256)  # paper: 256/512/1024 ×3
    cross_layers: int = 3                    # paper: 3 (DCN/DCNv2)
    dtype: str = "float32"

    @property
    def k(self) -> int:
        return len(self.field_sizes)

    @property
    def input_dim(self) -> int:
        return self.k * self.embed_dim

    def embedding_spec(self) -> FusedEmbeddingSpec:
        return FusedEmbeddingSpec(field_sizes=self.field_sizes,
                                  dim=self.embed_dim, dtype=self.dtype)

    def wide_spec(self) -> FusedEmbeddingSpec:
        """d=1 tables for linear terms (Wide&Deep / FM first order)."""
        return FusedEmbeddingSpec(field_sizes=self.field_sizes, dim=1,
                                  dtype=self.dtype)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def init_dense(key, fan_in: int, fan_out: int, dtype) -> dict:
    scale = np.sqrt(2.0 / (fan_in + fan_out))
    w = jax.random.normal(key, (fan_in, fan_out), dtype=dtype) * scale
    return {"w": w, "b": jnp.zeros((fan_out,), dtype=dtype)}


def mlp_init(key, dims: tuple[int, ...], dtype) -> list[dict]:
    keys = jax.random.split(key, len(dims) - 1)
    return [init_dense(k, dims[i], dims[i + 1], dtype)
            for i, k in enumerate(keys)]


# ---------------------------------------------------------------------------
# graph emission helpers
# ---------------------------------------------------------------------------

def emit_embedding_ops(g: OpGraph, emb: FusedEmbeddingCollection,
                       params: dict, level: str, *, out: str = "x_embed",
                       prefix: str = "emb") -> None:
    """Embedding module ops over the store subtree at ``params[prefix]``.

    ``naive`` = k serial gathers + concat off the store's dense view (the
    baseline the paper measures against); otherwise ONE fused lookup
    through whatever tiers the store keeps (mega-table or cache+backing).

    Refreshable stores declare ``runtime_keys``: those leaves become extra
    *graph inputs* (edge names from :func:`repro.embedding.runtime_edge`)
    instead of closed-over constants, so a compiled plan keeps working
    across cache refreshes — the caller feeds the current tensors per
    step (``compile_plan`` wires this; ``CTRModel.graph_env`` builds the
    matching env for the eager/training path).
    """
    store_params = params[prefix]
    if level == "naive":
        k = emb.spec.k
        offs = emb.spec.offsets
        table = emb.dense_view(store_params)
        for i in range(k):
            def one_field(ids, _i=i, _o=int(offs[i])):
                return jnp.take(table, ids[:, _i] + _o, axis=0)
            g.add(Op(f"{prefix}_lookup_{i}", one_field, ("ids",),
                     f"{prefix}_f{i}", module="embedding"))
        g.add(Op(f"{prefix}_concat",
                 lambda *cols: jnp.concatenate(cols, axis=1),
                 tuple(f"{prefix}_f{i}" for i in range(k)),
                 out, module="embedding"))
        return
    rt = tuple(emb.store.runtime_keys)
    if rt:
        static = {k_: v for k_, v in store_params.items() if k_ not in rt}
        edges = tuple(runtime_edge(prefix, leaf) for leaf in rt)
        for e in edges:
            g.add_input(e)

        def fused_runtime(ids, *leaves):
            return emb.apply({**static, **dict(zip(rt, leaves))}, ids)

        g.add(Op(f"{prefix}_fused", fused_runtime, ("ids",) + edges,
                 out, module="embedding"))
    else:
        g.add(Op(f"{prefix}_fused",
                 lambda ids: emb.apply(store_params, ids),
                 ("ids",), out, module="embedding"))


def emit_mlp_ops(g: OpGraph, layers: list[dict], src: str, module: str,
                 prefix: str = "mlp", final_act: bool = False,
                 compute_dtype: str = "fp32") -> str:
    """Per-layer GEMM (flagged) + ReLU (non-GEMM, fusable).

    ``compute_dtype="int8"`` swaps each fp32 GEMM + ReLU pair for ONE
    fused quantized op (``kops.dense_matmul_q8``): the weight matrix is
    quantized per output channel HERE, once at graph-build time — MLP
    weights are never runtime inputs, so the baked int8 constants keep
    refresh recompile-free by construction — while activations quantize
    per row dynamically inside the op, and dequant + bias + ReLU run in
    the kernel epilogue. Structural counters land in ``g.meta`` and
    surface as the ``mlp_quant_*`` fields of ``ExecutorStats``.
    """
    if compute_dtype not in ("fp32", "int8"):
        raise ValueError(f"unknown compute_dtype {compute_dtype!r}")
    cur = src
    n = len(layers)
    for li, layer in enumerate(layers):
        w, b = layer["w"], layer["b"]
        act = li < n - 1 or final_act
        if compute_dtype == "int8":
            qw, wscale = quantize_channels(w)
            out_edge = f"{prefix}_a{li}" if act else f"{prefix}_h{li}"
            g.add(Op(f"{prefix}_q8gemm{li}",
                     lambda h, _qw=qw, _ws=wscale, _b=b, _act=act:
                         kops.dense_matmul_q8(h, _qw, _ws, _b, relu=_act),
                     (cur,), out_edge, is_gemm=True, module=module))
            cur = out_edge
            fan_in, fan_out = int(w.shape[0]), int(w.shape[1])
            # int8 payload + one fp32 scale per output channel, vs 4 B/elt
            q8_bytes = fan_in * fan_out + 4 * fan_out
            g.meta["compute_dtype"] = "int8"
            g.meta["mlp_quant_matmuls"] = \
                g.meta.get("mlp_quant_matmuls", 0) + 1
            g.meta["mlp_quant_weight_bytes"] = \
                g.meta.get("mlp_quant_weight_bytes", 0) + q8_bytes
            g.meta["mlp_quant_weight_bytes_saved"] = \
                g.meta.get("mlp_quant_weight_bytes_saved", 0) \
                + 4 * fan_in * fan_out - q8_bytes
            continue
        g.add(Op(f"{prefix}_gemm{li}",
                 lambda h, _w=w, _b=b: h @ _w + _b,
                 (cur,), f"{prefix}_h{li}", is_gemm=True, module=module))
        cur = f"{prefix}_h{li}"
        if act:
            g.add(Op(f"{prefix}_relu{li}",
                     lambda h: jnp.maximum(h, 0),
                     (cur,), f"{prefix}_a{li}", module=module,
                     fused_hint="relu"))
            cur = f"{prefix}_a{li}"
    return cur


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def bce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable binary cross entropy from logits."""
    logits = logits.reshape(-1).astype(jnp.float32)
    labels = labels.reshape(-1).astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ---------------------------------------------------------------------------
# Pallas kernel registration for the C5 pattern registry
# ---------------------------------------------------------------------------

def _dispatch(pallas_fn: Callable, jnp_fn: Callable) -> Callable:
    """Use the Pallas kernel on TPU, identical jnp math elsewhere (the CPU
    benchmarks must not time interpret mode, and GSPMD cannot partition a
    kernel inside a mesh plan)."""
    def f(*args):
        if kops.use_pallas():
            return pallas_fn(*args)
        return jnp_fn(*args)
    return f


def _cross_v2_tail(x0, xw, x=None):
    # layer 0 dedups x_l == x0 into a 2-arg call
    if x is None:
        x = x0
    if kops.use_pallas():
        return kops.fused_cross_v2(x0, xw, x)
    return kref.ref_cross_v2_elementwise(x0, xw, x)


register_fused_kernel("cross_v2_tail", _cross_v2_tail)

register_fused_kernel(
    "fm_second_order",
    _dispatch(lambda v: kops.fused_fm_second_order(v),
              lambda v: kref.ref_fm_second_order(v)[:, None]))


# ---------------------------------------------------------------------------
# model base
# ---------------------------------------------------------------------------

class CTRModel:
    """Base: shares embedding init/placement + trainer-facing apply/loss.

    The embedding path runs through ``repro.embedding``: every model keys
    its param tree with one subtree per :class:`FusedEmbeddingCollection`
    (``params["emb"]`` for the main table; wide/FM variants add their own),
    whose internal layout belongs to the collection's store. Pass
    ``store=`` (e.g. ``repro.embedding.CachedStore``) to tier the main
    table; default is the monolithic ``DenseStore``.
    """

    #: param-tree key of the main (tierable) embedding subtree — the one
    #: ``store=``/``use_store``/``refresh_cache`` operate on
    main_embedding_key = "emb"

    def __init__(self, spec: CTRModelSpec, store=None):
        self.spec = spec
        self.embedding = FusedEmbeddingCollection(spec.embedding_spec(),
                                                  store=store)

    # subclasses fill these in -------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        raise NotImplementedError

    def build_graph(self, params: dict, level: str,
                    compute_dtype: str = "fp32") -> OpGraph:
        raise NotImplementedError

    # embedding-store surface --------------------------------------------------
    def embedding_collections(self) -> dict:
        """Param-tree key -> collection, for every embedding subtree this
        model owns. Placement and store plumbing walk this — never param
        *names* (the old ``"mega" in names`` heuristic broke on renames)."""
        return {self.main_embedding_key: self.embedding}

    def partition_spec(self, params: dict, model_axis: str = "model"):
        """Mesh placement for ``params``: embedding subtrees per their
        store's ``partition_spec`` (vocab-parallel tables, replicated cache
        tiers), everything else replicated (CTR dense nets are
        latency-bound)."""
        from jax.sharding import PartitionSpec as P
        specs = jax.tree.map(lambda _: P(), params)
        for key, coll in self.embedding_collections().items():
            if key in params:
                specs[key] = coll.partition_spec(model_axis)
        return specs

    def store_runtime_env(self, params: dict) -> dict:
        """Edge name -> tensor for every runtime store input this model's
        graphs declare (see ``emit_embedding_ops``): the leaves refreshable
        stores swap at refresh time. Empty for all-dense models."""
        env = {}
        for key, coll in self.embedding_collections().items():
            sub = params.get(key)
            if sub is None:
                continue
            for leaf in coll.store.runtime_keys:
                env[runtime_edge(key, leaf)] = sub[leaf]
        return env

    def graph_env(self, params: dict, ids: jax.Array) -> dict:
        """The full input env for executing a graph built at a fused level:
        ``ids`` plus the current runtime store tensors."""
        return {"ids": ids, **self.store_runtime_env(params)}

    def use_store(self, store, params: dict) -> dict:
        """Swap the main table's store, converting its param subtree (at
        ``main_embedding_key``) into the new layout (bit-exact — see
        ``EmbeddingStore.adopt``). Returns the updated param tree; the
        model's collection is rebound."""
        self.embedding = FusedEmbeddingCollection(self.spec.embedding_spec(),
                                                  store=store)
        key = self.main_embedding_key
        return {**params, key: store.adopt(params[key])}

    # shared -------------------------------------------------------------------
    def compile(self, params: dict, level: str = "dual",
                batch_size: int = 256, **kwargs):
        """Compile this model into an ``InferencePlan`` (the serving-side
        artifact): ``plan = model.compile(params); plan.predict(ids)``.
        Thin delegation to :func:`repro.core.plan.compile_plan`; serving
        deployments should hold plans (or an ``InferenceEngine``) rather
        than calling :meth:`apply` per request."""
        from repro.core.plan import compile_plan
        return compile_plan(self, params, level, batch_size, **kwargs)

    def apply(self, params: dict, ids: jax.Array) -> jax.Array:
        """Differentiable forward = whole graph in breadth-first order.

        This is the *training* path (traceable under jit/grad). For
        inference use :meth:`compile` / ``InferenceEngine`` — they own
        compiled, batch-shaped artifacts instead of re-executing the graph
        eagerly per call."""
        g = self.build_graph(params, "dual")
        env = g.execute(self.graph_env(params, ids))
        return env["logit"]

    def predict_proba(self, params: dict, ids: jax.Array) -> jax.Array:
        return jax.nn.sigmoid(self.apply(params, ids).reshape(-1))

    def loss(self, params: dict, batch: dict) -> jax.Array:
        return bce_loss(self.apply(params, batch["ids"]), batch["labels"])

    def n_params(self, params: dict) -> int:
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
