"""InferencePlan — the immutable compiled artifact of the serving stack.

The repo's execution API has three explicit layers (HugeCTR's inference
parameter server and PCDF's parallel-computing serving framework follow the
same decomposition):

  1. **compile** — :func:`compile_plan` turns (model, params, level,
     batch shape) into an :class:`InferencePlan` once: the fused ``OpGraph``,
     the breadth-first schedule, the ``ExecutorStats`` bookkeeping, and a
     runnable step. At level ``"dual"`` the step is AOT-lowered and
     compiled via ``jax.jit(...).lower(...).compile()`` so the first served
     request never pays trace/compile time; the other Fig.-8 levels keep
     their deliberate op-by-op dispatch but have every per-op jit warmed.
  2. **plan** — the ``InferencePlan`` is immutable and batch-shape-specific;
     it can be cached, shipped across engines, and called directly
     (``plan(ids) -> logits``, ``plan.predict(ids) -> scores``). A
     refreshable embedding store's tensors are *runtime inputs* of the
     step (``runtime_inputs``), not baked constants, so plans survive
     cache refreshes unchanged.
  3. **engine** — ``repro.serving.engine.InferenceEngine`` owns a cache of
     plans keyed by ``(model, level, batch_bucket)`` plus a pluggable
     batching policy (``repro.serving.batching``).

With ``mesh=`` the plan is a real multi-chip serving artifact: the
embedding mega-tables are placed row-sharded (vocab-parallel, the
``FusedEmbeddingCollection.partition_spec`` placement) over the mesh's
model axis before tracing, per-call batch inputs are sharded over the data
axis, and the compiled program runs under GSPMD. The resolved placements
are recorded on the plan (``input_shardings``/``runtime_shardings``) so
the serving layers can ``device_put`` incoming batches and — critically —
so a cache refresh republishes *placed* tensors (``place_params`` /
``EmbeddingStore.place``) instead of unplaced host arrays.

``DualParallelExecutor`` remains the graph-preparation machinery underneath;
user code should not need to touch it directly anymore.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from .dual_parallel import (BRANCH_ORDERS, LEVELS, DualParallelExecutor,
                            ExecutorStats)
from .opgraph import OpGraph

__all__ = ["PlanKey", "InferencePlan", "Launched", "compile_plan",
           "plan_key_for", "place_params", "COMPUTE_DTYPES"]

#: dense-branch compute dtypes a plan can be compiled at: fp32 GEMMs, or
#: int8 matmuls with fused in-kernel dequant (kernels.dense_matmul_q8)
COMPUTE_DTYPES = ("fp32", "int8")


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Cache identity of a compiled plan (the engine's cache key)."""
    model: str
    level: str
    batch_size: int
    branch_order: str = "longer_first"
    sharded: bool = False
    store: str = "dense"
    compute_dtype: str = "fp32"


def _store_describe(model) -> str:
    """Embedding-store identity of a model (plan keys and stats carry it:
    two models differing only in store tiers must never share plans)."""
    coll = getattr(model, "embedding", None)
    store = getattr(coll, "store", None)
    return store.describe() if store is not None else "none"


def plan_key_for(model, level: str, batch_size: int,
                 branch_order: str = "longer_first",
                 sharded: bool = False,
                 compute_dtype: str = "fp32") -> PlanKey:
    """The single definition of plan/cache identity — used both by
    :func:`compile_plan` (stamped on the plan) and by engines keying their
    caches, so the two can never drift."""
    return PlanKey(model=getattr(model.spec, "name", type(model).__name__),
                   level=level, batch_size=int(batch_size),
                   branch_order=branch_order, sharded=sharded,
                   store=_store_describe(model),
                   compute_dtype=compute_dtype)


@dataclasses.dataclass(frozen=True)
class InferencePlan:
    """One compiled, batch-shape-specific inference artifact.

    ``step`` maps ``rows (batch_size, row_width) int32 -> logits`` (a
    request row is the model's ``spec.row_width`` int32s: its numeric
    counts, then its id slots; ``k`` ids for a one-hot model); it is the
    AOT-compiled executable at level "dual" and the warmed eager chain at
    the other levels. Plans are immutable: recompile to change anything —
    with one deliberate exception: ``runtime_inputs`` names the embedding
    store tensors (a refreshable tier's cache/backing/index map) that the
    step takes as *per-call arguments* instead of baked constants. Their
    values come from the ``runtime_provider`` the plan was compiled with,
    so swapping the published tensors (a cache refresh) retargets every
    call without touching the compiled program.
    """
    key: PlanKey
    stats: ExecutorStats
    graph: OpGraph
    order: tuple[str, ...]
    step: Callable[[jax.Array], jax.Array]
    row_width: int
    donate: bool
    compile_ms: float
    runtime_inputs: tuple[str, ...] = ()
    #: mesh the plan was compiled against (None = single device)
    mesh: jax.sharding.Mesh | None = None
    #: per-call input leaf -> NamedSharding ("ids": batch dim over the
    #: mesh's data axis, fit_spec fallback for odd batch sizes); empty
    #: without a mesh. The step device_puts incoming batches to these, and
    #: engines may pre-place batches themselves.
    input_shardings: dict = dataclasses.field(default_factory=dict)
    #: runtime-input edge -> NamedSharding (the store placement the step
    #: was lowered against: backing/mega row-sharded over model, cache +
    #: slot_of_row replicated). A mesh-aware refresh MUST republish fresh
    #: tensors placed to exactly these.
    runtime_shardings: dict = dataclasses.field(default_factory=dict)
    #: the AOT ``jax.stages.Compiled`` step at level "dual" (None at the
    #: eager levels): ``as_text()`` / ``memory_analysis()`` of the program
    #: every call runs
    executable: Any = None

    @property
    def level(self) -> str:
        return self.key.level

    @property
    def batch_size(self) -> int:
        return self.key.batch_size

    def __call__(self, ids: jax.Array) -> jax.Array:
        return self.step(ids)

    def launch(self, ids) -> Launched:
        """First half of :meth:`predict`: pad ``ids`` to the plan's batch
        shape, copy them to the device and dispatch the step and the
        sigmoid, without waiting for either; the :class:`Launched` batch
        comes back."""
        with TraceAnnotation("plan.dispatch"):
            ids = np.asarray(ids, dtype=np.int32)
            if ids.ndim == 1:
                ids = ids[None, :]
            b = ids.shape[0]
            if b > self.batch_size:
                raise ValueError(
                    f"{b} rows > plan batch_size {self.batch_size}; use an "
                    "InferenceEngine (it batches) or compile a bigger plan")
            padded = ids
            if b < self.batch_size:
                pad = np.zeros((self.batch_size - b, ids.shape[1]),
                               dtype=ids.dtype)
                padded = np.concatenate([ids, pad])
            logits = self.step(jnp.asarray(padded))
            scores = jax.nn.sigmoid(jnp.reshape(jnp.asarray(logits), (-1,)))
        return Launched(scores, ids)

    @staticmethod
    def wait(launched: Launched) -> None:
        """Block until a launched batch's scores are ready on the
        device."""
        with TraceAnnotation("plan.wait"):
            launched.scores.block_until_ready()

    def predict(self, ids) -> np.ndarray:
        """Sigmoid scores for ``ids`` ((row_width,) or (b, row_width) with
        b ≤ batch_size): :meth:`launch`, :meth:`wait`, then the scores
        copied to the host with the padding sliced off. ``ids`` may also
        be a batch this plan launched: its scores are then read back, once
        ready, and not computed again."""
        launched = ids
        if not isinstance(ids, Launched):
            launched = self.launch(ids)
            self.wait(launched)
        with TraceAnnotation("plan.readback"):
            return np.asarray(launched.scores)[:len(launched.rows)]


@dataclasses.dataclass(frozen=True)
class Launched:
    """A batch :meth:`InferencePlan.launch` dispatched and nobody has read
    back yet: its ``scores`` on the device, padding rows included, and
    the request ``rows`` they are for. It stands for those rows as an
    array (``np.asarray(launched)``), so it can take their place in
    :meth:`InferencePlan.predict`, and code that wraps ``predict`` and
    reads its argument as rows sees the rows."""
    scores: jax.Array
    rows: np.ndarray

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.rows, dtype=dtype)


def _shard_params(params: Any, mesh: jax.sharding.Mesh, model_axis: str,
                  specs: Any = None) -> Any:
    """Place params on ``mesh`` per a PartitionSpec tree.

    ``specs`` comes from the model's ``partition_spec(params)`` — which
    delegates embedding subtrees to their store — so placement follows the
    parameter *structure*, not fragile name matching (the old
    ``"mega" in names`` heuristic broke as soon as a store renamed or
    nested its leaves). Leaves whose leading dim doesn't divide the axis
    fall back to replication; ``specs=None`` replicates everything.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        model_axis, 1)
    if specs is None:
        specs = jax.tree.map(lambda _: P(), params)

    def place(leaf, spec):
        dims = tuple(spec)
        if (dims and dims[0] == model_axis
                and (getattr(leaf, "ndim", 0) == 0
                     or leaf.shape[0] % n_shards != 0)):
            spec = P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(place, params, specs)


def place_params(model, params: Any, mesh: jax.sharding.Mesh,
                 model_axis: str = "model") -> Any:
    """Place a model's params on ``mesh`` per its structural
    ``partition_spec`` (embedding subtrees delegated to their store:
    backing/mega row-sharded vocab-parallel, cache tiers replicated).

    The one placement entry point shared by :func:`compile_plan` and
    ``InferenceEngine`` — an engine with a mesh places its live params
    here once at construction, so the provider feeding runtime store
    tensors into compiled steps always hands out *placed* arrays. On a
    mesh without the model axis (e.g. ``data``-only), tables replicate.
    """
    axis = model_axis if model_axis in mesh.axis_names else None
    specs = (model.partition_spec(params, axis)
             if hasattr(model, "partition_spec") else None)
    return _shard_params(params, mesh, axis, specs)


def compile_plan(model, params: Any, level: str = "dual",
                 batch_size: int = 256, *,
                 mesh: jax.sharding.Mesh | None = None,
                 donate: bool = False,
                 branch_order: str = "longer_first",
                 model_axis: str = "model",
                 runtime_provider: Callable[[], dict] | None = None,
                 compute_dtype: str = "fp32") -> InferencePlan:
    """Compile one (model, level, batch shape) into an InferencePlan.

    Args:
        model: a ``CTRModel`` (anything with ``spec.row_width`` and
            ``build_graph(params, level)``).
        params: the model's parameter pytree.
        level: one of ``repro.core.LEVELS`` (the Fig.-8 ladder).
        batch_size: the fixed batch shape this plan serves.
        mesh: optional device mesh; mega-tables are row-sharded over its
            ``model_axis`` before tracing (vocab-parallel placement) and
            per-call batch inputs are sharded over its data axis
            (``distributed.sharding.batch_specs`` with a ``fit_spec``
            replication fallback when the batch size doesn't divide the
            axis). The resolved placements are recorded on the plan
            (``input_shardings``/``runtime_shardings``) so engines can
            ``device_put`` incoming batches and refresh swaps to them.
        donate: donate the input buffer to the compiled step (XLA may
            reuse it; callers must treat submitted arrays as consumed).
            Only meaningful at level ``"dual"`` — the eager levels dispatch
            op-by-op and ignore it. Runtime store tensors are never
            donated (they are shared across calls and plans).
        branch_order: breadth-first head-branch policy (§V-H ablations).
        runtime_provider: zero-arg callable returning the current runtime
            store tensors (edge name -> array, the plan's
            ``runtime_inputs``), consulted on *every* step call. Default:
            bind the tensors in ``params`` at compile time — equivalent to
            the old baked-constant behavior. ``InferenceEngine`` passes a
            provider reading its live params so a ``refresh_cache()``
            tensor swap retargets every cached plan with zero recompiles.
        compute_dtype: ``"fp32"`` (default) or ``"int8"`` — quantize every
            dense-branch matmul: weights per output channel *once here at
            compile* (baked int8 constants — MLP weights are not runtime
            inputs, so refresh stays recompile-free), activations per row
            dynamically inside the fused ``dense_matmul_q8`` kernel. Part
            of the plan's cache identity, so quantized and fp32 plans
            coexist in one engine cache.
    """
    if level not in LEVELS:
        raise ValueError(f"level must be one of {LEVELS}, got {level!r}")
    if branch_order not in BRANCH_ORDERS:
        raise ValueError(f"branch_order must be one of {BRANCH_ORDERS}, "
                         f"got {branch_order!r}")
    if compute_dtype not in COMPUTE_DTYPES:
        raise ValueError(f"compute_dtype must be one of {COMPUTE_DTYPES}, "
                         f"got {compute_dtype!r}")
    if mesh is not None:
        params = place_params(model, params, mesh, model_axis)

    builder = model.build_graph
    if compute_dtype != "fp32":
        def builder(p, lvl, _build=model.build_graph):
            return _build(p, lvl, compute_dtype=compute_dtype)
    executor = DualParallelExecutor(builder, level=level,
                                    branch_order=branch_order)
    t0 = time.perf_counter()
    with TraceAnnotation("plan.compile"):
        graph, order = executor.prepare(params)
        step_env = executor.make_step(graph, order, donate=donate)
        row_width = model.spec.row_width

        # runtime store tensors (refreshable tiers only): extra step inputs,
        # re-read from the provider each call instead of baked into the program
        runtime = (model.store_runtime_env(params)
                   if hasattr(model, "store_runtime_env") else {})
        provider = runtime_provider if runtime_provider is not None \
            else (lambda: runtime)

        # resolved shardings (the multi-chip serving contract, recorded on the
        # plan): per-call inputs batch-sharded over the mesh's data axis with
        # fit_spec fallback for batch sizes the axis doesn't divide; runtime
        # store tensors carry the placement place_params gave them (backing/
        # mega row-sharded over model, cache + slot_of_row replicated)
        in_shardings: dict = {}
        rt_shardings: dict = {}
        if mesh is not None:
            from repro.distributed.sharding import input_shardings
            in_shardings = input_shardings(
                mesh, {"ids": jax.ShapeDtypeStruct((batch_size, row_width),
                                                   jnp.int32)})
            rt_shardings = {k: v.sharding for k, v in runtime.items()}

        def bind_inputs(ids: jax.Array) -> dict:
            if in_shardings:
                ids = jax.device_put(ids, in_shardings["ids"])
            return {"ids": ids}

        def bind_runtime() -> dict:
            env = provider()
            if rt_shardings:
                # no-op for tensors already placed (the refresh path places
                # before publishing); a safety net for callers that swap in
                # raw host arrays
                env = {k: jax.device_put(v, rt_shardings[k])
                       for k, v in env.items()}
            return env

        if level == "dual":
            # AOT: lower + compile the whole-graph program now, not on first
            # use — with the resolved input/runtime shardings baked into the
            # lowered avals so GSPMD partitions the program for the mesh
            spec = {"ids": jax.ShapeDtypeStruct(
                (batch_size, row_width), jnp.int32,
                sharding=in_shardings.get("ids"))}
            rt_spec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                               sharding=rt_shardings.get(k))
                       for k, v in runtime.items()}
            from repro.kernels.ops import mesh_context
            with mesh_context(mesh, model_axis):
                compiled = step_env.lower(spec, rt_spec).compile()
            executable = compiled

            def step(ids: jax.Array) -> jax.Array:
                return compiled(bind_inputs(ids), bind_runtime())
        else:
            # eager levels dispatch op-by-op on purpose; warm every per-op jit
            # so serving latency never includes compiles
            executable = None

            def step(ids: jax.Array) -> jax.Array:
                return step_env(bind_inputs(ids), bind_runtime())
            jax.block_until_ready(
                step(jnp.zeros((batch_size, row_width), dtype=jnp.int32)))
    compile_ms = (time.perf_counter() - t0) * 1e3

    key = plan_key_for(model, level, batch_size, branch_order,
                       sharded=mesh is not None,
                       compute_dtype=compute_dtype)
    stats = executor.stats
    stats.embedding_store = _store_describe(model)
    stats.compute_dtype = compute_dtype
    return InferencePlan(key=key, stats=stats, graph=graph,
                         order=tuple(order), step=step, row_width=row_width,
                         donate=donate, compile_ms=compile_ms,
                         runtime_inputs=tuple(sorted(runtime)),
                         mesh=mesh, input_shardings=in_shardings,
                         runtime_shardings=rt_shardings,
                         executable=executable)
