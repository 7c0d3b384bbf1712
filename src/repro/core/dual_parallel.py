"""DualParallelExecutor — contribution C1, tying C2–C5 together.

A CTR model exposes its forward pass as an ``OpGraph`` with four modules:
``embedding`` → (``explicit`` ∥ ``implicit``) → ``head``; a graph may name
another pair of parallel branches (``OpGraph.branches``: DLRM-DCNv2 runs
its ``embedding`` lookup beside its ``bottom`` MLP). The executor turns
that graph into a runnable step function at one of four optimization levels,
mirroring the paper's Fig.-8 breakdown exactly:

  level "naive"      per-field serial embedding, op-by-op eager dispatch,
                     depth-first order             (PyTorch-A analogue)
  level "fused_emb"  Alg.-1 fused mega-table lookup, rest eager
                                                    (DPIFrame-A)
  level "fused_all"  + non-GEMM subgraph fusion (C5), fused groups each
                     dispatched as one unit         (DPIFrame-B)
  level "dual"       + breadth-first interleaved branch schedule (C4) and
                     whole-graph jit so XLA's static scheduler can overlap
                     the two branches               (DPIFrame-C)

"Eager" here means each op is dispatched as its own jit-compiled call with
its own host→device round trip — the JAX reflection of per-kernel launch
overhead that the paper attributes to PyTorch. "dual" traces the whole graph
(in breadth-first order) into ONE XLA program.

Accuracy invariance (paper Table I): every level computes the identical
function — asserted in tests to float exactness on same-backend dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from .opgraph import OpGraph, fuse_non_gemm, op_outputs
from .scheduler import (breadth_first_schedule, depth_first_schedule,
                        full_order)

__all__ = ["DualParallelExecutor", "ExecutorStats", "LEVELS", "BRANCH_ORDERS"]

LEVELS = ("naive", "fused_emb", "fused_all", "dual")
BRANCH_ORDERS = ("longer_first", "explicit_first", "implicit_first")


@dataclasses.dataclass
class ExecutorStats:
    n_ops_before: int
    n_ops_after: int
    n_fused_groups: int
    kernels_used: tuple[str, ...]
    schedule_policy: str
    queue: tuple[str, ...]
    # identity of the embedding tier the plan was compiled against
    # ("dense(rows=...,d=...)" / "cached(C=...,rows=...,d=...)"), stamped by
    # compile_plan; live hit-rate counters are on EngineStats, not here
    embedding_store: str = "none"
    # dense-branch compute dtype the graph was emitted with, plus the
    # structural quantized-matmul counters emit_mlp_ops stamps in
    # OpGraph.meta (weight bytes count the int8 payload + per-channel
    # scales; "saved" is vs the 4 B/element fp32 matrix)
    compute_dtype: str = "fp32"
    mlp_quant_matmuls: int = 0
    mlp_quant_weight_bytes: int = 0
    mlp_quant_weight_bytes_saved: int = 0


class DualParallelExecutor:
    """Builds and runs a dual-parallel inference step from a model graph.

    Args:
        graph_builder: callable ``(params, level) -> OpGraph``. Models build
            the graph differently per level only for the *embedding* module
            (serial vs fused lookup); all other ops are identical — fusion
            and scheduling are applied here, not inside the model.
        level: one of LEVELS.
        branch_order: "longer_first" (paper default), "explicit_first",
            "implicit_first" (§V-H startup-sequence ablation).
    """

    def __init__(self, graph_builder: Callable[..., OpGraph], *,
                 level: str = "dual", branch_order: str = "longer_first"):
        if level not in LEVELS:
            raise ValueError(f"level must be one of {LEVELS}, got {level!r}")
        if branch_order not in BRANCH_ORDERS:
            raise ValueError(f"branch_order must be one of {BRANCH_ORDERS}, "
                             f"got {branch_order!r}")
        self.graph_builder = graph_builder
        self.level = level
        self.branch_order = branch_order
        self._stats: ExecutorStats | None = None

    # -- graph preparation ---------------------------------------------------
    def prepare(self, params: Any) -> tuple[OpGraph, list[str]]:
        graph = self.graph_builder(params, self.level)
        n_before = graph.n_kernels()
        if self.level in ("fused_all", "dual"):
            graph = fuse_non_gemm(graph)
        # the graph's two parallel branches: explicit ∥ implicit unless the
        # model names another pair ("explicit_first" then pins its first)
        explicit, implicit = (graph.by_module(m) for m in graph.branches)
        if self.level == "dual":
            # "explicit_first"/"implicit_first" pin the head branch
            # deterministically (equal-length branches included); only
            # "longer_first" lets Alg. 2 pick by branch length.
            first = {"longer_first": "longer",
                     "explicit_first": "explicit",
                     "implicit_first": "implicit"}[self.branch_order]
            sched = breadth_first_schedule(explicit, implicit, first=first)
        else:
            sched = depth_first_schedule(explicit, implicit)
        order = full_order(graph, sched)
        fused_groups = [op for op in graph.ops if hasattr(op, "members")]
        self._stats = ExecutorStats(
            n_ops_before=n_before,
            n_ops_after=graph.n_kernels(),
            n_fused_groups=len(fused_groups),
            kernels_used=tuple(op.kernel for op in fused_groups
                               if getattr(op, "kernel", None)),
            schedule_policy=sched.policy,
            queue=tuple(sched.queue),
            compute_dtype=graph.meta.get("compute_dtype", "fp32"),
            mlp_quant_matmuls=graph.meta.get("mlp_quant_matmuls", 0),
            mlp_quant_weight_bytes=graph.meta.get(
                "mlp_quant_weight_bytes", 0),
            mlp_quant_weight_bytes_saved=graph.meta.get(
                "mlp_quant_weight_bytes_saved", 0),
        )
        return graph, order

    @property
    def stats(self) -> ExecutorStats:
        if self._stats is None:
            raise RuntimeError("call build() first")
        return self._stats

    # -- runnable step ---------------------------------------------------------
    def build(self, params: Any) -> Callable[[dict[str, Any]], Any]:
        """Returns ``step(inputs_env) -> output`` at the configured level."""
        graph, order = self.prepare(params)
        return self.make_step(graph, order)

    def make_step(self, graph: OpGraph, order: list[str], *,
                  donate: bool = False) -> Callable[..., Any]:
        """Turn a prepared (graph, order) into
        ``step(inputs_env, runtime_env=None) -> output``.

        ``inputs_env`` carries the per-request values (``ids``);
        ``runtime_env`` carries runtime store tensors (a refreshable
        embedding tier's cache/backing/index map — see
        ``EmbeddingStore.runtime_keys``) that change across refreshes but
        never per request. They are separate arguments so ``donate`` can
        consume request buffers without ever donating the published store
        tensors. Split from :meth:`build` so ``repro.core.plan.
        compile_plan`` can AOT-lower the jit without re-preparing the
        graph (``step.lower`` is exposed at level "dual").
        """
        ops_in_order = [graph.op(n) for n in order]
        out_edge = ops_in_order[-1].output

        if self.level == "dual":
            # one traced program, breadth-first trace order
            def whole(env, runtime_env):
                e = graph.execute({**env, **runtime_env}, order)
                return e[out_edge]
            jitted_whole = jax.jit(whole,
                                   donate_argnums=(0,) if donate else ())

            def step(env, runtime_env=None):
                return jitted_whole(env, runtime_env or {})
            step.lower = jitted_whole.lower
            return step

        # eager op-by-op dispatch: each op is its own jit call (its own
        # device dispatch), mirroring per-kernel launch overhead
        jitted = [jax.jit(op.fn) for op in ops_in_order]

        def eager(env, runtime_env=None):
            env = {**env, **(runtime_env or {})}
            for op, jfn in zip(ops_in_order, jitted):
                res = jfn(*[env[e] for e in op.inputs])
                outs = op_outputs(op)
                if len(outs) == 1:
                    env[outs[0]] = res
                else:
                    for name, val in zip(outs, res):
                        env[name] = val
                jax.block_until_ready(res)
            return env[out_edge]
        return eager
