"""EmbeddingStore — the parameter-server tier behind the fused lookup.

DPIFrame's Alg.-1 mega-table assumes the whole concatenated table sits in
fast memory; production CTR vocabularies don't fit. HugeCTR's inference
parameter server (arXiv:2210.08804) answers with a tiered design: a small
device-resident cache of hot rows over a larger backing store, exploiting
the zipf skew of real CTR traffic. This module is that tier for the repro:

  ``EmbeddingStore``  the abstraction every embedding consumer talks to —
                      parameter init/placement, one-hot and multi-hot
                      lookup, traffic observation, cache bookkeeping.
  ``DenseStore``      today's monolithic mega-table (the default): one
                      ``mega_table`` leaf, every lookup one fused gather.
  ``CachedStore``     (``repro.embedding.cached``) hot-row cache of
                      capacity C + full backing table + index map.
  ``HostBackedStore`` (``repro.embedding.host``) hot-row cache + per-batch
                      staging buffer on device; the backing table stays in
                      host memory (or on disk via mmap) and misses are
                      resolved by an async prefetch pipeline.

``FusedEmbeddingCollection`` delegates all lookups and parameter handling
to its store, so the whole stack — ``kernels/ops.py`` →
``embedding/collection.py`` → ``core/plan.py`` → ``serving/engine.py`` —
is store-agnostic.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops
from repro.kernels.multi_table_lookup import (pack_rows, rows_per_line,
                                              unpack_rows)

from .spec import FusedEmbeddingSpec

__all__ = ["StoreStats", "EmbeddingStore", "DenseStore", "runtime_edge",
           "validate_deltas"]


def runtime_edge(prefix: str, leaf: str) -> str:
    """Graph-input edge name of one runtime store tensor.

    Refreshable stores expose their tensors (cache/backing/index map) as
    *runtime inputs* of compiled plans instead of baked constants, so a
    cache refresh is a tensor swap rather than a recompile. Everything
    that names those edges — model graph emission, ``compile_plan``'s AOT
    input spec, the engine's per-call bindings — goes through this one
    function so the convention can never drift.
    """
    return f"{prefix}:{leaf}"


def validate_deltas(spec: FusedEmbeddingSpec, row_ids, new_rows
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Canonicalize one ``(row_id, new_row)`` delta batch.

    Shared by every store's ``apply_deltas``: ``row_ids`` become a unique
    int64 vector (duplicates keep the **last** occurrence — the stream is
    ordered, and a scatter with duplicate indices has no defined winner),
    ``new_rows`` the matching ``(n, d)`` full-precision array. Rejects
    out-of-range ids and — hard — any id at or past ``spec.zero_row``:
    the zero row and the padding rows must stay zero for multi-hot
    masking, so a trainer can never push values into them.
    """
    row_ids = np.asarray(row_ids, dtype=np.int64).reshape(-1)
    rows = np.asarray(new_rows, dtype=np.dtype(spec.dtype))
    if rows.ndim == 1:
        rows = rows[None, :]
    if rows.shape != (row_ids.size, spec.dim):
        raise ValueError(f"delta rows shape {rows.shape} != "
                         f"{(row_ids.size, spec.dim)}")
    if row_ids.size == 0:
        return row_ids, rows
    if row_ids.min() < 0 or row_ids.max() >= spec.zero_row:
        bad = row_ids[(row_ids < 0) | (row_ids >= spec.zero_row)]
        raise ValueError(
            f"delta row ids {bad[:8].tolist()} out of range [0, "
            f"{spec.zero_row}) — the zero row and padding rows must stay "
            "zero (multi-hot masking depends on it)")
    # keep the LAST occurrence of each duplicated id (stream order wins)
    _, first_in_reversed = np.unique(row_ids[::-1], return_index=True)
    keep = row_ids.size - 1 - first_in_reversed
    return row_ids[keep], rows[keep]


@dataclasses.dataclass
class StoreStats:
    """Host-side traffic counters of one embedding store.

    ``hits``/``misses`` count *row lookups* (b·k per one-hot batch) against
    the store's current index map; ``refreshes`` counts cache rebuilds.
    All zero (and staying zero) for ``DenseStore``.

    The staging counters are live only for stores with ``needs_staging``:
    ``staged_rows`` counts rows gathered host-side at serve time (synchronous
    — the prefetch worker didn't get there first), ``prefetched_rows`` rows
    already resolved when the batch arrived, ``h2d_bytes`` the host→device
    staging traffic those synchronous rows cost, and ``staging_overflows``
    batches whose miss set exceeded the staging buffer (served via the
    chunked fallback).

    All byte counters are **wire** bytes — dtype-aware via the spec's
    ``wire_row_bytes`` (``4·d`` for fp32 rows, ``d + 4`` for int8 rows +
    their fp32 scale), never an fp32 assumption. ``gather_bytes`` accounts
    the device-side gather traffic of observed lookups (rows × wire bytes);
    the ``quant_*`` pair is nonzero only for quantized stores:
    ``quant_rows`` counts rows pushed through ``repro.quant`` at
    init/adopt/refresh/delta time, ``quant_bytes_saved`` the gather bytes
    the int8 representation avoided vs full-precision rows.

    ``delta_rows`` counts rows whose *values* changed through
    :meth:`EmbeddingStore.apply_deltas` (online trainer pushes) — distinct
    from ``refreshes``, which only re-admits existing values.
    """
    hits: int = 0
    misses: int = 0
    refreshes: int = 0
    staged_rows: int = 0
    prefetched_rows: int = 0
    h2d_bytes: int = 0
    staging_overflows: int = 0
    gather_bytes: int = 0
    quant_rows: int = 0
    quant_bytes_saved: int = 0
    delta_rows: int = 0

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_rate(self) -> float:
        n = self.lookups
        return self.hits / n if n else 0.0


class EmbeddingStore:
    """Interface of the embedding parameter tier.

    A store owns (a) the *layout* of embedding parameters — what leaves its
    param subtree contains and how they shard over a mesh — and (b) the
    *lookup* that turns per-field ids into embedding rows. Implementations
    must be bit-exact with each other: a store is a memory-system choice,
    never a numerics choice (paper Table I discipline).
    """

    spec: FusedEmbeddingSpec
    #: True when the store keeps a rebuildable cache tier — engines only
    #: run the observe/refresh loop for refreshable stores.
    refreshable: bool = False
    #: Param-subtree leaves that compiled plans must take as *runtime
    #: inputs* (per-call arguments) rather than bake as constants, so a
    #: ``refresh`` can swap them without invalidating any compiled plan.
    #: Empty for stores that never refresh (their tensors may be baked).
    runtime_keys: tuple = ()
    #: True when the store cannot resolve every lookup from device-resident
    #: tensors alone — the serve path must call :meth:`stage` with each
    #: batch's ids *before* the lookup (and may call :meth:`prefetch_hint`
    #: with upcoming batches to move that work off the critical path).
    needs_staging: bool = False

    def __init__(self, spec: FusedEmbeddingSpec):
        self.spec = spec
        self.stats = StoreStats()

    @property
    def quantized(self) -> bool:
        """True when this store's rows travel as int8 + per-row fp32 scale
        (``spec.row_dtype == "int8"``). Quantized stores relax the
        bit-exactness contract to the accuracy-parity gate — the gather
        dequantizes in-kernel, so scores differ from fp32 by at most the
        per-row round-trip error (≤ scale/2 per element)."""
        return self.spec.quantized

    @property
    def wire_row_bytes(self) -> int:
        """Bytes one row moves on gather / host→device staging."""
        return self.spec.wire_row_bytes

    @property
    def wire_dtype(self) -> np.dtype:
        """dtype of the stored rows: int8 when quantized, else the spec's."""
        return np.dtype(np.int8 if self.quantized else self.spec.dtype)

    # -- packed layout -----------------------------------------------------
    # Every device table a store keeps is packed lane-dense (rows of
    # ``wire_dtype`` as 128-lane lines of 32-bit words) — the layout the
    # Pallas gathers DMA from, kept from init on so no call relayouts.
    def unpack(self, lines):
        """``(rows, d)`` view of a packed table (jnp or numpy)."""
        return unpack_rows(lines, self.spec.dim, self.wire_dtype)

    def gather_rows(self, lines, rows) -> jax.Array:
        """Logical rows ``rows`` of a packed table (refresh-time cache
        fills) — the Pallas gather on a TPU, so the table is never
        relayouted; a plain take on its row view elsewhere."""
        rows = jnp.asarray(rows, dtype=jnp.int32)
        if kops.on_tpu():
            from repro.kernels.multi_table_lookup import mtl_gather
            return mtl_gather(rows, lines, dim=self.spec.dim,
                              dtype=self.wire_dtype)
        return jnp.take(self.unpack(lines), rows, axis=0)

    def _observe_traffic(self, rows: np.ndarray) -> None:
        """Wire-byte accounting shared by every tiered store's ``observe``:
        ``rows`` are the clipped global rows this batch gathered."""
        self.stats.gather_bytes += rows.size * self.wire_row_bytes
        if self.quantized:
            full = self.spec.dim * np.dtype(self.spec.dtype).itemsize
            self.stats.quant_bytes_saved += rows.size * (
                full - self.wire_row_bytes)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        """Fresh parameter subtree for this store."""
        raise NotImplementedError

    def init_dense_table(self, key: jax.Array) -> jax.Array:
        """The canonical (rows, d) mega-table init shared by every store
        (so Dense/Cached params built from one key are value-identical)."""
        spec = self.spec
        # flat small std, production-CTR style (fan-in scaling belongs to
        # the MLP, not the table). Row magnitude also sets the int8
        # absmax grid step, so oversized rows would punish quantized tiers.
        scale = 0.05
        table = jax.random.normal(
            key, (spec.rows, spec.dim), dtype=jnp.dtype(spec.dtype)) * scale
        # zero row (and padding rows) must stay zero for multi-hot masking
        return table.at[spec.zero_row:].set(0.0)

    def adopt(self, params: dict) -> dict:
        """Convert another store's param subtree into this store's layout
        (values preserved bit-for-bit — a store swap is a placement change,
        not a re-init). Engines use this to retrofit a cache onto a model
        whose params were built dense."""
        raise NotImplementedError

    def partition_spec(self, model_axis: str | None = "model") -> dict:
        """PartitionSpec subtree matching :meth:`init`'s structure."""
        raise NotImplementedError

    def place(self, params: dict, mesh, model_axis: str | None = "model"
              ) -> dict:
        """``device_put`` the param subtree onto ``mesh`` per
        :meth:`partition_spec` (vocab-parallel tables, replicated cache
        tiers), dropping mesh axes a leaf's dim doesn't divide.

        The **mesh-aware refresh primitive**: a refresh builds fresh
        tensors host-side, and the engine places them here before the
        double-buffered swap so it publishes *placed* tensors, never
        unplaced host arrays. The specs are re-derived from the same
        ``partition_spec`` the compile-time placement used, so they match
        the shardings recorded on every plan (``runtime_shardings``);
        were they ever to diverge, the plan step's per-call ``device_put``
        re-places the tensors — a cross-device copy on the hot path, not
        a recompile or a wrong answer (tests pin the match).
        """
        from repro.distributed.sharding import fit_spec
        from jax.sharding import NamedSharding
        if mesh is None:
            return params
        axis = model_axis if model_axis in mesh.axis_names else None
        specs = self.partition_spec(axis)
        return {k: jax.device_put(
                    v, NamedSharding(mesh, fit_spec(mesh, specs[k], v.shape)))
                for k, v in params.items()}

    def dense_view(self, params: dict) -> jax.Array:
        """The full (rows, d) table — the serial/naive level and the
        sharded shard_map path gather straight from it."""
        raise NotImplementedError

    # -- lookup ------------------------------------------------------------
    def lookup(self, params: dict, ids: jax.Array, offsets: jax.Array, *,
               strategy: str = "auto",
               interpret: bool | None = None) -> jax.Array:
        """ids (b, k) -> (b, k*d)."""
        raise NotImplementedError

    def lookup_multihot(self, params: dict, ids: jax.Array, mask: jax.Array,
                        offsets: jax.Array, *, strategy: str = "auto",
                        interpret: bool | None = None) -> jax.Array:
        """ids/mask (b, k, h) -> (b, k*d) sum-pooled."""
        raise NotImplementedError

    #: param leaves :meth:`lookup_sharded` reads, the row-sharded table
    #: last; empty for stores without a vocab-parallel lookup
    mesh_leaves: tuple = ()

    def _route(self, env: dict, rows: jax.Array, local: jax.Array,
               own: jax.Array, first: jax.Array):
        """Per-shard tier routing for :meth:`lookup_sharded`: given global
        ``rows``, their ``local`` row in this shard's slice of the table
        and whether this shard ``own``s them (``first`` = model shard 0),
        return ``(tier, row, tables, scale)`` for ``kops.gather_tiers``."""
        raise NotImplementedError

    def lookup_sharded(self, params: dict, ids: jax.Array,
                       offsets: jax.Array, mesh, model_axis: str = "model",
                       *, strategy: str = "auto",
                       interpret: bool | None = None) -> jax.Array:
        """Vocab-parallel one-hot lookup over ``mesh``, ``(b, k) ->
        (b, k*d)``, under ``shard_map``.

        Every model shard gathers the rows its slice of the row-sharded
        table holds (replicated tiers, such as a cache, serve from shard 0
        only) and zeros the rest; one psum over the model axis assembles
        the rows. Exactly one shard contributes each row, so the sum is
        exact. The batch is split over the mesh's other axes when it
        divides them.
        """
        from repro.distributed.sharding import fit_spec
        leaves = tuple(params[k] for k in self.mesh_leaves)
        specs = self.partition_spec(model_axis)
        in_specs = [fit_spec(mesh, specs[k], v.shape)
                    for k, v in zip(self.mesh_leaves, leaves)]
        sharded = tuple(in_specs[-1])[:1] == (model_axis,)
        data = tuple(a for a in mesh.axis_names if a != model_axis)
        n_data = math.prod(mesh.shape[a] for a in data)
        ids_spec = (P(data if len(data) > 1 else data[0], None)
                    if data and ids.shape[0] % n_data == 0 else P())
        dim = self.spec.dim
        # per shard the gather is a plain single-device kernel again
        local_strategy = (("pallas" if kops.on_tpu() else "jnp")
                          if strategy == "auto" else strategy)

        def local_lookup(ids, *vals):
            env = dict(zip(self.mesh_leaves, vals))
            table = env[self.mesh_leaves[-1]]
            n_local = table.shape[0] * rows_per_line(dim, self.wire_dtype)
            axis = jax.lax.axis_index(model_axis)
            first = axis == 0
            rows = (ids.astype(jnp.int32)
                    + offsets[None, :].astype(jnp.int32)).reshape(-1)
            lo = axis * n_local if sharded else 0
            local = rows - lo
            own = (local >= 0) & (local < n_local)
            if not sharded:
                own = own & first
            tier, row, tables, scale = self._route(
                env, rows, jnp.clip(local, 0, n_local - 1), own, first)
            out = kops.gather_tiers(
                tier, row, tables, scale,
                dim=dim, dtype=self.wire_dtype, strategy=local_strategy,
                interpret=interpret)
            return jax.lax.psum(out.reshape(ids.shape[0], -1), model_axis)

        return jax.shard_map(local_lookup, mesh=mesh,
                             in_specs=(ids_spec, *in_specs),
                             out_specs=ids_spec, check_vma=False)(ids, *leaves)

    # -- staging (only meaningful when ``needs_staging``) -------------------
    def stage(self, params: dict, ids, mask=None) -> dict:
        """Resolve this batch's misses into device-reachable tensors and
        return the param subtree to serve it with. No-op pass-through for
        stores whose device tensors already cover every row."""
        return params

    def prefetch_hint(self, ids, mask=None) -> None:
        """Hint that ``ids`` will be served soon — staging stores resolve
        their misses off-thread while earlier batches compute. No-op."""

    def split_for_staging(self, ids) -> list:
        """Split a batch into chunks each of which :meth:`stage` can
        resolve — the fallback after a staging overflow. Trivial single
        chunk for non-staging stores."""
        return [np.asarray(ids)]

    # -- traffic / cache management ---------------------------------------
    def observe(self, global_rows: np.ndarray) -> None:
        """Record served row traffic (host-side; outside jit)."""

    def refresh(self, params: dict) -> dict:
        """Rebuild any cache tier from observed traffic; returns the
        (possibly new) param subtree. No-op for cacheless stores."""
        return params

    def apply_deltas(self, params: dict, row_ids, new_rows
                     ) -> tuple[dict, int]:
        """Apply online ``(row_id, new_row)`` parameter deltas (a live
        trainer's incremental push) and return ``(fresh_subtree,
        n_rows_applied)``.

        The fresh subtree is built **on the side** — the caller publishes
        it through the same double-buffered swap as a refresh, so compiled
        plans survive every delta batch with zero recompiles. Incoming
        rows are always full-precision; quantized stores re-quantize them
        through ``repro.quant`` before publish. Only stores whose tensors
        are runtime plan inputs can support this — ``DenseStore`` bakes
        its ``mega_table`` into every compiled plan as a constant, so
        updated values could never reach a cached plan.
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not support online deltas: its "
            "tensors are compiled into plans as constants, not runtime "
            "inputs. Serve through CachedStore or HostBackedStore (their "
            "tiers republish through the recompile-free swap).")

    @property
    def cached_traffic_fraction(self) -> float:
        """Fraction of *observed traffic mass* whose rows are currently
        cached (1.0 for a store that holds everything in one tier)."""
        return 1.0

    def describe(self) -> str:
        """Short identity string (stamped into plan keys and stats)."""
        raise NotImplementedError


class DenseStore(EmbeddingStore):
    """The monolithic mega-table: everything in one fast-memory tier.

    Param subtree: ``{"mega_table": lines}`` — the ``(rows, d)`` table
    packed lane-dense (``pack_rows``).
    """

    def init(self, key: jax.Array) -> dict:
        return {"mega_table": pack_rows(self.init_dense_table(key))}

    def adopt(self, params: dict) -> dict:
        if "mega_table" in params:
            return params
        backing = params["backing"]
        if "backing_scale" in params and backing.dtype != jnp.float32:
            # a quantized tiered subtree: reconstitute full-precision rows
            # (lossy source — the int8 grid is all the values that remain)
            from repro import quant
            rows = unpack_rows(backing, self.spec.dim, np.int8)
            backing = pack_rows(quant.dequantize_rows(
                rows, params["backing_scale"]).astype(
                    jnp.dtype(self.spec.dtype)))
        return {"mega_table": backing}

    def partition_spec(self, model_axis: str | None = "model") -> dict:
        """Row-sharded (vocab-parallel) placement of the mega-table's
        packed lines."""
        return {"mega_table": P(model_axis, None)}

    def dense_view(self, params: dict) -> jax.Array:
        return self.unpack(params["mega_table"])

    def lookup(self, params: dict, ids: jax.Array, offsets: jax.Array, *,
               strategy: str = "auto",
               interpret: bool | None = None) -> jax.Array:
        return kops.multi_table_lookup(
            ids, params["mega_table"], offsets,
            strategy=strategy, interpret=interpret, dim=self.spec.dim)

    def lookup_multihot(self, params: dict, ids: jax.Array, mask: jax.Array,
                        offsets: jax.Array, *, strategy: str = "auto",
                        interpret: bool | None = None) -> jax.Array:
        return kops.multi_table_lookup_multihot(
            ids, mask, params["mega_table"], offsets,
            strategy=strategy, interpret=interpret, dim=self.spec.dim)

    mesh_leaves = ("mega_table",)

    def _route(self, env, rows, local, own, first):
        return (jnp.where(own, 0, -1), local, (env["mega_table"],), None)

    def describe(self) -> str:
        return f"dense(rows={self.spec.rows},d={self.spec.dim})"
