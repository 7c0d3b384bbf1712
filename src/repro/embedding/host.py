"""HostBackedStore — out-of-HBM embedding tier with an async prefetch path.

``CachedStore`` caches hot rows but still keeps the *full* backing table in
device memory, so the largest servable vocabulary is bounded by one chip's
HBM. This store removes that ceiling — the HugeCTR hierarchical parameter
server (arXiv:2210.08804 / 2210.08803) brought to the DPIFrame stack:

  device   (tables packed lane-dense, see ``EmbeddingStore.unpack``)
           ``cache``       (C, d)   hot-row copies (admission-managed)
           ``slot_of_row`` (rows,)  int32 cache map, -1 = uncached
           ``staging``     (S, d)   per-batch copies of this batch's misses
           ``staging_slot_of_row`` (rows,) int32 staging map, -1 = unstaged
  host     backing table   (rows, d) numpy array — **never uploaded whole**
  disk     optional third tier: ``backing_path=`` memory-maps the backing
           from a file (``np.memmap``), so the table need not fit host RAM
           either.

A lookup is one **three-way select** inside the scalar-prefetch gather
(``kernels.mtl_gather_three_level`` on TPU, jnp twin on CPU): cache hit →
cache row, staged miss → staging row, neither → zero-guard. Correctness
therefore rests on the serve path resolving every miss *before* the
lookup: ``stage(params, ids)`` gathers the batch's uncached rows from the
host backing into the staging buffer (most already there thanks to the
:class:`~repro.embedding.prefetch.PrefetchPipeline`'s async hints) and
publishes fresh ``staging``/``staging_slot_of_row`` tensors through the
same double-buffered swap a refresh uses — all four device tensors are
``runtime_keys``, so compiled plans survive every batch and every refresh
with zero recompiles. Bit-exactness with ``DenseStore`` is the hard
contract: staged and cached rows are verbatim copies of backing rows.

When a single batch's distinct miss set exceeds ``S``, ``stage`` raises
``StagingOverflowError`` and the caller serves the batch in chunks
(:meth:`split_for_staging`) — a synchronous host gather in waves, slower
but never wrong.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import quant
from repro.kernels import ops as kops
from repro.kernels.multi_table_lookup import pack_rows, set_rows, unpack_rows

from .prefetch import PrefetchPipeline, StagingOverflowError
from .spec import FusedEmbeddingSpec
from .store import EmbeddingStore, validate_deltas

__all__ = ["HostBackedStore"]


class HostBackedStore(EmbeddingStore):
    """Hot-row device cache + staging buffer over a host-resident backing.

    Args:
        spec: the fused embedding schema.
        capacity: device cache rows ``C`` (clamped to ``spec.rows``).
        staging_capacity: staging slots ``S``; must cover one sample's
            worst-case miss set (``k * multi_hot``) so chunked serving can
            always make progress. Default ``max(4 * k * multi_hot, 256)``
            (clamped to ``spec.rows``).
        backing_path: optional file for the third tier — the backing table
            is a ``np.memmap`` of this file instead of a RAM array. Create
            via :meth:`init`/:meth:`adopt` (writes the table), reopen an
            existing file with :meth:`open`.
        row_dtype: ``"int8"`` stores all three tiers quantized (symmetric
            absmax, one fp32 scale per row — ``repro.quant``): the host
            backing is int8 + an ``(rows, 1)`` scale column (the mmap tier
            writes the scales to a ``backing_path + ".scale"`` sidecar),
            the staging pipeline moves ``d + 4`` bytes per resolved row
            instead of ``4·d``, and the gather dequantizes in-kernel
            (``mtl_gather_three_level_q8``). Default ``None`` keeps the
            bit-exact full-precision tiers.

    The param subtree holds **only the four device tensors**; the backing
    lives on the store object itself (``host_view()``), which is exactly
    what keeps device-resident embedding bytes at ``(C + S) * d`` plus two
    int32 maps while ``rows`` grows arbitrarily. Consequences: ``lookup``
    requires prior staging, and ``dense_view`` (the serial/naive-level and
    shard_map paths, which want the whole table on device) raises.
    """

    refreshable = True
    needs_staging = True
    runtime_keys = ("cache", "slot_of_row", "staging", "staging_slot_of_row")

    def __init__(self, spec: FusedEmbeddingSpec, capacity: int,
                 staging_capacity: int | None = None,
                 backing_path: str | os.PathLike | None = None,
                 row_dtype: str | None = None):
        if row_dtype is not None:
            spec = dataclasses.replace(spec, row_dtype=row_dtype)
        super().__init__(spec)
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(min(capacity, spec.rows))
        per_sample = spec.k * spec.multi_hot
        if staging_capacity is None:
            staging_capacity = max(4 * per_sample, 256)
        if staging_capacity < per_sample:
            raise ValueError(
                f"staging_capacity {staging_capacity} < one sample's "
                f"worst-case miss set k*multi_hot = {per_sample}; chunked "
                "serving could never make progress")
        self.staging_capacity = int(min(staging_capacity, spec.rows))
        self.backing_path = os.fspath(backing_path) if backing_path else None
        self._backing: np.ndarray | None = None
        self._backing_scale: np.ndarray | None = None
        if self.quantized:
            self.runtime_keys = ("cache", "cache_scale", "slot_of_row",
                                 "staging", "staging_scale",
                                 "staging_slot_of_row")
        self._counts = np.zeros(spec.rows, dtype=np.int64)
        self._slot_of_row = self._seed_map()
        self.pipeline = PrefetchPipeline(self, self.staging_capacity)
        # cached device staging tensors, reused while the staging area is
        # unchanged (an all-hit batch re-publishes without moving a byte)
        self._staged_dev: tuple[int, dict] | None = None
        self._staging_sharding = None   # set via bind_mesh

    def _seed_map(self) -> np.ndarray:
        m = np.full(self.spec.rows, -1, dtype=np.int32)
        m[:self.capacity] = np.arange(self.capacity, dtype=np.int32)
        return m

    # -- host backing --------------------------------------------------------
    def host_view(self) -> np.ndarray:
        """The (rows, d) backing table — host memory (or disk via mmap).
        *Wire* format: int8 for quantized stores (see
        :meth:`host_scale_view`), ``spec.dtype`` otherwise."""
        if self._backing is None:
            raise RuntimeError("no backing attached yet — call init/adopt "
                               "(or HostBackedStore.open for an existing "
                               "backing_path)")
        return self._backing

    def host_scale_view(self) -> np.ndarray:
        """The (rows, 1) fp32 per-row scale column of a quantized backing
        (the prefetch pipeline stages it alongside each int8 row)."""
        if self._backing_scale is None:
            raise RuntimeError("no quantized backing attached — scales "
                               "exist only for row_dtype='int8' stores "
                               "with a backing")
        return self._backing_scale

    def cache_map_view(self) -> np.ndarray:
        """Host mirror of ``slot_of_row`` (the prefetch worker reads it)."""
        return self._slot_of_row

    @property
    def _scale_path(self) -> str | None:
        """Sidecar file of the mmap tier's per-row scales."""
        return self.backing_path + ".scale" if self.backing_path else None

    def _set_backing(self, table: np.ndarray) -> None:
        table = np.ascontiguousarray(
            np.asarray(table, dtype=np.dtype(self.spec.dtype)))
        if table.shape != (self.spec.rows, self.spec.dim):
            raise ValueError(f"backing shape {table.shape} != "
                             f"{(self.spec.rows, self.spec.dim)}")
        scale = None
        if self.quantized:
            # quantize once; every tier (cache/staging) copies these rows
            table, scale = quant.quantize_rows(table)
            self.stats.quant_rows += int(table.shape[0])
        if self.backing_path is not None:
            mm = np.memmap(self.backing_path, dtype=table.dtype, mode="w+",
                           shape=table.shape)
            mm[:] = table
            mm.flush()
            self._backing = mm
            if scale is not None:
                sm = np.memmap(self._scale_path, dtype=np.float32,
                               mode="w+", shape=scale.shape)
                sm[:] = scale
                sm.flush()
                self._backing_scale = sm
        else:
            self._backing = table
            self._backing_scale = scale

    @classmethod
    def open(cls, spec: FusedEmbeddingSpec, capacity: int,
             backing_path: str | os.PathLike,
             staging_capacity: int | None = None,
             row_dtype: str | None = None,
             mode: str = "r") -> "HostBackedStore":
        """Attach an existing on-disk backing (written by a prior
        :meth:`init`/:meth:`adopt` with the same spec) without copying it
        into RAM — the disk third tier's load path. ``row_dtype`` must
        match what the file was written with (int8 backings carry their
        scales in the ``backing_path + ".scale"`` sidecar). ``mode="r"``
        (default) maps the file read-only — :meth:`apply_deltas` then
        rejects pushes; reopen with ``mode="r+"`` to serve a backing that
        also accepts online trainer deltas."""
        if mode not in ("r", "r+"):
            raise ValueError(f"mode must be 'r' or 'r+', got {mode!r}")
        store = cls(spec, capacity, staging_capacity=staging_capacity,
                    backing_path=backing_path, row_dtype=row_dtype)
        wire = np.int8 if store.quantized else np.dtype(spec.dtype)
        store._backing = np.memmap(store.backing_path, dtype=wire,
                                   mode=mode, shape=(spec.rows, spec.dim))
        if store.quantized:
            store._backing_scale = np.memmap(
                store._scale_path, dtype=np.float32, mode=mode,
                shape=(spec.rows, 1))
        return store

    # -- params --------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        # same canonical table init as every store (value-identical with a
        # DenseStore built from the same key), then moved off device
        table = np.asarray(self.init_dense_table(key))
        self._set_backing(table)
        return self.device_params()

    def from_dense(self, dense_params: dict) -> dict:
        return self.adopt(dense_params)

    def adopt(self, params: dict) -> dict:
        leaf = params.get("mega_table", params.get("backing"))
        if leaf is None:
            raise ValueError("adopt needs a dense ('mega_table') or cached "
                             "('backing') subtree — a host-backed subtree "
                             "has no table to adopt; use open()")
        leaf = np.asarray(leaf)
        if "backing_scale" in params and leaf.dtype != np.float32:
            # an already-quantized cached subtree: reconstitute fp rows so
            # _set_backing canonicalizes (and re-quantizes on-grid values)
            leaf = quant.dequantize_rows(
                unpack_rows(leaf, self.spec.dim, np.int8),
                np.asarray(params["backing_scale"]))
        else:
            leaf = unpack_rows(leaf, self.spec.dim, self.spec.dtype)
        self._set_backing(leaf)
        return self.device_params()

    def device_params(self) -> dict:
        """Build the device subtree (four tensors, six when quantized)
        from the current backing + index maps (cache rows are verbatim
        backing copies — of the int8 grid, for quantized stores)."""
        backing = self.host_view()
        hot = np.flatnonzero(self._slot_of_row >= 0)
        cached_rows = hot[np.argsort(self._slot_of_row[hot])]
        if cached_rows.size != self.capacity:
            raise ValueError(f"index map holds {cached_rows.size} slots, "
                             f"capacity is {self.capacity}")
        out = {"cache": jnp.asarray(pack_rows(backing[cached_rows])),
               "slot_of_row": jnp.asarray(self._slot_of_row),
               **self._staging_leaves()}
        if self.quantized:
            out["cache_scale"] = jnp.asarray(
                self.host_scale_view()[cached_rows])
        return out

    def bind_mesh(self, mesh, model_axis: str | None = "model") -> None:
        """Make per-batch staging uploads land replicated on ``mesh`` (the
        engine calls this once at construction). Refresh-built tensors go
        through :meth:`place` as for any store; this covers the stage-time
        publishes, so the params an engine holds never mix single-device
        staging tensors into an otherwise mesh-placed tree."""
        if mesh is None:
            self._staging_sharding = None
        else:
            from jax.sharding import NamedSharding
            self._staging_sharding = NamedSharding(mesh, P())
        self._staged_dev = None

    def _staging_leaves(self) -> dict:
        """Device staging leaves for the pipeline's current state (incl.
        the scale sidecar when quantized), reusing the previous upload
        when the staging area hasn't changed."""
        buf, sbuf, smap, version = self.pipeline.snapshot()
        if self._staged_dev is not None and self._staged_dev[0] == version:
            return self._staged_dev[1]
        if self._staging_sharding is not None:
            put = lambda a: jax.device_put(a, self._staging_sharding)
        else:
            put = jnp.asarray
        leaves = {"staging": put(pack_rows(buf)),
                  "staging_slot_of_row": put(smap)}
        if sbuf is not None:
            leaves["staging_scale"] = put(sbuf)
        self._staged_dev = (version, leaves)
        return leaves

    def partition_spec(self, model_axis: str | None = "model") -> dict:
        """Every device leaf is small and latency-critical — replicated
        (scales placed like ``slot_of_row``). The backing never appears
        here: it is host state, not a param."""
        return {k: P() for k in self.runtime_keys}

    def dense_view(self, params: dict) -> jax.Array:
        raise NotImplementedError(
            "HostBackedStore keeps the backing table host-side; there is "
            "no device-resident dense view (that ceiling is the point). "
            "Use host_view() for host-side access, or a DenseStore/"
            "CachedStore for paths that need the whole table on device "
            "(serial baselines, the 'naive' level).")

    # -- staging (the per-batch miss pipeline) -------------------------------
    def _global_rows(self, ids, mask=None) -> np.ndarray:
        """Local (…, k[, h]) ids -> clipped global rows, masked slots
        dropped (their lookup is zero-guarded, nothing to stage)."""
        ids = np.asarray(ids, dtype=np.int64)
        if ids.ndim == 1:
            ids = ids[None, :]
        offs = self.spec.offsets
        rows = ids + (offs[None, :] if ids.ndim == 2 else offs[None, :, None])
        if mask is not None:
            rows = rows[np.asarray(mask).astype(bool)]
        return np.clip(rows.reshape(-1), 0, self.spec.rows - 1)

    def miss_rows(self, ids, mask=None) -> np.ndarray:
        """Distinct global rows of this batch absent from the device cache
        (the set the staging buffer must resolve)."""
        rows = np.unique(self._global_rows(ids, mask))
        return rows[self._slot_of_row[rows] < 0]

    def stage(self, params: dict, ids, mask=None) -> dict:
        """Resolve this batch's cache misses into the staging buffer and
        return the param subtree with fresh staging tensors.

        The host gather only touches rows the async prefetch worker hasn't
        already staged (those count as prefetch hits in ``stats``); the
        device upload is skipped entirely when the staging area is
        unchanged. Raises :class:`StagingOverflowError` when the distinct
        miss set exceeds the buffer — callers serve in
        :meth:`split_for_staging` chunks instead.
        """
        miss = self.miss_rows(ids, mask)
        try:
            staged, already = self.pipeline.ensure(miss)
        except StagingOverflowError:
            self.stats.staging_overflows += 1
            raise
        self.stats.staged_rows += staged
        self.stats.prefetched_rows += already
        # wire bytes: what the staging upload actually moves per row
        # (d + 4 for int8 rows + their scale, 4·d full-precision)
        self.stats.h2d_bytes += staged * self.wire_row_bytes
        return {**params, **self._staging_leaves()}

    def prefetch_hint(self, ids, mask=None) -> None:
        """Queue an upcoming batch's rows for speculative off-thread
        staging (the engine calls this with batch t+1's rows while batch
        t's dense compute runs)."""
        self.pipeline.hint(self._global_rows(ids, mask))

    def split_for_staging(self, ids) -> list:
        """Split a (b, k) batch into row-contiguous chunks whose distinct
        miss sets each fit the staging buffer — the synchronous fallback
        for miss storms. Greedy; singleton chunks always fit because
        ``staging_capacity >= k * multi_hot``."""
        ids = np.asarray(ids)
        if ids.ndim == 1:
            ids = ids[None, :]
        chunks, start, covered = [], 0, set()
        for i in range(ids.shape[0]):
            miss = set(self.miss_rows(ids[i:i + 1]).tolist())
            if i > start and len(covered | miss) > self.staging_capacity:
                chunks.append(ids[start:i])
                start, covered = i, miss
            else:
                covered |= miss
        chunks.append(ids[start:])
        return chunks

    # -- lookup --------------------------------------------------------------
    def lookup(self, params: dict, ids: jax.Array, offsets: jax.Array, *,
               strategy: str = "auto",
               interpret: bool | None = None) -> jax.Array:
        if self.quantized:
            return kops.multi_table_lookup_host_q8(
                ids, params["cache"], params["cache_scale"],
                params["staging"], params["staging_scale"],
                params["slot_of_row"], params["staging_slot_of_row"],
                offsets, strategy=strategy, interpret=interpret,
                dim=self.spec.dim)
        return kops.multi_table_lookup_host(
            ids, params["cache"], params["staging"], params["slot_of_row"],
            params["staging_slot_of_row"], offsets,
            strategy=strategy, interpret=interpret, dim=self.spec.dim)

    def lookup_multihot(self, params: dict, ids: jax.Array, mask: jax.Array,
                        offsets: jax.Array, *, strategy: str = "auto",
                        interpret: bool | None = None) -> jax.Array:
        if self.quantized:
            return kops.multi_table_lookup_host_q8_multihot(
                ids, mask, params["cache"], params["cache_scale"],
                params["staging"], params["staging_scale"],
                params["slot_of_row"], params["staging_slot_of_row"],
                offsets, strategy=strategy, interpret=interpret,
                dim=self.spec.dim)
        return kops.multi_table_lookup_host_multihot(
            ids, mask, params["cache"], params["staging"],
            params["slot_of_row"], params["staging_slot_of_row"], offsets,
            strategy=strategy, interpret=interpret, dim=self.spec.dim)

    # -- traffic / cache management ------------------------------------------
    def observe(self, global_rows: np.ndarray) -> None:
        rows = np.clip(np.asarray(global_rows).reshape(-1),
                       0, self._counts.size - 1)
        np.add.at(self._counts, rows, 1)
        hits = int((self._slot_of_row[rows] >= 0).sum())
        self.stats.hits += hits
        self.stats.misses += rows.size - hits
        self._observe_traffic(rows)

    def refresh(self, params: dict) -> dict:
        """Re-admit the C most frequent observed rows into the device
        cache (deterministic tie-break by row id), gathering their values
        from the *host* backing, and evict the promoted rows from staging
        — hot staged rows graduate to the cache tier. Returns the full
        fresh subtree for the double-buffered publish."""
        order = np.lexsort((np.arange(self._counts.size), -self._counts))
        hot = np.sort(order[:self.capacity]).astype(np.int32)
        new_map = np.full(self._counts.size, -1, dtype=np.int32)
        new_map[hot] = np.arange(self.capacity, dtype=np.int32)
        self._slot_of_row = new_map
        self.pipeline.drop(hot)
        self.stats.refreshes += 1
        return self.device_params()

    def apply_deltas(self, params: dict, row_ids, new_rows
                     ) -> tuple[dict, int]:
        """Write online trainer deltas through all three tiers.

        The host backing (RAM array or writable memmap) is updated in
        place — under the prefetch pipeline's staging lock, so a
        concurrent ``ensure``/``hint`` gather can never see a half-written
        row — and any of the updated rows already sitting in staging slots
        are re-gathered before the lock drops (stale staged copies would
        otherwise serve until eviction). Cached rows get their device
        cache slot rewritten functionally, and the returned subtree
        carries fresh staging leaves (the pipeline version bump forces the
        re-upload). Quantized stores re-quantize the incoming fp32 rows
        once, updating the scale sidecar alongside the int8 payload.

        One sharing caveat the A/B scenario must know: unlike
        ``CachedStore`` — whose device tensors are immutable, so a second
        engine's published subtree stays pinned pre-delta — the host
        backing is *store state shared by every engine serving through
        this object*; staged rows re-gathered after a delta see the new
        values on every engine. Version-pinned A/B needs device-resident
        stores (or two host stores over separate backings).
        """
        rows_idx, vals = validate_deltas(self.spec, row_ids, new_rows)
        n = int(rows_idx.size)
        if n == 0:
            return params, 0
        backing = self.host_view()
        if not backing.flags.writeable:
            if isinstance(backing, np.memmap):
                raise ValueError(
                    "host backing is a read-only memmap "
                    "(HostBackedStore.open defaults to mode='r'); reopen "
                    "with mode='r+' to accept online deltas")
            # adopt() aliased the source table zero-copy (np.asarray of a
            # device array is read-only): promote to a private writable
            # copy once, on the first push
            self._backing = backing = backing.copy()
        if self.quantized:
            q, scale = quant.quantize_rows(np.asarray(vals))
            self.stats.quant_rows += n
            wire = q

            def write():
                backing[rows_idx] = q
                self.host_scale_view()[rows_idx] = scale
        else:
            wire = np.asarray(vals)

            def write():
                backing[rows_idx] = wire
        self.pipeline.apply_backing_update(rows_idx, write)
        out = dict(params)
        slots = self._slot_of_row[rows_idx]
        cached = np.flatnonzero(slots >= 0)
        if cached.size:
            cidx = jnp.asarray(slots[cached])
            out["cache"] = set_rows(params["cache"], slots[cached],
                                    wire[cached])
            if self.quantized:
                out["cache_scale"] = params["cache_scale"].at[cidx].set(
                    jnp.asarray(scale[cached]))
        # fresh staging leaves: a bumped pipeline version re-uploads the
        # refreshed slots; untouched staging reuses the previous upload
        out.update(self._staging_leaves())
        self.stats.delta_rows += n
        return out, n

    @property
    def cached_traffic_fraction(self) -> float:
        total = int(self._counts.sum())
        if not total:
            return 0.0
        return float(self._counts[self._slot_of_row >= 0].sum()) / total

    def device_bytes(self, params: dict) -> int:
        """Bytes of embedding state resident on device — the budget the
        benchmark asserts stays put while ``rows`` grows (cache + staging
        rows plus the two int32 maps; the backing is absent)."""
        return sum(int(np.prod(params[k].shape)
                       * np.dtype(params[k].dtype).itemsize)
                   for k in self.runtime_keys)

    def describe(self) -> str:
        tier3 = ",mmap" if self.backing_path else ""
        q = ",int8" if self.quantized else ""
        return (f"host(C={self.capacity},S={self.staging_capacity},"
                f"rows={self.spec.rows},d={self.spec.dim}{tier3}{q})")
