"""CachedStore — HugeCTR-style hot-row cache over a backing mega-table.

Two tiers, one index map:

  ``backing``     (rows, d)  the full mega-table (conceptually host/HBM).
  ``cache``       (C, d)     device-resident copies of the C hottest rows.
                             (Both packed lane-dense, like every device
                             table — ``EmbeddingStore.unpack``.)
  ``slot_of_row`` (rows,)    int32 index map: cache slot of each global
                             row, -1 when the row is not cached.

A lookup is one *two-level gather*: :meth:`CachedStore._route` sends
cached rows to the cache tier and misses to the backing tier, and one
``gather_tiers`` call reads each slot from its tier only — on TPU the
Pallas gather ``mtl_gather_tiered`` (one DMA per slot, from the tier the
slot map chose), on CPU its identical-math jnp twin.

Bit-exactness by construction: cache rows are verbatim copies of backing
rows, so ``CachedStore`` and ``DenseStore`` built from the same key are
value-identical on every input — which cache state is live only changes
*where* a row is read from, never what is read (paper Table I discipline).

Admission/refresh follows the zipf skew of observed traffic: the store
counts served row frequencies host-side (``observe``), and ``refresh``
rebuilds the cache with the C most frequent rows (deterministic tie-break
by row id). Until the first refresh the cache seeds with the lowest C row
ids — the right prior for CTR id streams, where popular items cluster at
small ids (both the synthetic quadratic skew and zipf traffic do).

Quantized tier (``row_dtype="int8"``): both tiers hold int8 rows with one
fp32 scale per row (``backing_scale (rows, 1)`` / ``cache_scale (C, 1)``,
symmetric absmax via ``repro.quant``), quantized **once** at init/adopt —
cache rows stay verbatim copies of quantized backing rows, so tier choice
still never changes values *within the int8 representation*; what relaxes
is fp32 bit-exactness (round-trip error ≤ scale/2 per element, gated
model-level by ``benchmarks/accuracy_parity.py --quant``). The gather
moves ``d + 4`` bytes per row instead of ``4·d`` and dequantizes in-kernel
(``_route`` hands it each slot's scale with its row). Scales are runtime
inputs like everything else, so refresh stays recompile-free.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import quant
from repro.kernels.multi_table_lookup import pack_rows, set_rows

from .spec import FusedEmbeddingSpec
from .store import EmbeddingStore, validate_deltas

__all__ = ["CachedStore"]


class CachedStore(EmbeddingStore):
    """Hot-row cache of capacity ``C`` rows over the full backing table.

    The store keeps a host-side mirror of the index map plus per-row
    traffic counts; ``refresh`` is the only operation that changes cache
    contents, and it returns a *new* param subtree — a double buffer:
    the fresh cache/index tensors are built on the side while readers
    keep serving from the old ones, then the caller publishes the new
    subtree in one reference swap (``InferenceEngine.refresh_cache``).
    Because all three tensors are ``runtime_keys``, compiled plans take
    them as per-call inputs and survive the swap untouched — a refresh
    costs two device uploads, never a recompile.

    Multi-chip: ``partition_spec`` keeps ``backing`` row-sharded
    (vocab-parallel over the model axis) with ``cache``/``slot_of_row``
    replicated. ``refresh`` works on a *placed* backing unchanged — the
    eager gather in ``_with_cache`` reads across shards — and the caller
    (``InferenceEngine.refresh_cache``) republishes the fresh subtree
    through :meth:`EmbeddingStore.place` so the swap lands on the exact
    shardings every compiled plan was lowered against.
    """

    refreshable = True
    runtime_keys = ("cache", "backing", "slot_of_row")

    def __init__(self, spec: FusedEmbeddingSpec, capacity: int,
                 row_dtype: str | None = None):
        if row_dtype is not None:
            spec = dataclasses.replace(spec, row_dtype=row_dtype)
        super().__init__(spec)
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(min(capacity, spec.rows))
        self._counts = np.zeros(spec.rows, dtype=np.int64)
        self._slot_of_row = self._seed_map()
        if self.quantized:
            # scales are plan runtime inputs exactly like their rows, so a
            # refresh republishes them through the same recompile-free swap
            self.runtime_keys = ("cache", "cache_scale", "backing",
                                 "backing_scale", "slot_of_row")
            self.mesh_leaves = ("cache", "slot_of_row", "cache_scale",
                                "backing_scale", "backing")

    def _seed_map(self) -> np.ndarray:
        m = np.full(self.spec.rows, -1, dtype=np.int32)
        m[:self.capacity] = np.arange(self.capacity, dtype=np.int32)
        return m

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        return self.from_dense(
            {"mega_table": pack_rows(self.init_dense_table(key))})

    def from_dense(self, dense_params: dict) -> dict:
        """Adopt a DenseStore subtree (``{"mega_table": table}``) into the
        tiered layout, caching per the store's current index map. Quantized
        stores quantize the whole table here, **once** — every later
        refresh reuses these rows/scales, so tier contents stay verbatim
        copies of one quantization."""
        backing = dense_params["mega_table"]
        backing_scale = None
        if self.quantized:
            backing, backing_scale = self._quantize_table(backing)
        return self._with_cache(backing, self._slot_of_row, backing_scale)

    def adopt(self, params: dict) -> dict:
        if "backing" not in params:
            return self.from_dense(params)
        backing = params["backing"]
        if self.quantized and backing.dtype == jnp.float32:
            backing, backing_scale = self._quantize_table(backing)
        else:
            backing_scale = params.get("backing_scale")
        return self._with_cache(backing, self._slot_of_row, backing_scale)

    def _quantize_table(self, lines: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
        """Packed fp32 table -> (packed int8 table, (rows, 1) scales)."""
        table = lines.reshape(-1, self.spec.dim)
        q, scale = quant.quantize_rows(table)
        self.stats.quant_rows += int(table.shape[0])
        return pack_rows(q), scale

    def _with_cache(self, backing: jax.Array, slot_of_row: np.ndarray,
                    backing_scale: jax.Array | None = None) -> dict:
        hot = np.flatnonzero(slot_of_row >= 0)
        cached_rows = hot[np.argsort(slot_of_row[hot])]   # row of slot s
        if cached_rows.size != self.capacity:
            raise ValueError(f"index map holds {cached_rows.size} slots, "
                             f"capacity is {self.capacity}")
        rows = jnp.asarray(cached_rows)
        out = {"backing": backing,
               "cache": pack_rows(self.gather_rows(backing, rows)),
               "slot_of_row": jnp.asarray(slot_of_row)}
        if self.quantized:
            if backing_scale is None:
                raise ValueError("quantized store needs backing_scale "
                                 "alongside its int8 backing")
            out["backing_scale"] = backing_scale
            out["cache_scale"] = jnp.take(backing_scale, rows, axis=0)
        return out

    def partition_spec(self, model_axis: str | None = "model") -> dict:
        """Backing row-sharded (vocab-parallel); the hot cache, the index
        map, and the per-row scales are small and latency-critical —
        replicated (scales placed like ``slot_of_row``)."""
        spec = {"backing": P(model_axis, None),
                "cache": P(),
                "slot_of_row": P()}
        if self.quantized:
            spec["backing_scale"] = P()
            spec["cache_scale"] = P()
        return spec

    def dense_view(self, params: dict) -> jax.Array:
        if self.quantized:
            # the naive level / serial baselines want fp32 rows — rebuild
            # them from the int8 grid so every path sees identical values
            return quant.dequantize_rows(
                self.unpack(params["backing"]),
                params["backing_scale"]).astype(jnp.dtype(self.spec.dtype))
        return self.unpack(params["backing"])

    # -- lookup ------------------------------------------------------------
    mesh_leaves = ("cache", "slot_of_row", "backing")

    def _route(self, env, rows, local, own, first):
        # hits: the replicated cache, served by model shard 0 only;
        # misses: the backing row, served by the shard holding it
        slots = jnp.take(env["slot_of_row"], rows, axis=0)
        hit = slots >= 0
        tier = jnp.where(hit, jnp.where(first, 0, -1),
                         jnp.where(own, 1, -1))
        row = jnp.where(hit, slots, local)
        scale = None
        if self.quantized:
            # the backing's scales are replicated: indexed by global row
            scale = jnp.where(
                hit,
                jnp.take(env["cache_scale"][:, 0], jnp.maximum(slots, 0),
                         mode="clip"),
                jnp.take(env["backing_scale"][:, 0], rows, mode="clip"))
        return tier, row, (env["cache"], env["backing"]), scale

    # -- traffic / cache management ---------------------------------------
    def observe(self, global_rows: np.ndarray) -> None:
        # clip like the gather does (jnp.take clamps), so one malformed id
        # can't wedge the serving loop; O(b·k) — no full-vocab allocation
        # per batch (np.bincount(minlength=rows) would be O(vocab))
        rows = np.clip(np.asarray(global_rows).reshape(-1),
                       0, self._counts.size - 1)
        np.add.at(self._counts, rows, 1)
        hits = int((self._slot_of_row[rows] >= 0).sum())
        self.stats.hits += hits
        self.stats.misses += rows.size - hits
        self._observe_traffic(rows)

    def refresh(self, params: dict) -> dict:
        """Re-admit the C most frequent observed rows (ties -> lower row id
        wins, so refresh is deterministic for any traffic history)."""
        order = np.lexsort((np.arange(self._counts.size), -self._counts))
        hot = np.sort(order[:self.capacity]).astype(np.int32)
        new_map = np.full(self._counts.size, -1, dtype=np.int32)
        new_map[hot] = np.arange(self.capacity, dtype=np.int32)
        self._slot_of_row = new_map
        self.stats.refreshes += 1
        return self._with_cache(params["backing"], new_map,
                                params.get("backing_scale"))

    def apply_deltas(self, params: dict, row_ids, new_rows
                     ) -> tuple[dict, int]:
        """Scatter online trainer deltas into backing **and** cache.

        Functional (``.at[].set`` builds new arrays): the subtree handed
        back shares every untouched row with the old one, and the caller
        publishes it through the double-buffered swap — readers of the old
        subtree keep a consistent pre-delta view, so a torn update is
        impossible by construction. Rows currently cached get their cache
        slot rewritten too (cache rows stay verbatim copies of backing
        rows — the tier invariant deltas must preserve); the index map is
        untouched, so admission state survives value updates. Quantized
        stores re-quantize the incoming fp32 rows **once** here
        (``repro.quant``), updating the per-row scales alongside the int8
        payloads.
        """
        rows_idx, vals = validate_deltas(self.spec, row_ids, new_rows)
        n = int(rows_idx.size)
        if n == 0:
            return params, 0
        idx = jnp.asarray(rows_idx)
        out = dict(params)
        if self.quantized:
            q, scale = quant.quantize_rows(np.asarray(vals))
            self.stats.quant_rows += n
            wire = q
            out["backing_scale"] = \
                params["backing_scale"].at[idx].set(jnp.asarray(scale))
        else:
            wire = np.asarray(vals)
        out["backing"] = set_rows(params["backing"], rows_idx, wire)
        slots = self._slot_of_row[rows_idx]
        cached = np.flatnonzero(slots >= 0)
        if cached.size:
            cidx = jnp.asarray(slots[cached])
            out["cache"] = set_rows(params["cache"], slots[cached],
                                    wire[cached])
            if self.quantized:
                out["cache_scale"] = params["cache_scale"].at[cidx].set(
                    jnp.asarray(scale[cached]))
        self.stats.delta_rows += n
        return out, n

    @property
    def cached_traffic_fraction(self) -> float:
        """Share of observed traffic mass landing on currently-cached rows
        — the counter that grows with skew at fixed capacity (zipf mass
        concentrates in the top-C). O(rows): read it lazily (refresh time,
        stats dumps), not per served batch — engines do."""
        total = int(self._counts.sum())
        if not total:
            return 0.0
        return float(self._counts[self._slot_of_row >= 0].sum()) / total

    def describe(self) -> str:
        q = ",int8" if self.quantized else ""
        return (f"cached(C={self.capacity},rows={self.spec.rows},"
                f"d={self.spec.dim}{q})")
