"""FusedEmbeddingCollection — store-backed realization of paper Alg. 1.

All k per-field embedding tables are concatenated row-wise into ONE
mega-table; per-field ids become global rows via static offsets. One gather
replaces k serial lookups — contribution C2, with C3's output-first
allocation inside the kernel. A multi-hot field is ``h`` id slots at its
field's offset in that same gather, summed afterwards (:func:`pool_slots`).

Where the mega-table *lives* is the store's business
(:mod:`repro.embedding.store`): ``DenseStore`` holds it as one fast-memory
leaf, ``CachedStore`` splits it into a device-resident hot-row cache plus a
backing table, ``HostBackedStore`` keeps the backing in host memory. The
collection delegates parameter init/placement and every lookup to its
store, so models, plans, and engines never see the tiers. Every lookup
takes one path: ``store._route`` picks each slot's tier, row and int8
scale, and ``kops.gather_tiers`` gathers them (the Pallas kernel
``mtl_gather_tiered`` on TPU, its jnp twin elsewhere).

Distribution: the dense table (or the cached store's backing tier) is
*row-sharded* over the ``model`` mesh axis (vocab-parallel). Inside a mesh
plan, :meth:`FusedEmbeddingCollection.apply` runs the store's
``lookup_sharded``: the same route per shard, then a psum, under
``shard_map`` — the multi-chip generalization of Alg. 1.
:func:`sharded_vocab_lookup` is the same pattern for LM vocab embeddings
(a 1-table degenerate case).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

from .spec import FusedEmbeddingSpec
from .store import DenseStore, EmbeddingStore

__all__ = ["FusedEmbeddingCollection", "pool_slots", "sharded_vocab_lookup"]


class FusedEmbeddingCollection:
    """Lookup front-end over a pluggable :class:`EmbeddingStore`."""

    def __init__(self, spec: FusedEmbeddingSpec,
                 store: EmbeddingStore | None = None):
        self.spec = spec
        self.store = store if store is not None else DenseStore(spec)
        # row_dtype is the store's wire-format choice, not part of the
        # model's schema — two specs differing only there are compatible
        if dataclasses.replace(self.store.spec, row_dtype=None) != \
                dataclasses.replace(spec, row_dtype=None):
            raise ValueError("store was built for a different embedding "
                             f"spec: {self.store.spec} != {spec}")
        self._offsets = jnp.asarray(spec.offsets)
        self._slot_offsets = jnp.asarray(spec.slot_offsets)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        return self.store.init(key)

    def partition_spec(self, model_axis: str | None = "model") -> dict:
        """Mesh placement of the store's param subtree (vocab-parallel
        tables; cache tiers replicated)."""
        return self.store.partition_spec(model_axis)

    def dense_view(self, params: dict) -> jax.Array:
        """The full (rows, d) table, whichever tier holds it."""
        return self.store.dense_view(params)

    # -- single-device / replicated lookup ----------------------------------
    def apply(self, params: dict, ids: jax.Array, *,
              strategy: str = "auto", interpret: bool | None = None
              ) -> jax.Array:
        """ids (b, slots) -> (b, k*d). Each id slot is looked up at its
        field's offset in one gather (one slot a field: the plain fused
        lookup); with ``spec.hotness`` each field's slots are then summed
        in float32 (:func:`pool_slots`). Inside ``kops.mesh_context`` (a
        plan traced for a mesh with a model axis), stores with a
        vocab-parallel lookup run it."""
        ctx = kops.active_mesh()
        if (ctx is not None and self.store.mesh_leaves
                and ctx[1] in ctx[0].axis_names):
            rows = self.store.lookup_sharded(params, ids, self._slot_offsets,
                                             *ctx, strategy=strategy,
                                             interpret=interpret)
        else:
            rows = self.store.lookup(params, ids, self._slot_offsets,
                                     strategy=strategy, interpret=interpret)
        if self.spec.hotness is None:
            return rows
        return pool_slots(rows, self.spec.hotness, self.spec.dim)

    def apply_serial(self, params: dict, ids: jax.Array) -> jax.Array:
        """Baseline: k separate gathers + concat (PyTorch-A analogue)."""
        return kops.multi_table_lookup_serial(
            ids, self.store.dense_view(params), self._offsets)

    # -- traffic observation -------------------------------------------------
    def observe(self, ids: np.ndarray) -> None:
        """Feed served (b, slots) id traffic to the store's admission
        counters, each slot at its field's offset (host-side numpy; call
        outside jit — engines do)."""
        ids = np.asarray(ids, dtype=np.int64)
        if ids.ndim == 1:
            ids = ids[None, :]
        self.store.observe(ids + self.spec.slot_offsets[None, :])


def pool_slots(rows: jax.Array, hotness, dim: int) -> jax.Array:
    """``(b, Σh·d)`` per-slot rows -> ``(b, k·d)``: field ``i``'s ``h_i``
    adjacent slots summed, in XLA after the store's one-row-a-slot gather.
    One pooled gather call per distinct hotness read the lookup 1.7×
    faster alone on a v5e, but only ~5% more requests a second end to end
    (the host drain bounds the step) at 43 s of compile a run, twelve
    kernels unrolled over up to 100 slots (PERF.md, Findings)."""
    x = rows.reshape(rows.shape[0], -1, dim)
    ends = np.cumsum(hotness)
    return jnp.concatenate([jnp.sum(x[:, e - h:e], axis=1)
                            for h, e in zip(hotness, ends)], axis=1)


def sharded_vocab_lookup(table: jax.Array, ids: jax.Array, *,
                         model_axis: str = "model") -> jax.Array:
    """shard_map-interior vocab-parallel lookup (LM embedding reuse).

    Call *inside* an existing shard_map / with sharded ``table`` rows:
    masked local gather + psum over ``model_axis``.

    Args:
        table: (rows_per_shard, d) local shard of the embedding table.
        ids:   (...,) global token ids.

    Returns:
        (..., d) embeddings, replicated over the model axis.
    """
    shard_rows = table.shape[0]
    axis_idx = jax.lax.axis_index(model_axis)
    lo = axis_idx * shard_rows
    local = ids.astype(jnp.int32) - lo
    valid = (local >= 0) & (local < shard_rows)
    safe = jnp.where(valid, local, 0)
    vals = jnp.take(table, safe.reshape(-1), axis=0)
    vals = vals.reshape(*ids.shape, table.shape[1])
    vals = jnp.where(valid[..., None], vals, 0)
    return jax.lax.psum(vals, axis_name=model_axis)
