"""FusedEmbeddingCollection — store-backed realization of paper Alg. 1.

All k per-field embedding tables are concatenated row-wise into ONE
mega-table; per-field ids become global rows via static offsets. One gather
(Pallas on TPU / single XLA gather on CPU) replaces k serial lookups —
contribution C2, with C3's output-first allocation inside the kernel.

Where the mega-table *lives* is the store's business
(:mod:`repro.embedding.store`): ``DenseStore`` holds it as one fast-memory
leaf, ``CachedStore`` splits it into a device-resident hot-row cache plus a
backing table. The collection delegates parameter init/placement and every
lookup to its store, so models, plans, and engines never see the tiers.

Distribution: the dense table (or the cached store's backing tier) is
*row-sharded* over the ``model`` mesh axis (vocab-parallel). Inside a mesh
plan, :meth:`FusedEmbeddingCollection.apply` runs the store's
``lookup_sharded``: the masked-local-gather + psum pattern under
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

__all__ = ["FusedEmbeddingCollection", "sharded_vocab_lookup"]


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
        """ids (b, k) -> (b, k*d). Inside ``kops.mesh_context`` (a plan
        traced for a mesh with a model axis), stores with a vocab-parallel
        lookup run it."""
        ctx = kops.active_mesh()
        if (ctx is not None and self.store.mesh_leaves
                and ctx[1] in ctx[0].axis_names):
            return self.store.lookup_sharded(params, ids, self._offsets,
                                             *ctx, strategy=strategy,
                                             interpret=interpret)
        return self.store.lookup(params, ids, self._offsets,
                                 strategy=strategy, interpret=interpret)

    def apply_multihot(self, params: dict, ids: jax.Array, mask: jax.Array,
                       *, strategy: str = "auto",
                       interpret: bool | None = None) -> jax.Array:
        """ids/mask (b, k, h) -> (b, k*d) sum-pooled."""
        return self.store.lookup_multihot(params, ids, mask, self._offsets,
                                          strategy=strategy,
                                          interpret=interpret)

    def apply_serial(self, params: dict, ids: jax.Array) -> jax.Array:
        """Baseline: k separate gathers + concat (PyTorch-A analogue)."""
        return kops.multi_table_lookup_serial(
            ids, self.store.dense_view(params), self._offsets)

    # -- traffic observation -------------------------------------------------
    def observe(self, ids: np.ndarray) -> None:
        """Feed served (b, k) id traffic to the store's admission counters
        (host-side numpy; call outside jit — engines do)."""
        ids = np.asarray(ids, dtype=np.int64)
        if ids.ndim == 1:
            ids = ids[None, :]
        self.store.observe(ids + self.spec.offsets[None, :])


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
