"""Public jit'd wrappers for the DPIFrame kernels, with strategy dispatch.

The wrappers hide the backend question: on a TPU the Pallas kernels run
compiled; on this CPU container the same math runs either through
``interpret=True`` (kernel-body validation) or through the vectorized jnp
fused path (identical algorithm at the XLA level — one gather over the
mega-table — which is what the CPU benchmarks time).

Strategies for the lookup wrappers:

  "auto"        pallas on TPU (outside a mesh program), jnp elsewhere
  "pallas"      output-first Pallas gather (Alg. 1)          [C2+C3]
  "jnp"         vectorized single-gather over the mega-table [C2 at XLA level]

Every lookup wrapper takes its tables packed lane-dense (the layout the
stores keep, ``multi_table_lookup.pack_rows``) plus their row width
``dim``. The benchmark strawmen take a logical ``(N, d)`` table instead:
:func:`multi_table_lookup_serial` (per-field loop + concat, the paper's
PyTorch baseline), :func:`multi_table_lookup_input_first` (Fig.-11
field-major writes + transpose) and :func:`multi_table_lookup_onehot`
(one-hot MXU matmul over small stacked fields).
"""

from __future__ import annotations

import contextlib
import contextvars

import numpy as np
import jax
import jax.numpy as jnp

from repro import quant

from . import ref
from .dense_matmul import dmm_q8
from .fused_cross import fused_cross_v1, fused_cross_v2
from .fused_fm import fused_fm_second_order
from .multi_table_lookup import (
    mtl_gather,
    mtl_gather_tiered,
    rows_per_line,
    unpack_rows,
    mtl_gather_multihot,
    mtl_gather_three_level,
    mtl_gather_three_level_q8,
    mtl_gather_two_level,
    mtl_gather_two_level_q8,
    mtl_input_first,
    mtl_onehot,
)

__all__ = [
    "multi_table_lookup",
    "multi_table_lookup_serial",
    "multi_table_lookup_input_first",
    "multi_table_lookup_onehot",
    "multi_table_lookup_multihot",
    "multi_table_lookup_cached",
    "multi_table_lookup_cached_multihot",
    "multi_table_lookup_cached_q8",
    "multi_table_lookup_cached_q8_multihot",
    "multi_table_lookup_host",
    "multi_table_lookup_host_multihot",
    "multi_table_lookup_host_q8",
    "multi_table_lookup_host_q8_multihot",
    "dense_matmul_q8",
    "fused_cross_v1",
    "fused_cross_v2",
    "fused_fm_second_order",
    "on_tpu",
    "use_pallas",
    "mesh_context",
    "active_mesh",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: (mesh, model_axis) while a plan for that mesh is being traced
_MESH = contextvars.ContextVar("plan_mesh", default=None)


@contextlib.contextmanager
def mesh_context(mesh, model_axis: str = "model"):
    """Mark the code traced inside as one GSPMD program over ``mesh``.

    A Pallas kernel cannot be partitioned by GSPMD, so inside this context
    ``strategy="auto"`` resolves to the jnp twins, and embedding lookups
    run as vocab-parallel ``shard_map`` programs whose per-shard gathers
    are the Pallas kernels again (``EmbeddingStore.lookup_sharded``).
    ``compile_plan`` wraps a mesh plan's lowering in this."""
    token = _MESH.set(None if mesh is None else (mesh, model_axis))
    try:
        yield
    finally:
        _MESH.reset(token)


def active_mesh():
    """``(mesh, model_axis)`` inside :func:`mesh_context`, else None."""
    return _MESH.get()


def use_pallas() -> bool:
    """What ``strategy="auto"`` means here: the Pallas kernels on a TPU,
    outside a mesh program; the jnp twins otherwise."""
    return on_tpu() and active_mesh() is None


def _logical(table: jax.Array, dim: int, dtype=None) -> jax.Array:
    """The ``(N, d)`` row view of a packed table, for the jnp twins.
    ``dtype`` defaults to the packed dtype."""
    return unpack_rows(table, dim, dtype or table.dtype)


def _flat_rows(ids: jax.Array, offsets: jax.Array) -> jax.Array:
    """Alg. 1 lines 6–7 vectorized: local id -> global mega-table row."""
    return (ids.astype(jnp.int32) + offsets[None, :].astype(jnp.int32)).reshape(-1)


def _resolve(strategy: str, interpret: bool | None) -> tuple[str, bool]:
    if interpret is None:
        interpret = not on_tpu()
    if strategy == "auto":
        strategy = "pallas" if use_pallas() else "jnp"
    if strategy not in ("jnp", "pallas"):
        raise ValueError(f"unknown strategy {strategy!r}")
    return strategy, interpret


def multi_table_lookup(ids: jax.Array, mega_table: jax.Array,
                       offsets: jax.Array, *, dim: int,
                       strategy: str = "auto",
                       interpret: bool | None = None) -> jax.Array:
    """Fused multi-table embedding lookup (paper Algorithm 1).

    Args:
        ids:        (b, k) int32 per-field local ids.
        mega_table: the (N, d) concatenated tables, packed
                    (``multi_table_lookup.pack_rows``).
        offsets:    (k,) int32 starting row of each table.
        dim:        row width d.
        strategy:   see module docstring.
        interpret:  force Pallas interpret mode (defaults to not-on-TPU).

    Returns:
        (b, k*d) embedding output.
    """
    b, k = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    if strategy == "jnp":
        return ref.ref_multi_table_lookup(ids, _logical(mega_table, dim),
                                          offsets, k)
    return mtl_gather(_flat_rows(ids, offsets), mega_table, dim=dim,
                      interpret=interpret).reshape(b, k * dim)


def multi_table_lookup_serial(ids: jax.Array, table: jax.Array,
                              offsets: jax.Array) -> jax.Array:
    """Baseline: per-field gathers off a logical ``(N, d)`` table + concat
    (the paper's PyTorch baseline semantics; the k separate gathers are
    the cost)."""
    cols = [jnp.take(table, ids[:, i] + offsets[i], axis=0)
            for i in range(ids.shape[1])]
    return jnp.concatenate(cols, axis=1)


def multi_table_lookup_input_first(ids: jax.Array, table: jax.Array,
                                   offsets: jax.Array, *,
                                   interpret: bool | None = None
                                   ) -> jax.Array:
    """The Fig.-11 input-first strawman over a logical ``(N, d)`` table
    (benchmark comparisons only; Mosaic refuses its ``(1, d)`` row blocks,
    so it runs in interpret mode)."""
    if interpret is None:
        interpret = not on_tpu()
    return mtl_input_first(_flat_rows(ids, offsets), table, k=ids.shape[1],
                           interpret=interpret)


def _masked_rows(ids: jax.Array, mask: jax.Array, offsets: jax.Array,
                 zero_row: int) -> jax.Array:
    """(b, k, h) local ids -> flat global rows, masked slots redirected to
    the mega-table's all-zero row."""
    rows = ids.astype(jnp.int32) + offsets[None, :, None].astype(jnp.int32)
    return jnp.where(mask.astype(bool), rows, zero_row).reshape(-1)


def _pool(vals: jax.Array, mask: jax.Array, b: int, k: int, h: int,
          d: int) -> jax.Array:
    """The jnp twins' multi-hot pooling: mask-multiply, sum over slots."""
    return jnp.sum(vals.reshape(b, k, h, d)
                   * mask.reshape(b, k, h, 1).astype(vals.dtype),
                   axis=2).reshape(b, k * d)


def multi_table_lookup_cached(ids: jax.Array, cache: jax.Array,
                              backing: jax.Array, slot_of_row: jax.Array,
                              offsets: jax.Array, *, strategy: str = "auto",
                              interpret: bool | None = None,
                              dim: int) -> jax.Array:
    """Fused lookup through a tiered (cache + backing) embedding store.

    The CachedStore analogue of :func:`multi_table_lookup`: one two-level
    gather resolves every (field, id) — cached rows from ``cache``, misses
    from ``backing`` — bit-exact with the dense path because cache rows are
    verbatim copies.

    Args:
        ids:         (b, k) int32 per-field local ids.
        cache:       (C, d) hot-row copies, packed.
        backing:     (N, d) full mega-table, packed.
        slot_of_row: (N,) int32 cache slot per global row, -1 = uncached.
        offsets:     (k,) int32 starting row of each table.
        dim:         row width d.

    Returns:
        (b, k*d) embedding output.
    """
    b, k = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _flat_rows(ids, offsets)
    if strategy == "jnp":
        out = ref.ref_two_level_gather(rows, slot_of_row,
                                       _logical(cache, dim),
                                       _logical(backing, dim))
    else:
        slots = jnp.take(slot_of_row, rows, axis=0)
        out = mtl_gather_two_level(rows, slots, cache, backing, dim=dim,
                                   interpret=interpret)
    return out.reshape(b, -1)


def multi_table_lookup_cached_multihot(ids: jax.Array, mask: jax.Array,
                                       cache: jax.Array, backing: jax.Array,
                                       slot_of_row: jax.Array,
                                       offsets: jax.Array, *,
                                       strategy: str = "auto",
                                       interpret: bool | None = None,
                                       dim: int
                                       ) -> jax.Array:
    """Multi-hot (pooled) fused lookup through a tiered store.

    Mirrors :func:`multi_table_lookup_multihot` exactly — the jnp path
    repeats the dense oracle's mask-multiply-sum with the gather swapped
    for the two-level one, the pallas path redirects masked slots to the
    backing zero row — so either store produces bitwise-identical pooling.

    Args:
        ids:         (b, k, h) local ids; invalid slots arbitrary.
        mask:        (b, k, h) 1 for valid slots, 0 otherwise.
        cache:       (C, d) hot-row copies, packed.
        backing:     (N, d) full mega-table **with a trailing all-zero row**.
        slot_of_row: (N,) int32 index map.
        offsets:     (k,) table starts.
        dim:         row width d.

    Returns:
        (b, k*d) pooled output.
    """
    b, k, h = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    zero_row = slot_of_row.shape[0] - 1
    if strategy == "jnp":
        rows = (ids.astype(jnp.int32)
                + offsets[None, :, None].astype(jnp.int32)).reshape(-1)
        vals = ref.ref_two_level_gather(rows, slot_of_row,
                                        _logical(cache, dim),
                                        _logical(backing, dim))
        return _pool(vals, mask, b, k, h, vals.shape[-1])
    rows = _masked_rows(ids, mask, offsets, zero_row)
    slots = jnp.take(slot_of_row, rows, axis=0)
    out = mtl_gather_two_level(rows, slots, cache, backing, dim=dim, hot=h,
                               interpret=interpret)
    return out.reshape(b, k * dim)


def multi_table_lookup_cached_q8(ids: jax.Array, cache: jax.Array,
                                 cache_scale: jax.Array, backing: jax.Array,
                                 backing_scale: jax.Array,
                                 slot_of_row: jax.Array, offsets: jax.Array,
                                 *, strategy: str = "auto",
                                 interpret: bool | None = None,
                                 dim: int) -> jax.Array:
    """Quantized tiered lookup: int8 cache/backing rows, per-row fp32
    scales, dequantization inside the gather.

    The int8 twin of :func:`multi_table_lookup_cached` — same tier
    selection, ~``(d + 4) / 4d`` of its gather bytes, float32 output.
    Not bit-exact with the dense path (round-trip error ≤ scale/2 per
    element); the accuracy-parity benchmark gates the model-level impact.

    Args:
        ids:           (b, k) int32 per-field local ids.
        cache:         (C, d) int8 hot-row copies, packed.
        cache_scale:   (C, 1) fp32 per-row scales.
        backing:       (N, d) int8 full mega-table, packed.
        backing_scale: (N, 1) fp32 per-row scales.
        slot_of_row:   (N,) int32 cache slot per global row, -1 = uncached.
        offsets:       (k,) int32 starting row of each table.
        dim:           row width d.

    Returns:
        (b, k*d) float32 embedding output.
    """
    b, k = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _flat_rows(ids, offsets)
    if strategy == "jnp":
        out = ref.ref_two_level_gather_q8(
            rows, slot_of_row, _logical(cache, dim, jnp.int8), cache_scale,
            _logical(backing, dim, jnp.int8), backing_scale)
    else:
        slots = jnp.take(slot_of_row, rows, axis=0)
        out = mtl_gather_two_level_q8(rows, slots, cache, cache_scale,
                                      backing, backing_scale, dim=dim,
                                      interpret=interpret)
    return out.reshape(b, -1)


def multi_table_lookup_cached_q8_multihot(ids: jax.Array, mask: jax.Array,
                                          cache: jax.Array,
                                          cache_scale: jax.Array,
                                          backing: jax.Array,
                                          backing_scale: jax.Array,
                                          slot_of_row: jax.Array,
                                          offsets: jax.Array, *,
                                          strategy: str = "auto",
                                          interpret: bool | None = None,
                                          dim: int
                                          ) -> jax.Array:
    """Multi-hot (pooled) quantized tiered lookup.

    Masked slots redirect to the mega-table's zero row exactly as in the
    fp32 path — the zero row's int8 payload is 0, so it dequantizes to an
    exact 0.0 under any scale and pooling stays correct. Pooling happens
    in fp32 *after* per-row dequant (inside the kernel), never in int8.

    Args:
        ids:           (b, k, h) local ids; invalid slots arbitrary.
        mask:          (b, k, h) 1 for valid slots, 0 otherwise.
        cache:         (C, d) int8 hot-row copies, packed.
        cache_scale:   (C, 1) fp32 per-row scales.
        backing:       (N, d) int8 mega-table **with a trailing zero row**.
        backing_scale: (N, 1) fp32 per-row scales.
        slot_of_row:   (N,) int32 index map.
        offsets:       (k,) table starts.
        dim:           row width d.

    Returns:
        (b, k*d) float32 pooled output.
    """
    b, k, h = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _masked_rows(ids, mask, offsets, slot_of_row.shape[0] - 1)
    if strategy == "jnp":
        vals = ref.ref_two_level_gather_q8(
            rows, slot_of_row, _logical(cache, dim, jnp.int8), cache_scale,
            _logical(backing, dim, jnp.int8), backing_scale)
        return _pool(vals, mask, b, k, h, vals.shape[-1])
    slots = jnp.take(slot_of_row, rows, axis=0)
    out = mtl_gather_two_level_q8(rows, slots, cache, cache_scale,
                                  backing, backing_scale, dim=dim, hot=h,
                                  interpret=interpret)
    return out.reshape(b, k * dim)


def multi_table_lookup_host(ids: jax.Array, cache: jax.Array,
                            staging: jax.Array, slot_of_row: jax.Array,
                            staging_slot_of_row: jax.Array,
                            offsets: jax.Array, *, strategy: str = "auto",
                            interpret: bool | None = None,
                            dim: int) -> jax.Array:
    """Fused lookup through an out-of-HBM (cache + staging) store.

    The HostBackedStore analogue of :func:`multi_table_lookup_cached` with
    no device backing operand: cached rows from ``cache``, this batch's
    staged misses from ``staging``, anything else zero (the guard — the
    serve path stages every miss first, so the guard never fires on a
    correctly staged batch). Bit-exact with the dense path because both
    tiers hold verbatim backing-row copies.

    Args:
        ids:                 (b, k) int32 per-field local ids.
        cache:               (C, d) hot-row copies, packed.
        staging:             (S, d) staged miss rows, packed.
        slot_of_row:         (N,) int32 cache slot per row, -1 = uncached.
        staging_slot_of_row: (N,) int32 staging slot per row, -1 = unstaged.
        offsets:             (k,) int32 starting row of each table.
        dim:                 row width d.

    Returns:
        (b, k*d) embedding output.
    """
    b, k = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _flat_rows(ids, offsets)
    if strategy == "jnp":
        out = ref.ref_three_level_gather(rows, slot_of_row,
                                         staging_slot_of_row,
                                         _logical(cache, dim),
                                         _logical(staging, dim))
    else:
        cslots = jnp.take(slot_of_row, rows, axis=0)
        sslots = jnp.take(staging_slot_of_row, rows, axis=0)
        out = mtl_gather_three_level(cslots, sslots, cache, staging, dim=dim,
                                     interpret=interpret)
    return out.reshape(b, -1)


def multi_table_lookup_host_multihot(ids: jax.Array, mask: jax.Array,
                                     cache: jax.Array, staging: jax.Array,
                                     slot_of_row: jax.Array,
                                     staging_slot_of_row: jax.Array,
                                     offsets: jax.Array, *,
                                     strategy: str = "auto",
                                     interpret: bool | None = None,
                                     dim: int
                                     ) -> jax.Array:
    """Multi-hot (pooled) fused lookup through an out-of-HBM store.

    Mirrors :func:`multi_table_lookup_cached_multihot`: the jnp path masks
    after the three-level gather, the pallas path redirects masked slots
    to the mega-table's zero row — which pools zero from *any* tier, since
    the zero row's value is zero in the backing and every tier holds
    verbatim copies (and the zero-guard returns zero when it is in none).

    Args:
        ids:                 (b, k, h) local ids; invalid slots arbitrary.
        mask:                (b, k, h) 1 for valid slots, 0 otherwise.
        cache:               (C, d) hot-row copies, packed.
        staging:             (S, d) staged miss rows, packed.
        slot_of_row:         (N,) int32 cache index map.
        staging_slot_of_row: (N,) int32 staging index map.
        offsets:             (k,) table starts.
        dim:                 row width d.

    Returns:
        (b, k*d) pooled output.
    """
    b, k, h = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _masked_rows(ids, mask, offsets, slot_of_row.shape[0] - 1)
    if strategy == "jnp":
        vals = ref.ref_three_level_gather(rows, slot_of_row,
                                          staging_slot_of_row,
                                          _logical(cache, dim),
                                          _logical(staging, dim))
        return _pool(vals, mask, b, k, h, vals.shape[-1])
    cslots = jnp.take(slot_of_row, rows, axis=0)
    sslots = jnp.take(staging_slot_of_row, rows, axis=0)
    out = mtl_gather_three_level(cslots, sslots, cache, staging, dim=dim,
                                 hot=h, interpret=interpret)
    return out.reshape(b, k * dim)


def multi_table_lookup_host_q8(ids: jax.Array, cache: jax.Array,
                               cache_scale: jax.Array, staging: jax.Array,
                               staging_scale: jax.Array,
                               slot_of_row: jax.Array,
                               staging_slot_of_row: jax.Array,
                               offsets: jax.Array, *, strategy: str = "auto",
                               interpret: bool | None = None,
                               dim: int) -> jax.Array:
    """Quantized out-of-HBM lookup: int8 cache/staging rows, fp32 scales,
    in-gather dequant, zero-guard intact (q = 0 dequantizes to 0.0).

    The int8 twin of :func:`multi_table_lookup_host`; the serve path's
    staging contract is unchanged — every miss must be staged before the
    lookup, only the bytes staged per row shrink to ``d + 4``.

    Args:
        ids:                 (b, k) int32 per-field local ids.
        cache:               (C, d) int8 hot-row copies, packed.
        cache_scale:         (C, 1) fp32 per-row scales.
        staging:             (S, d) int8 staged miss rows (packed).
        staging_scale:       (S, 1) fp32 per-row scales.
        slot_of_row:         (N,) int32 cache slot per row, -1 = uncached.
        staging_slot_of_row: (N,) int32 staging slot per row, -1 = unstaged.
        offsets:             (k,) int32 starting row of each table.
        dim:                 row width d.

    Returns:
        (b, k*d) float32 embedding output.
    """
    b, k = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _flat_rows(ids, offsets)
    if strategy == "jnp":
        out = ref.ref_three_level_gather_q8(
            rows, slot_of_row, staging_slot_of_row,
            _logical(cache, dim, jnp.int8), cache_scale,
            _logical(staging, dim, jnp.int8), staging_scale)
    else:
        cslots = jnp.take(slot_of_row, rows, axis=0)
        sslots = jnp.take(staging_slot_of_row, rows, axis=0)
        out = mtl_gather_three_level_q8(cslots, sslots, cache, cache_scale,
                                        staging, staging_scale, dim=dim,
                                        interpret=interpret)
    return out.reshape(b, -1)


def multi_table_lookup_host_q8_multihot(ids: jax.Array, mask: jax.Array,
                                        cache: jax.Array,
                                        cache_scale: jax.Array,
                                        staging: jax.Array,
                                        staging_scale: jax.Array,
                                        slot_of_row: jax.Array,
                                        staging_slot_of_row: jax.Array,
                                        offsets: jax.Array, *,
                                        strategy: str = "auto",
                                        interpret: bool | None = None,
                                        dim: int
                                        ) -> jax.Array:
    """Multi-hot (pooled) quantized out-of-HBM lookup.

    Masked slots redirect to the zero row; whichever tier holds it (or the
    zero-guard, if neither does) contributes an exact 0.0 because the int8
    payload is 0. Pooling is fp32 post-dequant, as in the cached variant.

    Args:
        ids:                 (b, k, h) local ids; invalid slots arbitrary.
        mask:                (b, k, h) 1 for valid slots, 0 otherwise.
        cache:               (C, d) int8 hot-row copies, packed.
        cache_scale:         (C, 1) fp32 per-row scales.
        staging:             (S, d) int8 staged miss rows (packed).
        staging_scale:       (S, 1) fp32 per-row scales.
        slot_of_row:         (N,) int32 cache index map.
        staging_slot_of_row: (N,) int32 staging index map.
        offsets:             (k,) table starts.
        dim:                 row width d.

    Returns:
        (b, k*d) float32 pooled output.
    """
    b, k, h = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    rows = _masked_rows(ids, mask, offsets, slot_of_row.shape[0] - 1)
    if strategy == "jnp":
        vals = ref.ref_three_level_gather_q8(
            rows, slot_of_row, staging_slot_of_row,
            _logical(cache, dim, jnp.int8), cache_scale,
            _logical(staging, dim, jnp.int8), staging_scale)
        return _pool(vals, mask, b, k, h, vals.shape[-1])
    cslots = jnp.take(slot_of_row, rows, axis=0)
    sslots = jnp.take(staging_slot_of_row, rows, axis=0)
    out = mtl_gather_three_level_q8(cslots, sslots, cache, cache_scale,
                                    staging, staging_scale, dim=dim, hot=h,
                                    interpret=interpret)
    return out.reshape(b, k * dim)


def gather_tiers(tier: jax.Array, row: jax.Array, tables: tuple,
                 scale: jax.Array | None = None, *, dim: int, dtype,
                 strategy: str = "auto",
                 interpret: bool | None = None) -> jax.Array:
    """One-hot gather over explicit tiers: slot ``i`` reads logical row
    ``row[i]`` of packed table ``tables[tier[i]]`` (zero when
    ``tier[i] < 0``), dequantizing int8 rows by ``scale``. The building
    block of the vocab-parallel mesh lookup, where each shard routes its
    own rows (``EmbeddingStore.lookup_sharded``).

    Returns:
        (R, dim) rows of ``dtype`` (fp32 when ``scale`` is given).
    """
    strategy, interpret = _resolve(strategy, interpret)
    if strategy == "jnp":
        return ref.ref_tiered_gather(
            tier, row, tuple(unpack_rows(t, dim, dtype) for t in tables),
            scale)
    return mtl_gather_tiered(tier, row, tuple(tables), scale, hot=1,
                             dim=dim, dtype=np.dtype(dtype),
                             interpret=interpret)


# jitted so the epilogue's fp32 multiply-add chain contracts exactly like
# the (always-jitted) pallas kernel's — eager numpy-style evaluation would
# break the bitwise jnp-vs-interpret parity the kernel tests assert
_ref_dense_matmul_q8 = jax.jit(ref.ref_dense_matmul_q8,
                               static_argnames=("relu",))


def dense_matmul_q8(h: jax.Array, wq: jax.Array, wscale: jax.Array,
                    bias: jax.Array, *, relu: bool = True,
                    strategy: str = "auto",
                    interpret: bool | None = None) -> jax.Array:
    """Quantized dense layer: dynamic int8 activations × static int8
    weights, int32 accumulate, dequant + bias (+ ReLU) fused in the
    epilogue.

    The compute twin of the q8 gathers: weights arrive already quantized
    per output channel (once, at plan compile — see
    ``quant.quantize_channels``), activations are quantized per row *here*
    because their range is batch-dependent. Both strategies share that
    quantizer, so pallas-vs-jnp differ only in how the identical int8
    arithmetic is lowered. Not bit-exact with the fp32 GEMM (two absmax
    round-trips); the accuracy-parity benchmark gates the model-level
    impact (``accuracy_parity.py --quant-mlp``).

    Args:
        h:      (b, fan_in) fp32 activations.
        wq:     (fan_in, fan_out) int8 per-channel quantized weights.
        wscale: (1, fan_out) fp32 per-channel weight scales.
        bias:   (fan_out,) fp32.
        relu:   fuse the ReLU epilogue (off for pre-logit layers).

    Returns:
        (b, fan_out) float32 layer output.
    """
    if interpret is None:
        interpret = not on_tpu()
    if strategy == "auto":
        strategy = "pallas" if use_pallas() else "jnp"
    hscale = quant.absmax_scale(h, axis=-1)
    hq = quant.quantize(h, hscale)
    bias2d = bias.reshape(1, -1)
    if strategy == "jnp":
        return _ref_dense_matmul_q8(hq, hscale, wq, wscale, bias2d,
                                    relu=relu)
    if strategy == "pallas":
        return dmm_q8(hq, hscale, wq, wscale, bias2d, relu=relu,
                      interpret=interpret)
    raise ValueError(f"unknown strategy {strategy!r}")


def multi_table_lookup_onehot(ids: jax.Array, stacked_tables: jax.Array, *,
                              interpret: bool | None = None) -> jax.Array:
    """One-hot MXU lookup for small-field groups. Returns (b, k, d)."""
    if interpret is None:
        interpret = not on_tpu()
    return mtl_onehot(ids, stacked_tables, interpret=interpret)


def multi_table_lookup_multihot(ids: jax.Array, mask: jax.Array,
                                mega_table: jax.Array, offsets: jax.Array, *,
                                strategy: str = "auto",
                                interpret: bool | None = None,
                                dim: int) -> jax.Array:
    """Multi-hot (pooled) fused lookup.

    Args:
        ids:        (b, k, h) local ids; invalid slots arbitrary.
        mask:       (b, k, h) 1 for valid slots, 0 otherwise.
        mega_table: (N, d) concatenated tables **with a trailing all-zero
                    row** at index N-1, packed.
        offsets:    (k,) table starts.
        dim:        row width d.

    Returns:
        (b, k*d) pooled output.
    """
    b, k, h = ids.shape
    strategy, interpret = _resolve(strategy, interpret)
    if strategy == "jnp":
        return ref.ref_multi_hot_lookup(ids, mask, _logical(mega_table, dim),
                                        offsets)
    # the last row (the zero row, or zero padding after it) is all-zero
    zero_row = mega_table.shape[0] * rows_per_line(dim, mega_table.dtype) - 1
    rows = _masked_rows(ids, mask, offsets, zero_row)
    out = mtl_gather_multihot(rows, mega_table, hot=h, dim=dim,
                              interpret=interpret)
    return out.reshape(b, k * dim)
