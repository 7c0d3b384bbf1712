"""Public wrappers for the DPIFrame kernels, with strategy dispatch.

Every embedding lookup goes through one entry point, :func:`gather_tiers`:
slot ``i`` reads row ``row[i]`` of packed table ``tables[tier[i]]``, or
zero when ``tier[i] < 0``, dequantizing int8 rows by a per-slot scale.
Which tier and row each slot reads is the store's decision
(``EmbeddingStore._route``); this module only picks how the gather runs:

  "auto"        pallas on TPU (outside a mesh program), jnp elsewhere
  "pallas"      ``mtl_gather_tiered``, the output-first Pallas gather
                (Alg. 1)                                      [C2+C3]
  "jnp"         its twin ``ref.ref_tiered_gather``, one XLA gather per
                tier over the unpacked tables                 [C2 at XLA level]

Off a TPU the Pallas kernels run with ``interpret=True`` (kernel-body
validation). Tables arrive packed lane-dense (the layout the stores keep,
``multi_table_lookup.pack_rows``) plus their row width ``dim``. The
benchmark strawmen take a logical ``(N, d)`` table instead:
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
    mtl_gather_tiered,
    mtl_input_first,
    mtl_onehot,
    unpack_rows,
)

__all__ = [
    "gather_tiers",
    "multi_table_lookup_serial",
    "multi_table_lookup_input_first",
    "multi_table_lookup_onehot",
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


def _resolve(strategy: str, interpret: bool | None) -> tuple[str, bool]:
    if interpret is None:
        interpret = not on_tpu()
    if strategy == "auto":
        strategy = "pallas" if use_pallas() else "jnp"
    if strategy not in ("jnp", "pallas"):
        raise ValueError(f"unknown strategy {strategy!r}")
    return strategy, interpret


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
    rows = (ids.astype(jnp.int32)
            + offsets[None, :].astype(jnp.int32)).reshape(-1)
    return mtl_input_first(rows, table, k=ids.shape[1], interpret=interpret)


def gather_tiers(tier: jax.Array, row: jax.Array, tables: tuple,
                 scale: jax.Array | None = None, *, dim: int, dtype,
                 strategy: str = "auto",
                 interpret: bool | None = None) -> jax.Array:
    """The one embedding gather: slot ``i`` reads logical row ``row[i]`` of
    packed table ``tables[tier[i]]`` (zero when ``tier[i] < 0``),
    dequantizing int8 rows by ``scale``. Every store lookup, on one device
    (``EmbeddingStore.lookup``) and per shard of a mesh
    (``EmbeddingStore.lookup_sharded``), and the refresh-time cache fill
    (``EmbeddingStore.gather_rows``) end here. A row past the end of
    its table is clamped into it, never read out of bounds.

    Args:
        tier:   (R,) int32 table per slot, -1 = zero slot.
        row:    (R,) int32 row within that table.
        tables: packed tables, one per tier.
        scale:  (R,) fp32 per-slot scales (int8 tables only).
        dim:    row width d.
        dtype:  row dtype of ``tables``.

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
