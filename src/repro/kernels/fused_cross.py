"""Fused elementwise tails of DCN / DCNv2 cross layers (non-GEMM fusion, C5).

The cross layer is ``x_{l+1} = x0 ⊙ f(x_l) + [b] + x_l`` where ``f`` is the
GEMM part (left to the MXU via XLA). Everything after the GEMM is a chain of
small elementwise ops that the paper fuses into one kernel; on TPU we fuse
them into a single VPU pass with one VMEM round-trip instead of three.

  DCNv2:  out = x0 * (x_l W + b) + x_l      (``xw_plus`` = x_l W + b)
  DCNv1:  out = x0 * (x_l · w) + b + x_l    (``xlw`` is (b, 1) per-sample)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cross_v2_kernel(x0_ref, xw_ref, x_ref, out_ref):
    out_ref[...] = x0_ref[...] * xw_ref[...] + x_ref[...]


#: bytes of one operand's block; four operands, double-buffered, stay
#: well inside the 16 MiB of scoped VMEM a v5e kernel may use
BLOCK_BYTES = 2 << 20
LANES = 128


def _width_block(rows: int, dim: int, itemsize: int) -> int:
    """Columns of one block: the whole width while a ``rows``-high block
    fits :data:`BLOCK_BYTES`, else the widest multiple of 128 lanes that
    fits and divides ``dim`` (any fitting multiple of 128 when ``dim`` is
    not one: the last block is then ragged)."""
    if rows * dim * itemsize <= BLOCK_BYTES:
        return dim
    fit = max(1, BLOCK_BYTES // (rows * itemsize * LANES))
    if dim % LANES:
        return fit * LANES
    tiles = dim // LANES
    return max(m for m in range(1, min(fit, tiles) + 1)
               if tiles % m == 0) * LANES


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_cross_v2(x0: jax.Array, xw_plus: jax.Array, x: jax.Array, *,
                   block_b: int = 256, interpret: bool = False) -> jax.Array:
    """DCNv2 cross tail: ``x0 * xw_plus + x`` in one VMEM pass, over a grid
    of row blocks and, for widths whose row block would not fit
    :data:`BLOCK_BYTES`, column blocks."""
    b, dim = x0.shape
    bm = min(block_b, b)
    bd = _width_block(bm, dim, x0.dtype.itemsize)
    grid = (pl.cdiv(b, bm), pl.cdiv(dim, bd))
    spec = pl.BlockSpec((bm, bd), lambda i, j: (i, j))
    return pl.pallas_call(
        _cross_v2_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, dim), x0.dtype),
        interpret=interpret,
        name="fused_cross_v2",
    )(x0, xw_plus, x)


def _cross_v1_kernel(x0_ref, xlw_ref, bias_ref, x_ref, out_ref):
    out_ref[...] = x0_ref[...] * xlw_ref[...] + bias_ref[...] + x_ref[...]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_cross_v1(x0: jax.Array, xlw: jax.Array, bias: jax.Array,
                   x: jax.Array, *, block_b: int = 256,
                   interpret: bool = False) -> jax.Array:
    """DCNv1 cross tail: ``x0 * xlw + bias + x`` (xlw broadcast from (b,1))."""
    b, dim = x0.shape
    bm = min(block_b, b)
    grid = (pl.cdiv(b, bm),)
    spec = pl.BlockSpec((bm, dim), lambda i: (i, 0))
    return pl.pallas_call(
        _cross_v1_kernel,
        grid=grid,
        in_specs=[
            spec,
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, dim), lambda i: (0, 0)),
            spec,
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, dim), x0.dtype),
        interpret=interpret,
        name="fused_cross_v1",
    )(x0, xlw, bias.reshape(1, dim), x)
