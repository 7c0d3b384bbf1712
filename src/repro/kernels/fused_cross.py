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


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_cross_v2(x0: jax.Array, xw_plus: jax.Array, x: jax.Array, *,
                   block_b: int = 256, interpret: bool = False) -> jax.Array:
    """DCNv2 cross tail: ``x0 * xw_plus + x`` in one VMEM pass."""
    b, dim = x0.shape
    bm = min(block_b, b)
    grid = (pl.cdiv(b, bm),)
    spec = pl.BlockSpec((bm, dim), lambda i: (i, 0))
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
