"""Fused int8 dense matmul with in-kernel dequant — the MLP compute tier.

The quantized-compute twin of the MLP GEMMs emitted by
``models/ctr/common.emit_mlp_ops``: int8 activations (per-row scale) ×
int8 weights (per-output-channel scale) accumulate in int32 on the MXU,
and the epilogue — widen to fp32, apply both scales, add bias, optional
ReLU — runs in the same VMEM pass. The fp32 weight matrix never exists at
serve time; the fp32 activation exists only upstream of the per-row
quantizer in the wrapper (``ops.dense_matmul_q8``).

Blocking: one grid axis over batch blocks; the full (fan_in, fan_out)
weight tile rides in VMEM per block — CTR dense layers are a few hundred
units square (≤ ~0.5 MB int8), far under the VMEM budget, so K/N tiling
would only add accumulator plumbing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dmm_q8_kernel(hq_ref, hs_ref, wq_ref, ws_ref, b_ref, out_ref, *,
                   relu: bool):
    # int8 × int8 → int32 on the MXU; both operands stay int8 in VMEM
    acc = jax.lax.dot_general(hq_ref[...], wq_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    # dequant epilogue: row scale × channel scale factorizes the per-element
    # scale grid, so two rank-1 broadcasts undo both quantizers at once
    out = acc.astype(jnp.float32) * hs_ref[...] * ws_ref[...] + b_ref[...]
    if relu:
        out = jnp.maximum(out, 0.0)
    out_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("relu", "block_b", "interpret"))
def dmm_q8(hq: jax.Array, hscale: jax.Array, wq: jax.Array,
           wscale: jax.Array, bias: jax.Array, *, relu: bool = True,
           block_b: int = 256, interpret: bool = False) -> jax.Array:
    """Quantized dense layer: ``relu((hq·wq) * hscale * wscale + bias)``.

    Args:
        hq:     (b, fan_in) int8 per-row quantized activations.
        hscale: (b, 1) fp32 per-row activation scales.
        wq:     (fan_in, fan_out) int8 per-channel quantized weights.
        wscale: (1, fan_out) fp32 per-channel weight scales.
        bias:   (1, fan_out) fp32.
        relu:   fuse the ReLU epilogue (off for pre-logit layers).

    Returns:
        (b, fan_out) float32 layer output.
    """
    b, fan_in = hq.shape
    fan_out = wq.shape[1]
    bm = min(block_b, b)
    grid = (pl.cdiv(b, bm),)
    return pl.pallas_call(
        functools.partial(_dmm_q8_kernel, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, fan_in), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((fan_in, fan_out), lambda i: (0, 0)),
            pl.BlockSpec((1, fan_out), lambda i: (0, 0)),
            pl.BlockSpec((1, fan_out), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, fan_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, fan_out), jnp.float32),
        interpret=interpret,
        name="dmm_q8",
    )(hq, hscale, wq, wscale, bias)
