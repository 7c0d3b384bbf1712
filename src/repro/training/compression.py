"""Gradient compression for data-parallel reduction (distributed-opt trick).

Int8 block-quantized psum under ``shard_map``: ranks agree on a shared
per-block scale (pmax — a tiny f32 reduction), quantize locally to int8,
sum the int32-widened payload over the data axis (wire bytes ≈ ¼ of f32),
and dequantize with the shared scale. 8-bit rounding error only — validated
in tests to ~1% relative against the exact psum.

The quantize/dequantize math itself lives in ``repro.quant`` — the same
symmetric-absmax codepath the quantized embedding stores use for their
int8 rows; this module only adds what is collective-specific (blocking,
the pmax'd shared scale, the int32-widened psum).

Under GSPMD the DP all-reduce is normally implicit in the backward; this
explicit form exists so deployments that are ICI-bound on the gradient
reduction (§Roofline collective term) can opt in per-tensor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import quant
from jax import shard_map

__all__ = ["compressed_psum_mean", "make_compressed_dp_step", "BLOCK"]

BLOCK = 256


def compressed_psum_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean-reduce ``x`` over ``axis_name`` with int8 wire format.

    Call inside shard_map / under a mapped axis.
    """
    dtype = x.dtype
    shape = x.shape
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    pad = (-n) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    # shared per-block scale across ranks (small f32 wire cost); the pmax
    # sits between the local absmax and the eps floor so every rank
    # quantizes against the same guarded scale
    local_scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / quant.QMAX
    scale = jnp.maximum(jax.lax.pmax(local_scale, axis_name),
                        quant.SCALE_EPS)
    q = quant.quantize(blocks, scale)
    # int8 payload summed in int32 (≤ 2^23 ranks before overflow)
    qs = jax.lax.psum(q.astype(jnp.int32), axis_name)
    ranks = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    out = (quant.dequantize(qs, scale) / ranks).reshape(-1)[:n]
    return out.reshape(shape).astype(dtype)


def make_compressed_dp_step(loss_fn, mesh: Mesh, axis: str = "data"):
    """Build a data-parallel grad step whose DP reduction uses the int8
    wire format: ``step(params, batch) -> (loss, grads)`` with params
    replicated, batch sharded over ``axis``, and the gradient mean computed
    by ``compressed_psum_mean`` instead of the implicit f32 all-reduce.
    """
    def _local(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        grads = jax.tree.map(
            lambda g: compressed_psum_mean(g, axis), grads)
        return loss, grads

    def step(params, batch):
        pspec = jax.tree.map(lambda _: P(), params)
        bspec = jax.tree.map(
            lambda x: P(axis, *([None] * (x.ndim - 1))), batch)
        return shard_map(_local, mesh=mesh,
                         in_specs=(pspec, bspec),
                         out_specs=(P(), pspec),
                         check_vma=False)(params, batch)
    return step
