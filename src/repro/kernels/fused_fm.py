"""Fused factorization-machine second-order kernel (DeepFM, non-GEMM fusion C5).

The FM pairwise-interaction term

    y_fm(b) = 0.5 * Σ_d [ (Σ_k v[b,k,d])² − Σ_k v[b,k,d]² ]

is, un-fused, a chain of square / reduce-sum / subtract ops each writing an
intermediate to HBM. The fused kernel keeps the (bm, k, d) tile VMEM-resident
and emits only the (bm, 1) result — exactly the paper's C5 treatment of
DeepFM's explicit-interaction module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fm_kernel(v_ref, out_ref):
    v = v_ref[...].astype(jnp.float32)            # (bm, k, d)
    s = jnp.sum(v, axis=1)                        # (bm, d)
    sq = jnp.sum(v * v, axis=1)                   # (bm, d)
    out = 0.5 * jnp.sum(s * s - sq, axis=-1)      # (bm,)
    out_ref[...] = out[:, None].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_fm_second_order(v: jax.Array, *, block_b: int = 128,
                          interpret: bool = False) -> jax.Array:
    """Fused FM 2nd-order term.

    Args:
        v: (b, k, d) field embeddings.

    Returns:
        (b, 1) interaction score (kept 2-D for TPU-friendly layout).
    """
    b, k, d = v.shape
    bm = min(block_b, b)
    grid = (pl.cdiv(b, bm),)
    return pl.pallas_call(
        _fm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k, d), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), v.dtype),
        interpret=interpret,
        name="fused_fm_second_order",
    )(v)
