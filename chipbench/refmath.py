"""Plain ``jax.numpy`` pieces the model references share: the seeded
weight init and the float32 layers.

Nothing here imports the program. Products take JAX's default matmul
precision of the moment: ``check.reference_scores`` sets it to the
configuration's ``matmul_precision`` around every call. The dtype of a
computation follows the weights it is given, so the same functions run
the bfloat16 control when handed bfloat16 weights (``cast``).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

def field_offsets(cfg: dict) -> np.ndarray:
    """First row of each field's table in the concatenated table."""
    sizes = np.asarray(cfg["schema"]["field_sizes"], np.int64)
    return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)


def n_rows(cfg: dict) -> int:
    return int(sum(cfg["schema"]["field_sizes"]))


def table(key, cfg: dict, dim: int) -> jax.Array:
    """One row per id of every field, ``N(0, init.table_std²)``."""
    return jax.random.normal(key, (n_rows(cfg), dim), jnp.float32) \
        * cfg["init"]["table_std"]


def dense(key, fan_in: int, fan_out: int) -> dict:
    """Glorot-normal weight, zero bias."""
    scale = np.sqrt(2.0 / (fan_in + fan_out))
    return {"w": jax.random.normal(key, (fan_in, fan_out), jnp.float32)
            * scale, "b": jnp.zeros((fan_out,), jnp.float32)}


def mlp_init(key, dims) -> list[dict]:
    keys = jax.random.split(key, len(dims) - 1)
    return [dense(k, dims[i], dims[i + 1]) for i, k in enumerate(keys)]


def linear(x, layer: dict):
    return jnp.dot(x, layer["w"]) + layer["b"]


def mlp(x, layers: list[dict]):
    """ReLU after every layer (the deep branch of DCNv2 and DeepFM)."""
    for layer in layers:
        x = jnp.maximum(linear(x, layer), 0)
    return x


def lookup(tbl, ids, offsets):
    """``(b, k)`` per-field ids -> ``(b, k, dim)`` rows."""
    return jnp.take(tbl, ids + jnp.asarray(offsets)[None, :], axis=0)


def cast(weights, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), weights)


def matmul_flops(dims) -> int:
    """2·fan_in·fan_out summed over a chain of layer widths."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def gather_bytes(batch: int, k: int, dim: int, row_bytes: int = 4) -> int:
    """Bytes one one-hot lookup of ``batch`` requests needs: each of the
    ``batch·k`` rows read and written once, and its 4-byte id read."""
    return batch * k * (2 * dim * row_bytes + 4)
