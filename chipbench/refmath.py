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


def table(key, cfg: dict, dim: int, rows: int | None = None) -> jax.Array:
    """One row per id of every field, ``N(0, init.table_std²)``, in an
    array ``rows`` high (default ``n_rows``; rows past the ids are zero).

    With ``init.block_rows`` (``B``) set, block ``i``, ids
    ``[i·B, (i+1)·B)`` with the last block clipped to ``n_rows``, is drawn
    from ``fold_in(key, i)`` and written into the array in place, so making
    it holds the array and one block's draw: a table that fills most of a
    chip can be made on it, at any height, with the same rows. Without it,
    one draw of the whole table, zero-padded (what every configuration
    without the key has always had).
    """
    n, std = n_rows(cfg), cfg["init"]["table_std"]
    rows = n if rows is None else rows
    block = cfg["init"].get("block_rows")
    if not block:
        t = jax.random.normal(key, (n, dim), jnp.float32) * std
        return jnp.pad(t, ((0, rows - n), (0, 0))) if rows > n else t

    def draw(i):
        return jax.random.normal(jax.random.fold_in(key, i), (block, dim),
                                 jnp.float32) * std

    def put(i, t):
        return jax.lax.dynamic_update_slice(t, draw(i), (i * block, 0))

    whole, rest = divmod(n, block)
    t = jax.lax.fori_loop(0, whole, put, jnp.zeros((rows, dim), jnp.float32))
    if rest:
        t = jax.lax.dynamic_update_slice(t, draw(whole)[:rest],
                                         (whole * block, 0))
    return t


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


def split_rows(cfg: dict, rows):
    """``(numeric, ids)`` of request rows laid out as
    ``loadgen.request_rows`` makes them: the schema's numeric counts
    first (none without ``schema.numeric``), then every field's ids."""
    n = len(cfg["schema"].get("numeric", {}).get("caps", ()))
    return rows[:, :n], rows[:, n:]


def lookup(tbl, ids, offsets):
    """``(b, k)`` per-field ids -> ``(b, k, dim)`` rows."""
    return jnp.take(tbl, ids + jnp.asarray(offsets)[None, :], axis=0)


def pooled_lookup(tbl, ids, offsets, hotness):
    """``(b, Σh)`` multi-hot ids, field ``i``'s ``h_i`` slots side by side
    -> ``(b, k, dim)``: each field's rows summed over its slots."""
    slot_offsets = np.repeat(np.asarray(offsets), hotness)
    rows = lookup(tbl, ids, slot_offsets)
    ends = np.cumsum(hotness)
    return jnp.stack([rows[:, e - h:e].sum(axis=1)
                      for h, e in zip(hotness, ends)], axis=1)


def cast(weights, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), weights)


def matmul_flops(dims) -> int:
    """2·fan_in·fan_out summed over a chain of layer widths."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def gather_bytes(batch: int, k: int, dim: int, row_bytes: int = 4,
                 hotness=None) -> int:
    """Bytes one pooled lookup of ``batch`` requests over ``k`` fields
    needs: each of the ``batch·Σh`` rows read once with its 4-byte id
    (``hotness``: ``h_i`` ids per field, one each by default), and the
    ``batch·k`` pooled rows written once."""
    slots = k if hotness is None else sum(hotness)
    return batch * (slots * (dim * row_bytes + 4) + k * dim * row_bytes)
