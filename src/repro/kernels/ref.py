"""Pure-jnp / numpy reference oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the ground truth the kernels are tested against
(``tests/test_kernels.py`` sweeps shapes and dtypes with assert_allclose).

``multi_table_lookup_alg1`` is a *literal* transcription of the paper's
Algorithm 1 (flat element-wise traversal of the output matrix) — O(b·k·d)
scalar Python, used only at tiny sizes to anchor the vectorized oracles.
``ref_tiered_gather`` is the one gather twin: ``ops.gather_tiers`` runs it
in place of the Pallas gather wherever its strategy resolves to jnp.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Algorithm 1 — multi-table lookup
# ---------------------------------------------------------------------------

def multi_table_lookup_alg1(ids: np.ndarray, tables: list[np.ndarray]) -> np.ndarray:
    """Literal transcription of DPIFrame Algorithm 1 (element-by-element).

    Args:
        ids:    (b, k) integer feature IDs; ``ids[s, i]`` indexes table ``i``.
        tables: list of k arrays, the i-th of shape (n_i, d).

    Returns:
        (b, k*d) lookup results, exactly the paper's ``EmbedOut``.
    """
    b, k = ids.shape
    d = tables[0].shape[1]
    ids_flat = ids.reshape(-1)                       # paper indexes IDs[row*k + table_id]
    total_elements = b * k * d                       # line 1
    row_width = k * d                                # line 2
    out = np.empty(total_elements, dtype=tables[0].dtype)
    for idx in range(total_elements):                # line 3
        row = idx // row_width                       # line 4
        col = idx % row_width                        # line 5
        table_id = col // d                          # line 6
        emb_row = ids_flat[row * k + table_id]       # line 7
        emb_col = col % d                            # line 8
        table = tables[table_id].reshape(-1)
        out[idx] = table[emb_row * d + emb_col]      # line 9
    return out.reshape(b, row_width)


def ref_serial_lookup(ids, tables):
    """The *baseline* the paper accelerates: k independent lookups + concat.

    Mirrors a per-field ``nn.Embedding`` loop (PyTorch-A analogue): every
    field materializes its own (b, d) intermediate before concatenation.
    """
    cols = [jnp.take(tables[i], ids[:, i], axis=0) for i in range(len(tables))]
    return jnp.concatenate(cols, axis=1)


def ref_tiered_gather(tier, row, tables, scale=None):
    """Oracle of ``mtl_gather_tiered`` (one-hot): slot ``i`` reads row
    ``row[i]`` of ``tables[tier[i]]``, or zero when ``tier[i] < 0``;
    int8 tables dequantize by the per-slot ``scale``.

    Args:
        tier:   (R,) int32 table index per slot, -1 = zero.
        row:    (R,) int32 row within that table.
        tables: (n_i, d) row tables (logical, not packed).
        scale:  (R,) fp32 per-slot scales (int8 tables only).

    Returns:
        (R, d) rows (float32 when dequantizing).
    """
    out = jnp.zeros((tier.shape[0], tables[0].shape[1]), tables[0].dtype)
    for t, table in enumerate(tables):
        vals = jnp.take(table, row, axis=0, mode="clip")
        out = jnp.where((tier == t)[:, None], vals, out)
    if scale is not None:
        out = out.astype(jnp.float32) * scale[:, None]
    return jnp.where((tier >= 0)[:, None], out, jnp.zeros_like(out))


def ref_dense_matmul_q8(hq, hscale, wq, wscale, bias, relu: bool = True):
    """Quantized dense-layer oracle — the int8 MLP matmul.

    Mirrors ``dense_matmul_q8``'s arithmetic *exactly* (int8×int8→int32
    dot, widen to fp32, row scale then channel scale then bias, optional
    ReLU), so the kernel-vs-ref comparison in interpret mode is bitwise.

    Args:
        hq:     (b, fan_in) int8 per-row quantized activations.
        hscale: (b, 1) fp32 per-row activation scales.
        wq:     (fan_in, fan_out) int8 per-channel quantized weights.
        wscale: (1, fan_out) fp32 per-channel weight scales.
        bias:   (1, fan_out) fp32.
        relu:   apply the fused ReLU epilogue.

    Returns:
        (b, fan_out) float32 layer output.
    """
    acc = jax.lax.dot_general(hq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * hscale * wscale + bias
    if relu:
        out = jnp.maximum(out, 0.0)
    return out


# ---------------------------------------------------------------------------
# Fused non-GEMM oracles (C5)
# ---------------------------------------------------------------------------

def ref_cross_v2_elementwise(x0, xw_plus, x):
    """DCNv2 cross-layer tail:  out = x0 * xw_plus + x.

    ``xw_plus = x_l @ W + b`` is produced by the (un-fused) GEMM; the fused
    kernel covers the remaining elementwise chain.
    """
    return x0 * xw_plus + x


def ref_cross_v1_elementwise(x0, xlw, bias, x):
    """DCNv1 cross-layer tail:  out = x0 * xlw + bias + x.

    ``xlw`` is the (b, 1) scalar-per-sample result of ``x_l · w``.
    """
    return x0 * xlw + bias[None, :] + x


def ref_fm_second_order(v):
    """Factorization-machine 2nd-order term.

    Args:
        v: (b, k, d) field embeddings.

    Returns:
        (b,) 0.5 * sum_d [ (sum_k v)^2 - sum_k v^2 ].
    """
    s = jnp.sum(v, axis=1)               # (b, d)
    sq = jnp.sum(v * v, axis=1)          # (b, d)
    return 0.5 * jnp.sum(s * s - sq, axis=-1)


def ref_mlp_tail(h, residual=None, act: str = "relu"):
    """Post-GEMM MLP tail: activation (+ optional residual)."""
    if act == "relu":
        h = jnp.maximum(h, 0)
    elif act == "gelu":
        h = 0.5 * h * (1 + jnp.tanh(0.7978845608028654 * (h + 0.044715 * h**3)))
    elif act == "silu":
        h = h * (1 / (1 + jnp.exp(-h)))
    elif act != "none":
        raise ValueError(f"unknown activation {act!r}")
    if residual is not None:
        h = h + residual
    return h
