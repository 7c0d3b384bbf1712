"""Pallas TPU kernels for DPIFrame hot spots, with pure-jnp oracles.

  multi_table_lookup.py  fused embedding gather (paper Alg. 1)  [C2, C3]
  fused_cross.py         DCN/DCNv2 cross elementwise tails      [C5]
  fused_fm.py            DeepFM FM 2nd-order term               [C5]
  dense_matmul.py        int8 MLP matmul with fused dequant epilogue
  ops.py                 public wrappers + strategy dispatch
  ref.py                 reference oracles (incl. literal Alg. 1)

Embedding lookups have one entry point, :func:`gather_tiers`: a store
routes each slot to a tier, a row and an int8 scale
(``EmbeddingStore._route``), and ``gather_tiers`` runs the one Pallas
gather ``mtl_gather_tiered`` or its jnp twin ``ref.ref_tiered_gather``.
"""

from .ops import (
    dense_matmul_q8,
    fused_cross_v1,
    fused_cross_v2,
    fused_fm_second_order,
    gather_tiers,
    multi_table_lookup_onehot,
    on_tpu,
)

__all__ = [
    "dense_matmul_q8",
    "fused_cross_v1",
    "fused_cross_v2",
    "fused_fm_second_order",
    "gather_tiers",
    "multi_table_lookup_onehot",
    "on_tpu",
]
