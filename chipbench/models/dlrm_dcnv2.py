"""DLRM-DCNv2: MLPerf's ``dlrm_v2`` recommendation model (MLPerf Training
v3.0+ / Inference v3.1+, torchrec's ``DLRM_DCN``), with DCN V2's low-rank
cross (arXiv:2008.13535).

A request row is ``[count_1 .. count_m | field 1's h_1 ids | ...]``
(``refmath.split_rows``)::

    z       = MLP_ReLU(log(count + 1))                 bottom (dense) MLP
    e_i     = Σ_j E[id_ij]                             sum-pooled field i
    x0      = [z; e_1; ...; e_k]                       interaction, concat
    x_{l+1} = x0 ⊙ (W_l (V_l x_l) + b_l) + x_l         low-rank cross, eq. (2)
    logit   = w_head · MLP_ReLU(x_L) + b_head          top (over) MLP
    score   = σ(logit)

Departures from the torchrec reference, none of which changes a shape:
``log(x + 1)`` of the counts is taken here, where MLPerf's data
preprocessing takes it; tables are N(0, 0.05²) as in the harness's other
models (torchrec draws each table uniform in ±1/√rows); dense layers and
``V_l`` are glorot-normal with zero biases (torchrec keeps ``nn.Linear``'s
default init for the MLPs). Counts are cast to the weights' dtype, so the
bfloat16 control computes in bfloat16 throughout.

Plain float32, every product at the configuration's ``matmul_precision``;
it imports nothing of the program. Weights are the harness's own, made
from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refmath as rm


def _widths(cfg: dict) -> tuple[int, int, tuple]:
    """(numeric features, interaction width, bottom MLP widths)."""
    a = cfg["arch"]
    k = len(cfg["schema"]["field_sizes"])
    return (a["n_numeric"], a["bottom"][-1] + k * cfg["embed_dim"],
            tuple(a["bottom"]))


def tables(cfg: dict) -> dict:
    """Embedding tables of the weights: key -> row width."""
    return {"emb": cfg["embed_dim"]}


def init_weights(cfg: dict, key, table=rm.table) -> dict:
    """Weights from ``key``; ``table(key, cfg, dim)`` makes each embedding
    table (``refmath.table``, or the same rows in a taller array)."""
    m, d, bottom = _widths(cfg)
    r = cfg["arch"]["cross_rank"]
    keys = jax.random.split(key, 4 + cfg["cross_layers"])
    cross = []
    for i in range(cfg["cross_layers"]):
        kv, kw = jax.random.split(keys[4 + i])
        cross.append({**rm.dense(kw, r, d), "v": rm.dense(kv, d, r)["w"]})
    return {
        "emb": table(keys[0], cfg, cfg["embed_dim"]),
        "bottom": rm.mlp_init(keys[1], (m, *bottom)),
        "cross": cross,
        "top": rm.mlp_init(keys[2], (d, *cfg["hidden"])),
        "head": rm.dense(keys[3], cfg["hidden"][-1], 1),
    }


def logits(cfg: dict, w: dict, rows):
    """``(b, m + Σh)`` request rows -> ``(b,)`` logits."""
    numeric, ids = rm.split_rows(cfg, rows)
    b = rows.shape[0]
    dtype = w["head"]["w"].dtype
    z = rm.mlp(jnp.log1p(numeric.astype(jnp.float32)).astype(dtype),
               w["bottom"])
    e = rm.pooled_lookup(w["emb"], ids, rm.field_offsets(cfg),
                         cfg["schema"]["hotness"])
    x0 = jnp.concatenate([z, e.reshape(b, -1)], axis=1)
    x = x0
    for layer in w["cross"]:
        x = x0 * rm.linear(jnp.dot(x, layer["v"]), layer) + x
    return rm.linear(rm.mlp(x, w["top"]), w["head"])[:, 0]


def flops_per_request(cfg: dict) -> int:
    """Matrix-product FLOPs of one scored request: the bottom MLP, two
    GEMMs a cross layer (``d·r`` each way), the top MLP and its head.
    Pooling and the cross's elementwise tail, under 0.1% of it, are not
    counted."""
    m, d, bottom = _widths(cfg)
    r = cfg["arch"]["cross_rank"]
    return (rm.matmul_flops((m, *bottom))
            + cfg["cross_layers"] * rm.matmul_flops((d, r, d))
            + rm.matmul_flops((d, *cfg["hidden"], 1)))


def gather_bytes(cfg: dict, batch: int) -> int:
    """Bytes the pooled lookup needs for a batch of ``batch`` rows: every
    id slot's row and id read, each field's pooled row written."""
    return rm.gather_bytes(batch, len(cfg["schema"]["field_sizes"]),
                           cfg["embed_dim"],
                           hotness=cfg["schema"]["hotness"])

