"""DCNv2 (Wang et al. 2021, "DCN V2", arXiv:2008.13535), parallel form.

    x0      = [e_1; ...; e_k]                    concatenated embeddings
    x_{l+1} = x0 ⊙ (W_l x_l + b_l) + x_l         full-rank cross layer, eq. (1)
    h       = MLP_ReLU(x0)                       deep branch
    logit   = w_head · [x_L; h] + b_head         stacked head
    score   = σ(logit)

Plain float32, every product at the configuration's ``matmul_precision``;
it imports nothing of the program. Weights are the harness's own, made from the seed.
"""

from __future__ import annotations

import jax

from chipbench import refmath as rm


def tables(cfg: dict) -> dict:
    """Embedding tables of the weights: key -> row width."""
    return {"emb": cfg["embed_dim"]}


def init_weights(cfg: dict, key, table=rm.table) -> dict:
    """Weights from ``key``; ``table(key, cfg, dim)`` makes each embedding
    table (``refmath.table``, or the same rows in a taller array)."""
    k = len(cfg["schema"]["field_sizes"])
    d_in = k * cfg["embed_dim"]
    keys = jax.random.split(key, 3 + cfg["cross_layers"])
    return {
        "emb": table(keys[0], cfg, cfg["embed_dim"]),
        "mlp": rm.mlp_init(keys[1], (d_in, *cfg["hidden"])),
        "head": rm.dense(keys[2], d_in + cfg["hidden"][-1], 1),
        "cross": [rm.dense(keys[3 + i], d_in, d_in)
                  for i in range(cfg["cross_layers"])],
    }


def logits(cfg: dict, w: dict, ids):
    """``(b, k)`` per-field ids -> ``(b,)`` logits."""
    b = ids.shape[0]
    x0 = rm.lookup(w["emb"], ids, rm.field_offsets(cfg)).reshape(b, -1)
    x = x0
    for layer in w["cross"]:
        x = x0 * rm.linear(x, layer) + x
    h = rm.mlp(x0, w["mlp"])
    z = jax.numpy.concatenate([x, h], axis=1)
    return rm.linear(z, w["head"])[:, 0]


def flops_per_request(cfg: dict) -> int:
    """Matrix-product FLOPs of one scored request (elementwise work,
    under 0.1% of it, not counted)."""
    d_in = len(cfg["schema"]["field_sizes"]) * cfg["embed_dim"]
    cross = cfg["cross_layers"] * rm.matmul_flops((d_in, d_in))
    deep = rm.matmul_flops((d_in, *cfg["hidden"]))
    head = rm.matmul_flops((d_in + cfg["hidden"][-1], 1))
    return cross + deep + head


def gather_bytes(cfg: dict, batch: int) -> int:
    """Bytes the step's lookups need for a batch of ``batch`` rows."""
    return rm.gather_bytes(batch, len(cfg["schema"]["field_sizes"]),
                           cfg["embed_dim"])
