"""DeepFM (Guo et al. 2017, arXiv:1703.04247).

    e_i     = V[id_i] (d wide), w_i = W[id_i] (width 1)
    y_FM    = b + Σ_i w_i + ½ Σ_f [(Σ_i e_if)² − Σ_i e_if²]     eq. (2)
    y_DNN   = w_head · MLP_ReLU([e_1; ...; e_k]) + b_head       eq. (3)-(4)
    score   = σ(y_FM + y_DNN)

Plain float32, every product at the configuration's ``matmul_precision``;
it imports nothing of the program. Weights are the harness's own, made from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import refmath as rm


def tables(cfg: dict) -> dict:
    """Embedding tables of the weights: key -> row width."""
    return {"emb": cfg["embed_dim"], "fm_w": 1}


def init_weights(cfg: dict, key, table=rm.table) -> dict:
    """Weights from ``key``; ``table(key, cfg, dim)`` makes each embedding
    table (``refmath.table``, or the same rows in a taller array)."""
    k = len(cfg["schema"]["field_sizes"])
    keys = jax.random.split(key, 4)
    return {
        "emb": table(keys[0], cfg, cfg["embed_dim"]),
        "fm_w": table(keys[1], cfg, 1),
        "fm_bias": jnp.zeros((1,), jnp.float32),
        "mlp": rm.mlp_init(keys[2], (k * cfg["embed_dim"], *cfg["hidden"])),
        "deep_head": rm.dense(keys[3], cfg["hidden"][-1], 1),
    }


def logits(cfg: dict, w: dict, ids):
    """``(b, k)`` per-field ids -> ``(b,)`` logits."""
    b = ids.shape[0]
    offsets = rm.field_offsets(cfg)
    v = rm.lookup(w["emb"], ids, offsets)                  # (b, k, d)
    first = jnp.sum(rm.lookup(w["fm_w"], ids, offsets)[..., 0], axis=1) \
        + w["fm_bias"][0]
    s = jnp.sum(v, axis=1)
    second = 0.5 * jnp.sum(s * s - jnp.sum(v * v, axis=1), axis=1)
    h = rm.mlp(v.reshape(b, -1), w["mlp"])
    deep = rm.linear(h, w["deep_head"])[:, 0]
    return first + second + deep


def flops_per_request(cfg: dict) -> int:
    """Matrix-product FLOPs of one scored request; the FM terms, about
    3·k·d elementwise operations (0.25% of it here), are not counted."""
    k = len(cfg["schema"]["field_sizes"])
    return (rm.matmul_flops((k * cfg["embed_dim"], *cfg["hidden"]))
            + rm.matmul_flops((cfg["hidden"][-1], 1)))


def gather_bytes(cfg: dict, batch: int) -> int:
    """Bytes the step's two lookups (d-wide rows, 1-wide FM weights) need
    for a batch of ``batch`` rows."""
    k = len(cfg["schema"]["field_sizes"])
    return (rm.gather_bytes(batch, k, cfg["embed_dim"])
            + rm.gather_bytes(batch, k, 1))
