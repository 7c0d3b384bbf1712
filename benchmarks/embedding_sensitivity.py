"""Paper Fig. 10 — embedding sensitivity: batch size, embedding dim,
#fields, #features. Fused single-gather (Alg. 1, "jnp" strategy on CPU =
identical algorithm at the XLA level) vs per-field serial lookup + concat.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import FusedEmbeddingCollection, FusedEmbeddingSpec

from .common import emit, time_fn


def _setup(k: int, n: int, d: int):
    spec = FusedEmbeddingSpec(field_sizes=(n,) * k, dim=d)
    emb = FusedEmbeddingCollection(spec)
    params = emb.init(jax.random.PRNGKey(0))
    return emb, params


def _ids(k: int, n: int, b: int):
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, n, size=(b, k)), dtype=jnp.int32)


def _pair(emb, params, ids, tag: str) -> float:
    # params passed as arguments (a closure would bake the mega-table into
    # the executable as multi-GB constants)
    fused = jax.jit(lambda p, i: emb.apply(p, i, strategy="jnp"))
    serial = jax.jit(emb.apply_serial)
    tf = time_fn(fused, params, ids, reps=3, warmup=1)
    ts = time_fn(serial, params, ids, reps=3, warmup=1)
    emit(f"emb/{tag}/serial", ts)
    emit(f"emb/{tag}/fused", tf, f"speedup={ts/tf:.2f}x")
    return ts / tf


def run(quick: bool = False) -> dict:
    out = {}
    # (1) batch size sweep (paper: criteo, d=32)
    for b in ([2048] if quick else [1024, 4096, 16384, 65536]):
        emb, params = _setup(39, 100_000, 32)
        out[f"batch_{b}"] = _pair(emb, params, _ids(39, 100_000, b),
                                  f"batch_{b}")
    # (2) embedding dim sweep (batch 2048)
    for d in ([16] if quick else [8, 16, 32, 64]):
        emb, params = _setup(39, 100_000, d)
        out[f"dim_{d}"] = _pair(emb, params, _ids(39, 100_000, 2048),
                                f"dim_{d}")
    # (3) #fields sweep (500k features per field in the paper; 100k here)
    for k in ([20] if quick else [10, 20, 40, 80]):
        emb, params = _setup(k, 100_000, 32)
        out[f"fields_{k}"] = _pair(emb, params, _ids(k, 100_000, 2048),
                                   f"fields_{k}")
    # (4) #features sweep (height of tables; paper: no effect)
    for n in ([10_000] if quick else [1_000, 10_000, 100_000, 300_000]):
        emb, params = _setup(50, n, 32)
        out[f"features_{n}"] = _pair(emb, params, _ids(50, n, 2048),
                                     f"features_{n}")
    return out


if __name__ == "__main__":
    run()
