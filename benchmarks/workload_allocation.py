"""Paper Fig. 11 — output-first vs input-first workload allocation.

XLA-level analogues of the two kernels (timing Pallas interpret mode would
measure the Python emulator, not the algorithm): output-first = one
row-gather writing the (b, k·d) output directly; input-first = field-major
gather producing (k, b, d) + the reorganization transpose it then needs.
Numerical equality of the two layouts is asserted every run (the kernels
themselves are validated in tests/test_kernels.py).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import FusedEmbeddingSpec

from .common import emit, time_fn


def run(quick: bool = False) -> dict:
    out = {}
    cases = ([(2048, 32)] if quick
             else [(2048, 32), (16384, 32), (65536, 32), (2048, 60)])
    for b, d in cases:
        k, n = 39, 100_000
        spec = FusedEmbeddingSpec(field_sizes=(n,) * k, dim=d)
        # a plain (rows, d) table: d=60 has no packed layout, and this
        # suite times XLA's own gather orders, not a store
        table = jax.random.normal(jax.random.PRNGKey(0), (spec.rows, d)) * 0.05
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, n, size=(b, k)), dtype=jnp.int32)
        offs = jnp.asarray(spec.offsets)

        @jax.jit
        def output_first(table, ids):
            rows = (ids + offs[None, :]).reshape(-1)
            return jnp.take(table, rows, axis=0).reshape(b, k * d)

        @jax.jit
        def input_first(table, ids):
            rows_fmajor = (ids.T + offs[:, None]).reshape(-1)      # (k*b,)
            g = jnp.take(table, rows_fmajor, axis=0).reshape(k, b, d)
            return jnp.transpose(g, (1, 0, 2)).reshape(b, k * d)

        np.testing.assert_allclose(np.asarray(output_first(table, ids)),
                                   np.asarray(input_first(table, ids)),
                                   rtol=1e-6)
        t_of = time_fn(output_first, table, ids, reps=3, warmup=1)
        t_if = time_fn(input_first, table, ids, reps=3, warmup=1)
        tag = f"b{b}_d{d}"
        emit(f"alloc/{tag}/input_first", t_if)
        emit(f"alloc/{tag}/output_first", t_of, f"speedup={t_if/t_of:.2f}x")
        out[tag] = t_if / t_of
    return out


if __name__ == "__main__":
    run()
