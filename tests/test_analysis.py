"""Roofline-analyzer unit tests: loop-corrected HLO accounting on programs
with known ground truth."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.analysis.hlo_parse import parse_hlo
from repro.analysis.analytic import model_flops, param_stats


def test_dot_flops_loop_corrected():
    L, n = 7, 64

    def f(x, w):
        def step(c, wi):
            return c @ wi, None
        return jax.lax.scan(step, x, w)[0]

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((L, n, n), jnp.float32)).compile()
    st = parse_hlo(c.as_text())
    expect = 2 * n**3 * L
    assert abs(st.dot_flops - expect) / expect < 0.01
    # raw cost_analysis counts the body once — the analyzer must not
    assert c.cost_analysis()["flops"] < expect / 2
    assert st.trip_counts == [L]


def test_dot_flops_with_tpu_tiled_layouts():
    """Inline operand shapes may carry TPU tiling in the layout
    (``{1,0:T(8,128)}``); the contraction dim must still be read."""
    hlo = """\
ENTRY %main.1 (a: f32[64,32], b: f32[32,16]) -> f32[64,16] {
  %Arg_0.1 = f32[64,32]{1,0:T(8,128)} parameter(0)
  %Arg_1.2 = f32[32,16]{1,0:T(8,128)} parameter(1)
  ROOT %dot.3 = f32[64,16]{1,0:T(8,128)} dot(f32[64,32]{1,0:T(8,128)} %Arg_0.1, f32[32,16]{1,0:T(8,128)} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    st = parse_hlo(hlo)
    assert st.dot_flops == 2 * 64 * 16 * 32


def test_nested_loop_multipliers():
    L1, L2, n = 3, 4, 32

    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return c2 @ wi, None
            return jax.lax.scan(inner, c, None, length=L2)[0], None
        return jax.lax.scan(outer, x, w)[0]

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((L1, n, n), jnp.float32)).compile()
    st = parse_hlo(c.as_text())
    expect = 2 * n**3 * L1 * L2
    assert abs(st.dot_flops - expect) / expect < 0.01


def test_collective_bytes_counted_once_per_op():
    from jax.sharding import NamedSharding, PartitionSpec as P
    if jax.device_count() < 2:
        import pytest
        pytest.skip("needs >1 device")
    from repro.compat import make_mesh
    mesh = make_mesh((jax.device_count(),), ("data",))

    def f(x):
        return jnp.sum(x)                 # all-reduce over data

    with mesh:
        c = jax.jit(f, in_shardings=NamedSharding(mesh, P("data"))).lower(
            jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
    st = parse_hlo(c.as_text())
    assert st.total_collective_bytes > 0


def test_model_flops_6nd():
    st = param_stats("llama3-8b")
    assert 7.5e9 < st["total"] < 9e9          # ~8B
    mf = model_flops("llama3-8b", "train_4k")
    n = st["active"] - st["embed"]
    assert mf == 6.0 * n * 256 * 4096


def test_moe_active_params():
    st = param_stats("phi3.5-moe-42b-a6.6b")
    assert st["active"] < st["total"] / 2     # top-2 of 16 experts
    assert 35e9 < st["total"] < 50e9


def test_analyze_cell_int8_companion_terms():
    """The int8 twin of each roofline cell: matmuls at the int8 MXU
    peak, the weights-read HBM component at ~1/4 bytes, both arithmetic
    intensities populated (int8 strictly higher — same FLOPs over fewer
    bytes)."""
    import dataclasses

    from repro.analysis import hw
    from repro.analysis.analytic import analytic_cost
    from repro.analysis.roofline import TARGET, analyze_cell

    n = 64
    c = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    rep = analyze_cell("llama3-8b", "train_4k", "pod", 512, c)

    v5e = hw.chip(TARGET)
    np.testing.assert_allclose(
        rep.compute_s_int8,
        rep.compute_s * v5e.peak_flops_bf16 / v5e.peak_ops_int8, rtol=1e-12)
    an = analytic_cost("llama3-8b", "train_4k", 512, rep.n_micro)
    w_read = an.components["weights_read"]
    assert w_read > 0
    np.testing.assert_allclose(
        rep.memory_s_int8,
        (an.hbm_bytes_per_device - 0.75 * w_read) / v5e.hbm_bw, rtol=1e-12)
    assert rep.memory_s_int8 < rep.memory_s
    assert rep.arith_intensity_int8 > rep.arith_intensity > 0.0
    # the dry-run record schema: new fields serialize with the rest
    d = dataclasses.asdict(rep)
    for k in ("compute_s_int8", "memory_s_int8", "arith_intensity",
              "arith_intensity_int8"):
        assert k in d


def test_unknown_device_kind_is_an_error():
    """Peaks come from the published table, keyed by device kind; a chip
    missing from it must fail loudly, never fall back to v5e numbers."""
    from repro.analysis import hw
    v5e = hw.chip("TPU v5 lite")
    assert (v5e.peak_flops_bf16, v5e.peak_ops_int8, v5e.hbm_bw) == \
        (197e12, 393e12, 819e9)
    assert v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        hw.chip("TPU v99")
