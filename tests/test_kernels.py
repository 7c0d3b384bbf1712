"""Per-kernel allclose validation against the pure-jnp oracles.

Sweeps shapes and dtypes per the deliverable: every Pallas kernel is executed
in interpret mode (CPU) and compared against repro.kernels.ref.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.multi_table_lookup import (
    mtl_gather,
    mtl_onehot,
    pack_rows,
    unpack_rows,
)

TOL = dict(rtol=1e-5, atol=1e-5)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def make_tables(rng, sizes, d, dtype):
    tables = [jnp.asarray(rng.normal(size=(n, d)), dtype=dtype) for n in sizes]
    mega = jnp.concatenate(tables, axis=0)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)[:-1]]),
                          dtype=jnp.int32)
    return tables, mega, offsets


def make_ids(rng, sizes, b):
    return jnp.asarray(
        np.stack([rng.integers(0, n, size=b) for n in sizes], axis=1),
        dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Algorithm 1 anchoring: literal paper pseudocode == vectorized oracle
# ---------------------------------------------------------------------------

def test_alg1_literal_matches_vectorized():
    rng = np.random.default_rng(0)
    sizes, d, b = [3, 17, 5], 4, 6
    tables, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    ids = make_ids(rng, sizes, b)
    lit = ref.multi_table_lookup_alg1(np.asarray(ids),
                                      [np.asarray(t) for t in tables])
    vec = ref.ref_multi_table_lookup(ids, mega, offsets, len(sizes))
    np.testing.assert_allclose(lit, vec, **TOL)


# ---------------------------------------------------------------------------
# mtl_gather (output-first, the paper's kernel) — shape × dtype sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [8, 16, 32, 128])
@pytest.mark.parametrize("b,k", [(4, 2), (16, 5), (32, 9)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mtl_gather_sweep(b, k, d, dtype):
    rng = np.random.default_rng(b * k * d)
    sizes = list(rng.integers(2, 50, size=k))
    _, mega, offsets = make_tables(rng, sizes, d, dtype)
    ids = make_ids(rng, sizes, b)
    want = ref.ref_multi_table_lookup(ids, mega, offsets, k)
    rows = (ids + offsets[None, :]).reshape(-1)
    got = mtl_gather(rows, pack_rows(mega), dim=d, dtype=dtype,
                     interpret=True).reshape(b, k * d)
    tol = BF16_TOL if dtype == jnp.bfloat16 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("strategy", ["jnp", "pallas", "serial", "input_first"])
def test_ops_dispatch_equivalence(strategy):
    rng = np.random.default_rng(7)
    sizes, d, b = [11, 3, 40, 8], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    ids = make_ids(rng, sizes, b)
    want = ref.ref_multi_table_lookup(ids, mega, offsets, len(sizes))
    if strategy == "serial":
        got = ops.multi_table_lookup_serial(ids, mega, offsets)
    elif strategy == "input_first":
        got = ops.multi_table_lookup_input_first(ids, mega, offsets,
                                                 interpret=True)
    else:
        got = ops.multi_table_lookup(ids, pack_rows(mega), offsets, dim=d,
                                     strategy=strategy, interpret=True)
    np.testing.assert_allclose(got, want, **TOL)


def test_input_first_matches_output_first():
    """Fig.-11 pair must be numerically identical (only layout differs)."""
    rng = np.random.default_rng(3)
    sizes, d, b = [9, 21], 8, 10
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    ids = make_ids(rng, sizes, b)
    a = ops.multi_table_lookup(ids, pack_rows(mega), offsets, dim=d,
                               strategy="pallas", interpret=True)
    z = ops.multi_table_lookup_input_first(ids, mega, offsets,
                                           interpret=True)
    np.testing.assert_allclose(a, z, **TOL)


# ---------------------------------------------------------------------------
# one-hot MXU variant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [8, 32])
@pytest.mark.parametrize("n_pad", [16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mtl_onehot_sweep(d, n_pad, dtype):
    rng = np.random.default_rng(d + n_pad)
    k, b = 4, 20
    stacked = jnp.asarray(rng.normal(size=(k, n_pad, d)), dtype=dtype)
    ids = jnp.asarray(rng.integers(0, n_pad, size=(b, k)), dtype=jnp.int32)
    got = mtl_onehot(ids, stacked, interpret=True)
    want = jnp.stack([stacked[f][ids[:, f]] for f in range(k)], axis=1)
    tol = BF16_TOL if dtype == jnp.bfloat16 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


# ---------------------------------------------------------------------------
# multi-hot pooling
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h", [1, 3, 5])
def test_multihot(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    k = len(sizes)
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    mega_z = jnp.concatenate([mega, jnp.zeros((1, d), jnp.float32)], axis=0)
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=(b, h)) for n in sizes], axis=1),
        dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, k, h)), dtype=jnp.float32)
    want = ref.ref_multi_hot_lookup(ids, mask, mega_z, offsets)
    got = ops.multi_table_lookup_multihot(ids, mask, pack_rows(mega_z),
                                          offsets, dim=d, strategy="pallas",
                                          interpret=True)
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# two-level (cache + backing) gather
# ---------------------------------------------------------------------------

def _split_cache(rng, mega, capacity):
    """Random hot set of ``capacity`` rows + its slot map."""
    n = mega.shape[0]
    hot = np.sort(rng.choice(n, size=capacity, replace=False))
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(capacity, dtype=np.int32)
    cache = jnp.take(mega, jnp.asarray(hot), axis=0)
    return cache, jnp.asarray(slot_of_row)


@pytest.mark.parametrize("capacity", [1, 16, 48])
def test_two_level_gather_matches_dense(capacity):
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    cache, slot_of_row = _split_cache(rng, mega, capacity)
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=b) for n in sizes], axis=1),
        dtype=jnp.int32)
    mega, cache = pack_rows(mega), pack_rows(cache)
    want = ops.multi_table_lookup(ids, mega, offsets, dim=d, strategy="jnp")
    got = ops.multi_table_lookup_cached(ids, cache, mega, slot_of_row,
                                        offsets, strategy="jnp", dim=d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_pl = ops.multi_table_lookup_cached(ids, cache, mega, slot_of_row,
                                           offsets, strategy="pallas",
                                           interpret=True, dim=d)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))


@pytest.mark.parametrize("h", [1, 3])
def test_two_level_multihot_matches_dense(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    k = len(sizes)
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    mega_z = jnp.concatenate([mega, jnp.zeros((1, d), jnp.float32)], axis=0)
    cache, slot_of_row = _split_cache(rng, mega_z, 16)
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=(b, h)) for n in sizes], axis=1),
        dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, k, h)), dtype=jnp.float32)
    mega_z, cache = pack_rows(mega_z), pack_rows(cache)
    want_jnp = ops.multi_table_lookup_multihot(ids, mask, mega_z, offsets,
                                               strategy="jnp", dim=d)
    got_jnp = ops.multi_table_lookup_cached_multihot(
        ids, mask, cache, mega_z, slot_of_row, offsets, strategy="jnp",
        dim=d)
    np.testing.assert_array_equal(np.asarray(got_jnp), np.asarray(want_jnp))
    want_pl = ops.multi_table_lookup_multihot(ids, mask, mega_z, offsets,
                                              strategy="pallas",
                                              interpret=True, dim=d)
    got_pl = ops.multi_table_lookup_cached_multihot(
        ids, mask, cache, mega_z, slot_of_row, offsets, strategy="pallas",
        interpret=True, dim=d)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want_pl))


# ---------------------------------------------------------------------------
# three-level (cache / staging / zero-guard) gather
# ---------------------------------------------------------------------------

def _split_tiers(rng, mega, capacity, staged):
    """Disjoint cache + staging over ``mega``'s rows, plus both slot maps
    (rows in neither tier keep -1 in both — the zero-guard case)."""
    n = mega.shape[0]
    pick = rng.choice(n, size=capacity + staged, replace=False)
    hot, warm = np.sort(pick[:capacity]), np.sort(pick[capacity:])
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(capacity, dtype=np.int32)
    smap = np.full(n, -1, dtype=np.int32)
    smap[warm] = np.arange(staged, dtype=np.int32)
    cache = jnp.take(mega, jnp.asarray(hot), axis=0)
    staging = jnp.take(mega, jnp.asarray(warm), axis=0)
    return cache, staging, jnp.asarray(slot_of_row), jnp.asarray(smap)


@pytest.mark.parametrize("capacity", [1, 16, 40])
def test_three_level_gather_matches_dense_when_fully_staged(capacity):
    """Every row in some tier -> bitwise equal to the dense gather (the
    HostBackedStore contract: the serve path stages all misses first)."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    cache, staging, slot_of_row, smap = _split_tiers(
        rng, mega, capacity, mega.shape[0] - capacity)   # all rows covered
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=b) for n in sizes], axis=1),
        dtype=jnp.int32)
    mega, cache, staging = (pack_rows(t) for t in (mega, cache, staging))
    want = ops.multi_table_lookup(ids, mega, offsets, dim=d, strategy="jnp")
    got = ops.multi_table_lookup_host(ids, cache, staging, slot_of_row,
                                      smap, offsets, strategy="jnp", dim=d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_pl = ops.multi_table_lookup_host(ids, cache, staging, slot_of_row,
                                         smap, offsets, strategy="pallas",
                                         interpret=True, dim=d)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))


def test_three_level_gather_zero_guards_unresolved_rows():
    rng = np.random.default_rng(0)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    cache, staging, slot_of_row, smap = _split_tiers(rng, mega, 8, 8)
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=b) for n in sizes], axis=1),
        dtype=jnp.int32)
    mega, cache, staging = (pack_rows(t) for t in (mega, cache, staging))
    for strategy in ("jnp", "pallas"):
        got = np.asarray(ops.multi_table_lookup_host(
            ids, cache, staging, slot_of_row, smap, offsets,
            strategy=strategy, interpret=True,
            dim=d)).reshape(b, len(sizes), d)
        rows = np.asarray(ids) + np.asarray(offsets)[None, :]
        unresolved = ((np.asarray(slot_of_row)[rows] < 0)
                      & (np.asarray(smap)[rows] < 0))
        assert unresolved.any()
        assert np.all(got[unresolved] == 0.0)
        want = np.asarray(ops.multi_table_lookup(
            ids, mega, offsets, dim=d,
            strategy="jnp")).reshape(b, len(sizes), d)
        np.testing.assert_array_equal(got[~unresolved], want[~unresolved])


@pytest.mark.parametrize("h", [1, 3])
def test_three_level_multihot_matches_dense(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    k = len(sizes)
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    mega_z = jnp.concatenate([mega, jnp.zeros((1, d), jnp.float32)], axis=0)
    cache, staging, slot_of_row, smap = _split_tiers(
        rng, mega_z, 16, mega_z.shape[0] - 16)           # all rows covered
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=(b, h)) for n in sizes], axis=1),
        dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, k, h)), dtype=jnp.float32)
    mega_z, cache, staging = (pack_rows(t) for t in (mega_z, cache, staging))
    # compare per strategy: jnp and pallas pool in different f32 orders
    for strategy in ("jnp", "pallas"):
        want = ops.multi_table_lookup_multihot(ids, mask, mega_z, offsets,
                                               strategy=strategy,
                                               interpret=True, dim=d)
        got = ops.multi_table_lookup_host_multihot(
            ids, mask, cache, staging, slot_of_row, smap, offsets,
            strategy=strategy, interpret=True, dim=d)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# fused non-GEMM kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,D", [(4, 16), (32, 80), (7, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_cross_v2(b, D, dtype):
    rng = np.random.default_rng(b * D)
    x0, xw, x = (jnp.asarray(rng.normal(size=(b, D)), dtype=dtype)
                 for _ in range(3))
    got = ops.fused_cross_v2(x0, xw, x, interpret=True)
    want = ref.ref_cross_v2_elementwise(x0, xw, x)
    tol = BF16_TOL if dtype == jnp.bfloat16 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("b,D", [(4, 16), (32, 80)])
def test_fused_cross_v1(b, D):
    rng = np.random.default_rng(b + D)
    x0 = jnp.asarray(rng.normal(size=(b, D)), dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, D)), dtype=jnp.float32)
    bias = jnp.asarray(rng.normal(size=(D,)), dtype=jnp.float32)
    xlw = jnp.asarray(rng.normal(size=(b, 1)), dtype=jnp.float32)
    got = ops.fused_cross_v1(x0, xlw, bias, x, interpret=True)
    want = ref.ref_cross_v1_elementwise(x0, xlw, bias, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,k,d", [(4, 3, 8), (32, 13, 16), (16, 39, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_fm(b, k, d, dtype):
    rng = np.random.default_rng(b * k)
    v = jnp.asarray(rng.normal(size=(b, k, d)), dtype=dtype)
    got = ops.fused_fm_second_order(v, interpret=True)[:, 0]
    want = ref.ref_fm_second_order(v.astype(jnp.float32))
    tol = BF16_TOL if dtype == jnp.bfloat16 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


# ---------------------------------------------------------------------------
# quantized (int8 rows + per-row fp32 scale) gathers
# ---------------------------------------------------------------------------

from repro import quant  # noqa: E402


def _q8_split_cache(rng, mega, capacity):
    """Quantize the mega-table once, carve a random hot set out of the
    shared int8 grid (cache and backing hold verbatim copies + scales)."""
    q, scale = quant.quantize_rows(mega)
    n = mega.shape[0]
    hot = np.sort(rng.choice(n, size=capacity, replace=False))
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(capacity, dtype=np.int32)
    cache = jnp.take(q, jnp.asarray(hot), axis=0)
    cache_scale = jnp.take(scale, jnp.asarray(hot), axis=0)
    return (pack_rows(q), scale, pack_rows(cache), cache_scale,
            jnp.asarray(slot_of_row))


@pytest.mark.parametrize("capacity", [1, 16, 48])
def test_two_level_q8_round_trip_bound(capacity):
    """Per-element error of the dequantized gather stays within half the
    int8 grid step (scale = absmax/127) of the fp32 dense lookup."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    q, scale, cache, cscale, slot_of_row = _q8_split_cache(
        rng, mega, capacity)
    ids = make_ids(rng, sizes, b)
    want = np.asarray(ops.multi_table_lookup(
        ids, pack_rows(mega), offsets, dim=d,
        strategy="jnp")).reshape(b, len(sizes), d)
    got = np.asarray(ops.multi_table_lookup_cached_q8(
        ids, cache, cscale, q, scale, slot_of_row, offsets,
        strategy="jnp", dim=d)).reshape(b, len(sizes), d)
    rows = np.asarray(ids) + np.asarray(offsets)[None, :]
    bound = np.asarray(scale)[rows] * 0.5 + 1e-7      # (b, k, 1) per row
    assert np.all(np.abs(got - want) <= bound)


@pytest.mark.parametrize("capacity", [1, 16, 48])
def test_two_level_q8_kernel_matches_ref(capacity):
    """The Pallas kernel (interpret mode) is bitwise equal to the jnp ref
    twin — both select the int8 payload + scale, then multiply once."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    q, scale, cache, cscale, slot_of_row = _q8_split_cache(
        rng, mega, capacity)
    ids = make_ids(rng, sizes, b)
    got_jnp = ops.multi_table_lookup_cached_q8(
        ids, cache, cscale, q, scale, slot_of_row, offsets, strategy="jnp",
        dim=d)
    got_pl = ops.multi_table_lookup_cached_q8(
        ids, cache, cscale, q, scale, slot_of_row, offsets,
        strategy="pallas", interpret=True, dim=d)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_jnp))


@pytest.mark.parametrize("h", [1, 3])
def test_two_level_q8_multihot_pooled(h):
    """Pooled multi-hot: fp32 pooling after per-row dequant, masked slots
    hit the zero row (int8 payload 0 -> exact 0.0), and the pooled error
    stays within the sum of the contributing rows' half grid steps."""
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    k = len(sizes)
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    mega_z = jnp.concatenate([mega, jnp.zeros((1, d), jnp.float32)], axis=0)
    q, scale, cache, cscale, slot_of_row = _q8_split_cache(rng, mega_z, 16)
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=(b, h)) for n in sizes], axis=1),
        dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, k, h)), dtype=jnp.float32)
    want = np.asarray(ops.multi_table_lookup_multihot(
        ids, mask, pack_rows(mega_z), offsets, strategy="jnp",
        dim=d)).reshape(b, k, d)
    for strategy in ("jnp", "pallas"):
        got = np.asarray(ops.multi_table_lookup_cached_q8_multihot(
            ids, mask, cache, cscale, q, scale, slot_of_row, offsets,
            strategy=strategy, interpret=True, dim=d)).reshape(b, k, d)
        rows = np.asarray(ids) + np.asarray(offsets)[None, :, None]
        row_scale = np.asarray(scale)[rows][..., 0]    # (b, k, h)
        bound = ((row_scale * 0.5 + 1e-7)
                 * np.asarray(mask)).sum(axis=-1, keepdims=True)
        assert np.all(np.abs(got - want) <= bound + 1e-6)


@pytest.mark.parametrize("capacity", [1, 16, 40])
def test_three_level_q8_staged_round_trip(capacity):
    """Fully staged three-level q8 path: within the per-row grid-step
    bound of the fp32 dense gather, and kernel == ref bitwise."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    q, scale = quant.quantize_rows(mega)
    n = mega.shape[0]
    pick = rng.choice(n, size=n, replace=False)
    hot, warm = np.sort(pick[:capacity]), np.sort(pick[capacity:])
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(capacity, dtype=np.int32)
    smap = np.full(n, -1, dtype=np.int32)
    smap[warm] = np.arange(n - capacity, dtype=np.int32)
    cache = pack_rows(jnp.take(q, jnp.asarray(hot), axis=0))
    cscale = jnp.take(scale, jnp.asarray(hot), axis=0)
    staging = pack_rows(jnp.take(q, jnp.asarray(warm), axis=0))
    sscale = jnp.take(scale, jnp.asarray(warm), axis=0)
    ids = make_ids(rng, sizes, b)
    want = np.asarray(ops.multi_table_lookup(
        ids, pack_rows(mega), offsets, dim=d,
        strategy="jnp")).reshape(b, len(sizes), d)
    got_jnp = ops.multi_table_lookup_host_q8(
        ids, cache, cscale, staging, sscale, jnp.asarray(slot_of_row),
        jnp.asarray(smap), offsets, strategy="jnp", dim=d)
    got_pl = ops.multi_table_lookup_host_q8(
        ids, cache, cscale, staging, sscale, jnp.asarray(slot_of_row),
        jnp.asarray(smap), offsets, strategy="pallas", interpret=True,
        dim=d)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_jnp))
    rows = np.asarray(ids) + np.asarray(offsets)[None, :]
    bound = np.asarray(scale)[rows] * 0.5 + 1e-7
    got = np.asarray(got_jnp).reshape(b, len(sizes), d)
    assert np.all(np.abs(got - want) <= bound)


def test_three_level_q8_zero_guards_unresolved_rows():
    rng = np.random.default_rng(0)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    q, scale = quant.quantize_rows(mega)
    n = mega.shape[0]
    pick = rng.choice(n, size=16, replace=False)
    hot, warm = np.sort(pick[:8]), np.sort(pick[8:])
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(8, dtype=np.int32)
    smap = np.full(n, -1, dtype=np.int32)
    smap[warm] = np.arange(8, dtype=np.int32)
    cache = pack_rows(jnp.take(q, jnp.asarray(hot), axis=0))
    cscale = jnp.take(scale, jnp.asarray(hot), axis=0)
    staging = pack_rows(jnp.take(q, jnp.asarray(warm), axis=0))
    sscale = jnp.take(scale, jnp.asarray(warm), axis=0)
    ids = make_ids(rng, sizes, b)
    for strategy in ("jnp", "pallas"):
        got = np.asarray(ops.multi_table_lookup_host_q8(
            ids, cache, cscale, staging, sscale, jnp.asarray(slot_of_row),
            jnp.asarray(smap), offsets, strategy=strategy,
            interpret=True, dim=d)).reshape(b, len(sizes), d)
        rows = np.asarray(ids) + np.asarray(offsets)[None, :]
        unresolved = (slot_of_row[rows] < 0) & (smap[rows] < 0)
        assert unresolved.any()
        assert np.all(got[unresolved] == 0.0)


@pytest.mark.parametrize("h", [1, 3])
def test_three_level_q8_multihot_matches_jnp_twin(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    k = len(sizes)
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    mega_z = jnp.concatenate([mega, jnp.zeros((1, d), jnp.float32)], axis=0)
    q, scale = quant.quantize_rows(mega_z)
    n = mega_z.shape[0]
    pick = rng.choice(n, size=n, replace=False)
    hot, warm = np.sort(pick[:16]), np.sort(pick[16:])
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(16, dtype=np.int32)
    smap = np.full(n, -1, dtype=np.int32)
    smap[warm] = np.arange(n - 16, dtype=np.int32)
    cache = pack_rows(jnp.take(q, jnp.asarray(hot), axis=0))
    cscale = jnp.take(scale, jnp.asarray(hot), axis=0)
    staging = pack_rows(jnp.take(q, jnp.asarray(warm), axis=0))
    sscale = jnp.take(scale, jnp.asarray(warm), axis=0)
    ids = jnp.asarray(
        np.stack([rng.integers(0, n_, size=(b, h)) for n_ in sizes], axis=1),
        dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=(b, k, h)), dtype=jnp.float32)
    got_jnp = ops.multi_table_lookup_host_q8_multihot(
        ids, mask, cache, cscale, staging, sscale, jnp.asarray(slot_of_row),
        jnp.asarray(smap), offsets, strategy="jnp", dim=d)
    got_pl = ops.multi_table_lookup_host_q8_multihot(
        ids, mask, cache, cscale, staging, sscale, jnp.asarray(slot_of_row),
        jnp.asarray(smap), offsets, strategy="pallas", interpret=True, dim=d)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(got_jnp),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# quantized dense matmul (int8 x int8 -> int32, fused dequant epilogue)
# ---------------------------------------------------------------------------


def _q8_mlp_layer(rng, b, fan_in, fan_out):
    h = jnp.asarray(rng.normal(size=(b, fan_in)), dtype=jnp.float32)
    w = jnp.asarray(rng.normal(size=(fan_in, fan_out)), dtype=jnp.float32)
    bias = jnp.asarray(rng.normal(size=(fan_out,)), dtype=jnp.float32)
    wq, wscale = quant.quantize_channels(w)
    return h, w, bias, wq, wscale


@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("b,fan_in,fan_out", [
    (1, 1, 1), (4, 16, 8), (32, 80, 96), (33, 7, 5),   # odd, non-multiple
])
def test_dense_matmul_q8_kernel_matches_ref(relu, b, fan_in, fan_out):
    """The Pallas kernel (interpret mode) is bitwise equal to the jitted
    jnp twin — same int32 accumulate, same epilogue multiply order."""
    rng = np.random.default_rng(b * 101 + fan_in)
    h, _, bias, wq, wscale = _q8_mlp_layer(rng, b, fan_in, fan_out)
    want = ops.dense_matmul_q8(h, wq, wscale, bias, relu=relu,
                               strategy="jnp")
    got = ops.dense_matmul_q8(h, wq, wscale, bias, relu=relu,
                              strategy="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("relu", [True, False])
def test_dense_matmul_q8_error_bound_vs_fp32(relu):
    """Quantized output stays within the propagated grid-step budget of
    the fp32 matmul: each product term errs by at most
    (|h|·ws/2 + |w|·hs/2 + hs·ws/4) so the row-sum bound is linear in
    fan_in; ReLU never widens it (1-Lipschitz)."""
    rng = np.random.default_rng(7)
    b, fan_in, fan_out = 16, 64, 32
    h, w, bias, wq, wscale = _q8_mlp_layer(rng, b, fan_in, fan_out)
    hscale = quant.absmax_scale(h, axis=-1)

    exact = np.asarray(h) @ np.asarray(w) + np.asarray(bias)[None, :]
    if relu:
        exact = np.maximum(exact, 0.0)
    got = np.asarray(ops.dense_matmul_q8(h, wq, wscale, bias, relu=relu,
                                         strategy="jnp"))

    hs, ws = np.asarray(hscale), np.asarray(wscale)
    habs, wabs = np.abs(np.asarray(h)), np.abs(np.asarray(w))
    bound = (habs @ (np.ones_like(wabs) * ws) * 0.5
             + (np.ones_like(habs) * hs) @ wabs * 0.5
             + fan_in * hs * ws * 0.25) + 1e-5
    assert np.all(np.abs(got - exact) <= bound)


def test_dense_matmul_q8_batch_grid_tiling():
    """Batches that straddle the block_b grid tile bitwise-match the
    single-tile result (same rows, different grid decomposition)."""
    rng = np.random.default_rng(3)
    h, _, bias, wq, wscale = _q8_mlp_layer(rng, 24, 16, 8)
    one = ops.dense_matmul_q8(h, wq, wscale, bias, strategy="pallas",
                              interpret=True)
    from repro.kernels.dense_matmul import dmm_q8
    hscale = quant.absmax_scale(h, axis=-1)
    hq = quant.quantize(h, hscale)
    tiled = dmm_q8(hq, hscale, wq, wscale, bias.reshape(1, -1),
                   block_b=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(tiled))


# ---------------------------------------------------------------------------
# packed (lane-dense) table layout + every production gather vs its twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,d", [(jnp.float32, 1), (jnp.float32, 16),
                                     (jnp.float32, 32), (jnp.int8, 16),
                                     (jnp.int8, 32), (jnp.bfloat16, 8)])
def test_pack_rows_round_trip(dtype, d):
    """pack -> unpack is the identity on every row (plus zero padding up
    to whole 128-lane lines), for jax and numpy tables alike."""
    rng = np.random.default_rng(d)
    n = 77
    rows = np.asarray(rng.normal(size=(n, d)) * 50).astype(
        np.dtype(dtype))
    for table in (jnp.asarray(rows), rows):
        lines = pack_rows(table)
        assert lines.shape[1] == 128 and lines.dtype.itemsize == 4
        back = np.asarray(unpack_rows(lines, d, np.dtype(dtype)))
        np.testing.assert_array_equal(back[:n], rows)
        assert not back[n:].any()


def test_set_rows_touches_only_the_given_rows():
    from repro.kernels.multi_table_lookup import set_rows
    rng = np.random.default_rng(0)
    table = rng.normal(size=(64, 16)).astype(np.float32)
    ids = np.array([0, 5, 7, 63])
    new = rng.normal(size=(4, 16)).astype(np.float32)
    got = unpack_rows(set_rows(pack_rows(jnp.asarray(table)), ids, new),
                      16, np.float32)
    table[ids] = new
    np.testing.assert_array_equal(np.asarray(got), table)


def _tiered_case(rng, d, fmt):
    sizes = [300, 45, 700]
    n = sum(sizes) + 1
    table = (rng.normal(size=(n, d)) * 0.05).astype(np.float32)
    table[-1] = 0
    scale = None
    if fmt == "int8":
        from repro import quant
        q, scale = quant.quantize_rows(table)
        table, scale = np.asarray(q), np.asarray(scale)
    offsets = jnp.asarray(np.cumsum([0] + sizes[:-1]), jnp.int32)
    pick = rng.choice(n, size=240, replace=False)
    hot, warm = pick[:96], pick[96:]
    cmap = np.full(n, -1, np.int32)
    cmap[hot] = np.arange(hot.size)
    smap = np.full(n, -1, np.int32)
    smap[warm] = np.arange(warm.size)
    ids = jnp.asarray(np.stack([rng.integers(0, s, size=40) for s in sizes],
                               axis=1), jnp.int32)
    return table, scale, offsets, hot, warm, cmap, smap, ids


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("fmt", ["fp32", "int8"])
@pytest.mark.parametrize("tier", ["dense", "two_level", "three_level"])
def test_gather_matches_ref_twin_bitwise(tier, fmt, d):
    """Every production gather over *packed* tables (the store layout),
    interpret mode, is bitwise equal to its jnp twin in ``kernels/ref.py``
    at the paper's widths d ∈ {16, 32}."""
    rng = np.random.default_rng(d + len(tier) + len(fmt))
    table, scale, offsets, hot, warm, cmap, smap, ids = _tiered_case(
        rng, d, fmt)
    rows = (ids + offsets[None, :]).reshape(-1)
    pk = lambda a: pack_rows(jnp.asarray(a))
    if tier == "dense":
        got = mtl_gather(rows, pk(table), dim=d, dtype=table.dtype,
                         interpret=True)
        want = jnp.take(jnp.asarray(table), rows, axis=0)
    elif tier == "two_level" and fmt == "fp32":
        got = ops.multi_table_lookup_cached(
            ids, pk(table[hot]), pk(table), jnp.asarray(cmap), offsets,
            strategy="pallas", interpret=True, dim=d)
        want = ref.ref_two_level_gather(rows, jnp.asarray(cmap),
                                        jnp.asarray(table[hot]),
                                        jnp.asarray(table))
    elif tier == "two_level":
        got = ops.multi_table_lookup_cached_q8(
            ids, pk(table[hot]), jnp.asarray(scale[hot]), pk(table),
            jnp.asarray(scale), jnp.asarray(cmap), offsets,
            strategy="pallas", interpret=True, dim=d)
        want = ref.ref_two_level_gather_q8(
            rows, jnp.asarray(cmap), jnp.asarray(table[hot]),
            jnp.asarray(scale[hot]), jnp.asarray(table), jnp.asarray(scale))
    elif fmt == "fp32":
        got = ops.multi_table_lookup_host(
            ids, pk(table[hot]), pk(table[warm]), jnp.asarray(cmap),
            jnp.asarray(smap), offsets, strategy="pallas", interpret=True,
            dim=d)
        want = ref.ref_three_level_gather(
            rows, jnp.asarray(cmap), jnp.asarray(smap),
            jnp.asarray(table[hot]), jnp.asarray(table[warm]))
    else:
        got = ops.multi_table_lookup_host_q8(
            ids, pk(table[hot]), jnp.asarray(scale[hot]), pk(table[warm]),
            jnp.asarray(scale[warm]), jnp.asarray(cmap), jnp.asarray(smap),
            offsets, strategy="pallas", interpret=True, dim=d)
        want = ref.ref_three_level_gather_q8(
            rows, jnp.asarray(cmap), jnp.asarray(smap),
            jnp.asarray(table[hot]), jnp.asarray(scale[hot]),
            jnp.asarray(table[warm]), jnp.asarray(scale[warm]))
    np.testing.assert_array_equal(np.asarray(got).reshape(want.shape),
                                  np.asarray(want))


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("tier,fmt", [("dense", "fp32"),
                                      ("two_level", "fp32"),
                                      ("three_level", "fp32")])
def test_pooled_gather_matches_ref_twin_bitwise(tier, fmt, d):
    """The multi-hot (sum-pooled) fp32 gathers over packed tables equal
    their jnp twins bitwise: both add a row's slots in slot order, and a
    masked slot adds an exact zero. (The int8 forms differ from their
    twins in the last bits in interpret mode on CPU: XLA's CPU backend
    fuses the kernel's dequantizing multiply and the pooled add into one
    multiply-add, rounded once. Their tests above compare at rtol 1e-6;
    on a TPU they matched bitwise.)"""
    rng = np.random.default_rng(d + len(tier) + len(fmt))
    table, scale, offsets, hot, warm, cmap, smap, _ = _tiered_case(
        rng, d, fmt)
    h = 3
    ids = jnp.asarray(np.stack(
        [rng.integers(0, s, size=(40, h)) for s in (300, 45, 700)], axis=1),
        jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=ids.shape), jnp.float32)
    pk = lambda a: pack_rows(jnp.asarray(a))
    cm, sm = jnp.asarray(cmap), jnp.asarray(smap)
    if fmt == "int8":
        cs, ss, bs = (jnp.asarray(scale[hot]), jnp.asarray(scale[warm]),
                      jnp.asarray(scale))

    def run(strategy):
        kw = dict(strategy=strategy, interpret=True, dim=d)
        if tier == "dense":
            return ops.multi_table_lookup_multihot(ids, mask, pk(table),
                                                   offsets, **kw)
        if tier == "two_level" and fmt == "fp32":
            return ops.multi_table_lookup_cached_multihot(
                ids, mask, pk(table[hot]), pk(table), cm, offsets, **kw)
        if tier == "two_level":
            return ops.multi_table_lookup_cached_q8_multihot(
                ids, mask, pk(table[hot]), cs, pk(table), bs, cm, offsets,
                **kw)
        if fmt == "fp32":
            return ops.multi_table_lookup_host_multihot(
                ids, mask, pk(table[hot]), pk(table[warm]), cm, sm, offsets,
                **kw)
        return ops.multi_table_lookup_host_q8_multihot(
            ids, mask, pk(table[hot]), cs, pk(table[warm]), ss, cm, sm,
            offsets, **kw)

    np.testing.assert_array_equal(np.asarray(run("pallas")),
                                  np.asarray(run("jnp")))
