"""Per-kernel allclose validation against the pure-jnp oracles.

Sweeps shapes and dtypes per the deliverable: every Pallas kernel is executed
in interpret mode (CPU) and compared against repro.kernels.ref. Lookups are
checked where they run: the one gather (``ops.gather_tiers`` /
``mtl_gather_tiered``) against its twin ``ref.ref_tiered_gather``, and each
store's routing through ``store.lookup`` over hand-built tiers.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.embedding import (CachedStore, DenseStore, FusedEmbeddingSpec,
                             HostBackedStore)
from repro.kernels import ops, ref
from repro.kernels.multi_table_lookup import (
    mtl_gather_tiered,
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


def plain_lookup(ids, mega, offsets):
    """The plain reference: one take per slot off the logical table."""
    rows = (ids + offsets[None, :]).reshape(-1)
    return jnp.take(mega, rows, axis=0).reshape(ids.shape[0], -1)


def plain_pooled(ids, mask, mega, offsets):
    """Plain multi-hot reference: (b, k, h) ids, masked slots dropped,
    each field's slots summed."""
    b, k, h = ids.shape
    rows = (ids + offsets[None, :, None]).reshape(-1)
    vals = jnp.take(mega, rows, axis=0).reshape(b, k, h, -1)
    return jnp.sum(vals * mask[..., None], axis=2).reshape(b, -1)


def one_tier(ids, offsets):
    """(tier, row) of a single-table gather of (b, k) ids."""
    rows = (ids + offsets[None, :]).reshape(-1)
    return jnp.zeros_like(rows), rows


def make_store(kind, sizes, d, fmt="fp32", capacity=1):
    """A store of ``kind`` over ``sizes``; the tests hand it its tiers."""
    spec = FusedEmbeddingSpec(field_sizes=tuple(sizes), dim=d,
                              row_dtype="int8" if fmt == "int8" else None)
    if kind == "dense":
        return DenseStore(spec)
    cls = CachedStore if kind == "cached" else HostBackedStore
    return cls(spec, capacity=capacity)


def pooled_lookup(store, params, ids, mask, offsets, strategy):
    """Multi-hot (b, k, h) ids through ``store``'s route into the pooled
    gather: a masked slot becomes tier -1 (the zero guard) and each output
    row sums its ``h`` slots — in ``mtl_gather_tiered(hot=h)`` for
    "pallas", after ``ref_tiered_gather`` for "jnp"."""
    b, k, h = ids.shape
    d = store.spec.dim
    rows = (ids + offsets[None, :, None]).reshape(-1)
    tier, row, tables, scale = store._route(
        params, rows, rows, jnp.ones(rows.shape, bool), True)
    tier = jnp.where(mask.reshape(-1) > 0, tier, -1)
    if strategy == "pallas":
        out = mtl_gather_tiered(tier, row, tables, scale, hot=h, dim=d,
                                dtype=store.wire_dtype, interpret=True)
    else:
        out = ref.ref_tiered_gather(
            tier, row, tuple(store.unpack(t) for t in tables), scale)
        out = jnp.sum(out.reshape(-1, h, d), axis=1)
    return out.reshape(b, k * d)


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
    tier, rows = one_tier(ids, offsets)
    vec = ref.ref_tiered_gather(tier, rows, (mega,)).reshape(b, -1)
    np.testing.assert_allclose(lit, vec, **TOL)


# ---------------------------------------------------------------------------
# the one gather (output-first, the paper's kernel) — shape × dtype sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [8, 16, 32, 128])
@pytest.mark.parametrize("b,k", [(4, 2), (16, 5), (32, 9)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mtl_gather_sweep(b, k, d, dtype):
    rng = np.random.default_rng(b * k * d)
    sizes = list(rng.integers(2, 50, size=k))
    _, mega, offsets = make_tables(rng, sizes, d, dtype)
    ids = make_ids(rng, sizes, b)
    tier, rows = one_tier(ids, offsets)
    want = ref.ref_tiered_gather(tier, rows, (mega,))
    got = mtl_gather_tiered(tier, rows, (pack_rows(mega),), hot=1, dim=d,
                            dtype=np.dtype(dtype), interpret=True)
    tol = BF16_TOL if dtype == jnp.bfloat16 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("strategy", ["jnp", "pallas", "serial", "input_first"])
def test_ops_dispatch_equivalence(strategy):
    rng = np.random.default_rng(7)
    sizes, d, b = [11, 3, 40, 8], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    ids = make_ids(rng, sizes, b)
    want = plain_lookup(ids, mega, offsets)
    if strategy == "serial":
        got = ops.multi_table_lookup_serial(ids, mega, offsets)
    elif strategy == "input_first":
        got = ops.multi_table_lookup_input_first(ids, mega, offsets,
                                                 interpret=True)
    else:
        tier, rows = one_tier(ids, offsets)
        got = ops.gather_tiers(tier, rows, (pack_rows(mega),), dim=d,
                               dtype=jnp.float32, strategy=strategy,
                               interpret=True).reshape(b, -1)
    np.testing.assert_allclose(got, want, **TOL)


def test_input_first_matches_output_first():
    """Fig.-11 pair must be numerically identical (only layout differs)."""
    rng = np.random.default_rng(3)
    sizes, d, b = [9, 21], 8, 10
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    ids = make_ids(rng, sizes, b)
    tier, rows = one_tier(ids, offsets)
    a = ops.gather_tiers(tier, rows, (pack_rows(mega),), dim=d,
                         dtype=jnp.float32, strategy="pallas",
                         interpret=True).reshape(b, -1)
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
# multi-hot pooling (the gather's ``hot`` slots a row; masked slot = tier -1)
# ---------------------------------------------------------------------------

def _multihot_ids(rng, sizes, b, h):
    ids = jnp.asarray(
        np.stack([rng.integers(0, n, size=(b, h)) for n in sizes], axis=1),
        dtype=jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=ids.shape), dtype=jnp.float32)
    return ids, mask


@pytest.mark.parametrize("h", [1, 3, 5])
def test_multihot(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    ids, mask = _multihot_ids(rng, sizes, b, h)
    want = plain_pooled(ids, mask, mega, offsets)
    store = make_store("dense", sizes, d)
    got = pooled_lookup(store, {"mega_table": pack_rows(mega)}, ids, mask,
                        offsets, "pallas")
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# two-level (cache + backing): CachedStore's route
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
    ids = make_ids(rng, sizes, b)
    dense = {"mega_table": pack_rows(mega)}
    cached = {"cache": pack_rows(cache), "backing": pack_rows(mega),
              "slot_of_row": slot_of_row}
    want = make_store("dense", sizes, d).lookup(dense, ids, offsets,
                                                strategy="jnp")
    store = make_store("cached", sizes, d, capacity=capacity)
    got = store.lookup(cached, ids, offsets, strategy="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_pl = store.lookup(cached, ids, offsets, strategy="pallas",
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))


@pytest.mark.parametrize("h", [1, 3])
def test_two_level_multihot_matches_dense(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    cache, slot_of_row = _split_cache(rng, mega, 16)
    ids, mask = _multihot_ids(rng, sizes, b, h)
    dense = (make_store("dense", sizes, d), {"mega_table": pack_rows(mega)})
    cached = (make_store("cached", sizes, d, capacity=16),
              {"cache": pack_rows(cache), "backing": pack_rows(mega),
               "slot_of_row": slot_of_row})
    for strategy in ("jnp", "pallas"):
        want = pooled_lookup(*dense, ids, mask, offsets, strategy)
        got = pooled_lookup(*cached, ids, mask, offsets, strategy)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# three-level (cache / staging / zero-guard): HostBackedStore's route
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


def _host_params(cache, staging, slot_of_row, smap):
    return {"cache": pack_rows(cache), "staging": pack_rows(staging),
            "slot_of_row": slot_of_row, "staging_slot_of_row": smap}


@pytest.mark.parametrize("capacity", [1, 16, 40])
def test_three_level_gather_matches_dense_when_fully_staged(capacity):
    """Every row in some tier -> bitwise equal to the dense gather (the
    HostBackedStore contract: the serve path stages all misses first)."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    tiers = _split_tiers(rng, mega, capacity,
                         mega.shape[0] - capacity)       # all rows covered
    ids = make_ids(rng, sizes, b)
    want = make_store("dense", sizes, d).lookup(
        {"mega_table": pack_rows(mega)}, ids, offsets, strategy="jnp")
    store = make_store("host", sizes, d, capacity=capacity)
    params = _host_params(*tiers)
    got = store.lookup(params, ids, offsets, strategy="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_pl = store.lookup(params, ids, offsets, strategy="pallas",
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))


def test_three_level_gather_zero_guards_unresolved_rows():
    rng = np.random.default_rng(0)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    cache, staging, slot_of_row, smap = _split_tiers(rng, mega, 8, 8)
    ids = make_ids(rng, sizes, b)
    store = make_store("host", sizes, d, capacity=8)
    params = _host_params(cache, staging, slot_of_row, smap)
    for strategy in ("jnp", "pallas"):
        got = np.asarray(store.lookup(
            params, ids, offsets, strategy=strategy,
            interpret=True)).reshape(b, len(sizes), d)
        rows = np.asarray(ids) + np.asarray(offsets)[None, :]
        unresolved = ((np.asarray(slot_of_row)[rows] < 0)
                      & (np.asarray(smap)[rows] < 0))
        assert unresolved.any()
        assert np.all(got[unresolved] == 0.0)
        want = np.asarray(plain_lookup(
            ids, mega, offsets)).reshape(b, len(sizes), d)
        np.testing.assert_array_equal(got[~unresolved], want[~unresolved])


@pytest.mark.parametrize("h", [1, 3])
def test_three_level_multihot_matches_dense(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    tiers = _split_tiers(rng, mega, 16, mega.shape[0] - 16)  # all covered
    ids, mask = _multihot_ids(rng, sizes, b, h)
    dense = (make_store("dense", sizes, d), {"mega_table": pack_rows(mega)})
    hosted = (make_store("host", sizes, d, capacity=16), _host_params(*tiers))
    # compare per strategy: jnp and pallas pool in different f32 orders
    for strategy in ("jnp", "pallas"):
        want = pooled_lookup(*dense, ids, mask, offsets, strategy)
        got = pooled_lookup(*hosted, ids, mask, offsets, strategy)
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
# quantized (int8 rows + per-row fp32 scale) stores
# ---------------------------------------------------------------------------

from repro import quant  # noqa: E402


def _q8_split_cache(rng, mega, capacity):
    """Quantize the mega-table once, carve a random hot set out of the
    shared int8 grid (cache and backing hold verbatim copies + scales):
    a quantized CachedStore's params."""
    q, scale = quant.quantize_rows(mega)
    n = mega.shape[0]
    hot = np.sort(rng.choice(n, size=capacity, replace=False))
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(capacity, dtype=np.int32)
    return {"backing": pack_rows(q), "backing_scale": scale,
            "cache": pack_rows(jnp.take(q, jnp.asarray(hot), axis=0)),
            "cache_scale": jnp.take(scale, jnp.asarray(hot), axis=0),
            "slot_of_row": jnp.asarray(slot_of_row)}


def _q8_split_tiers(rng, mega, capacity, staged):
    """Quantized HostBackedStore params over disjoint cache + staging
    tiers of one int8 grid, plus the (rows, 1) scales of that grid."""
    q, scale = quant.quantize_rows(mega)
    n = mega.shape[0]
    pick = rng.choice(n, size=capacity + staged, replace=False)
    hot, warm = np.sort(pick[:capacity]), np.sort(pick[capacity:])
    slot_of_row = np.full(n, -1, dtype=np.int32)
    slot_of_row[hot] = np.arange(capacity, dtype=np.int32)
    smap = np.full(n, -1, dtype=np.int32)
    smap[warm] = np.arange(staged, dtype=np.int32)
    params = {"cache": pack_rows(jnp.take(q, jnp.asarray(hot), axis=0)),
              "cache_scale": jnp.take(scale, jnp.asarray(hot), axis=0),
              "staging": pack_rows(jnp.take(q, jnp.asarray(warm), axis=0)),
              "staging_scale": jnp.take(scale, jnp.asarray(warm), axis=0),
              "slot_of_row": jnp.asarray(slot_of_row),
              "staging_slot_of_row": jnp.asarray(smap)}
    return params, scale


@pytest.mark.parametrize("capacity", [1, 16, 48])
def test_two_level_q8_round_trip_bound(capacity):
    """Per-element error of the dequantized gather stays within half the
    int8 grid step (scale = absmax/127) of the fp32 dense lookup."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    params = _q8_split_cache(rng, mega, capacity)
    ids = make_ids(rng, sizes, b)
    want = np.asarray(plain_lookup(ids, mega, offsets)).reshape(
        b, len(sizes), d)
    store = make_store("cached", sizes, d, "int8", capacity)
    got = np.asarray(store.lookup(params, ids, offsets,
                                  strategy="jnp")).reshape(b, len(sizes), d)
    rows = np.asarray(ids) + np.asarray(offsets)[None, :]
    bound = np.asarray(params["backing_scale"])[rows] * 0.5 + 1e-7
    assert np.all(np.abs(got - want) <= bound)


@pytest.mark.parametrize("capacity", [1, 16, 48])
def test_two_level_q8_kernel_matches_ref(capacity):
    """The Pallas kernel (interpret mode) is bitwise equal to the jnp ref
    twin — both select the int8 payload + scale, then multiply once."""
    rng = np.random.default_rng(capacity)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    params = _q8_split_cache(rng, mega, capacity)
    ids = make_ids(rng, sizes, b)
    store = make_store("cached", sizes, d, "int8", capacity)
    got_jnp = store.lookup(params, ids, offsets, strategy="jnp")
    got_pl = store.lookup(params, ids, offsets, strategy="pallas",
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_jnp))


@pytest.mark.parametrize("h", [1, 3])
def test_two_level_q8_multihot_pooled(h):
    """Pooled multi-hot: fp32 pooling after per-row dequant, masked slots
    read the zero guard (an exact 0.0), and the pooled error stays within
    the sum of the contributing rows' half grid steps."""
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    k = len(sizes)
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    params = _q8_split_cache(rng, mega, 16)
    ids, mask = _multihot_ids(rng, sizes, b, h)
    want = np.asarray(plain_pooled(ids, mask, mega, offsets)).reshape(b, k, d)
    store = make_store("cached", sizes, d, "int8", 16)
    for strategy in ("jnp", "pallas"):
        got = np.asarray(pooled_lookup(store, params, ids, mask, offsets,
                                       strategy)).reshape(b, k, d)
        rows = np.asarray(ids) + np.asarray(offsets)[None, :, None]
        row_scale = np.asarray(params["backing_scale"])[rows][..., 0]
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
    params, scale = _q8_split_tiers(rng, mega, capacity,
                                    mega.shape[0] - capacity)
    ids = make_ids(rng, sizes, b)
    want = np.asarray(plain_lookup(ids, mega, offsets)).reshape(
        b, len(sizes), d)
    store = make_store("host", sizes, d, "int8", capacity)
    got_jnp = store.lookup(params, ids, offsets, strategy="jnp")
    got_pl = store.lookup(params, ids, offsets, strategy="pallas",
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(got_jnp))
    rows = np.asarray(ids) + np.asarray(offsets)[None, :]
    bound = np.asarray(scale)[rows] * 0.5 + 1e-7
    got = np.asarray(got_jnp).reshape(b, len(sizes), d)
    assert np.all(np.abs(got - want) <= bound)


def test_three_level_q8_zero_guards_unresolved_rows():
    rng = np.random.default_rng(0)
    sizes, d, b = [13, 29, 6], 16, 24
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    params, _ = _q8_split_tiers(rng, mega, 8, 8)
    ids = make_ids(rng, sizes, b)
    store = make_store("host", sizes, d, "int8", 8)
    for strategy in ("jnp", "pallas"):
        got = np.asarray(store.lookup(
            params, ids, offsets, strategy=strategy,
            interpret=True)).reshape(b, len(sizes), d)
        rows = np.asarray(ids) + np.asarray(offsets)[None, :]
        unresolved = ((np.asarray(params["slot_of_row"])[rows] < 0)
                      & (np.asarray(params["staging_slot_of_row"])[rows] < 0))
        assert unresolved.any()
        assert np.all(got[unresolved] == 0.0)


@pytest.mark.parametrize("h", [1, 3])
def test_three_level_q8_multihot_matches_jnp_twin(h):
    rng = np.random.default_rng(h)
    sizes, d, b = [13, 29, 6], 16, 12
    _, mega, offsets = make_tables(rng, sizes, d, jnp.float32)
    params, _ = _q8_split_tiers(rng, mega, 16, mega.shape[0] - 16)
    ids, mask = _multihot_ids(rng, sizes, b, h)
    store = make_store("host", sizes, d, "int8", 16)
    got_jnp = pooled_lookup(store, params, ids, mask, offsets, "jnp")
    got_pl = pooled_lookup(store, params, ids, mask, offsets, "pallas")
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
    """Tables of three fields at a paper width, quantized for int8, with
    disjoint random cache and staging sets over them: the params of each
    store, the (b, k) ids and the plain expectation of every slot."""
    sizes = [300, 45, 700]
    n = sum(sizes) + 1
    table = (rng.normal(size=(n, d)) * 0.05).astype(np.float32)
    table[-1] = 0
    offsets = jnp.asarray(np.cumsum([0] + sizes[:-1]), jnp.int32)
    pick = rng.choice(n, size=240, replace=False)
    hot, warm = pick[:96], pick[96:]
    cmap = np.full(n, -1, np.int32)
    cmap[hot] = np.arange(hot.size)
    smap = np.full(n, -1, np.int32)
    smap[warm] = np.arange(warm.size)
    ids = jnp.asarray(np.stack([rng.integers(0, s, size=40) for s in sizes],
                               axis=1), jnp.int32)
    pk = lambda a: pack_rows(jnp.asarray(a))
    wire, values = table, table
    cached = {"cache": pk(table[hot]), "backing": pk(table),
              "slot_of_row": jnp.asarray(cmap)}
    host = {"cache": pk(table[hot]), "staging": pk(table[warm]),
            "slot_of_row": jnp.asarray(cmap),
            "staging_slot_of_row": jnp.asarray(smap)}
    if fmt == "int8":
        q, scale = quant.quantize_rows(table)
        wire, scale = np.asarray(q), np.asarray(scale)
        values = wire.astype(np.float32) * scale
        cached.update(cache=pk(wire[hot]), backing=pk(wire),
                      cache_scale=jnp.asarray(scale[hot]),
                      backing_scale=jnp.asarray(scale))
        host.update(cache=pk(wire[hot]), staging=pk(wire[warm]),
                    cache_scale=jnp.asarray(scale[hot]),
                    staging_scale=jnp.asarray(scale[warm]))
    params = {"dense": {"mega_table": pk(wire)}, "cached": cached,
              "host": host}
    rows = np.asarray(ids + offsets[None, :]).reshape(-1)
    staged = (cmap[rows] >= 0) | (smap[rows] >= 0)
    return sizes, offsets, ids, params, values, rows, staged


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("fmt", ["fp32", "int8"])
@pytest.mark.parametrize("tier", ["dense", "two_level", "three_level"])
def test_gather_matches_ref_twin_bitwise(tier, fmt, d):
    """Every store's route over *packed* tables (the store layout) feeds
    ``gather_tiers``, whose Pallas kernel (interpret mode) is bitwise
    equal to its jnp twin ``ref_tiered_gather`` and to the plain
    expectation (int8: the dequantized grid; host: zero where no tier
    holds the row) at the paper's widths d ∈ {16, 32}. The dense int8
    case is the raw-row gather refreshes use (``gather_rows``)."""
    rng = np.random.default_rng(d + len(tier) + len(fmt))
    sizes, offsets, ids, params, values, rows, staged = _tiered_case(
        rng, d, fmt)
    kind = {"dense": "dense", "two_level": "cached",
            "three_level": "host"}[tier]
    store = make_store(kind, sizes, d, fmt, capacity=96)
    env = params[kind]
    flat = jnp.asarray(rows)
    tier_, row, tables, scale = store._route(
        env, flat, flat, jnp.ones(flat.shape, bool), True)
    got = ops.gather_tiers(tier_, row, tables, scale, dim=d,
                           dtype=store.wire_dtype, strategy="pallas",
                           interpret=True)
    twin = ref.ref_tiered_gather(
        tier_, row, tuple(store.unpack(t) for t in tables), scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))
    if kind == "dense":
        want = np.asarray(store.unpack(env["mega_table"]))[rows]
    else:
        want = values[rows]
        if kind == "host":
            want = np.where(staged[:, None], want, 0)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(store.lookup(env, ids, offsets, strategy="pallas",
                                interpret=True)).reshape(want.shape), want)


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("tier,fmt", [("dense", "fp32"),
                                      ("two_level", "fp32"),
                                      ("three_level", "fp32")])
def test_pooled_gather_matches_ref_twin_bitwise(tier, fmt, d):
    """The multi-hot (sum-pooled) fp32 gather over each store's packed
    tiers equals its jnp twin bitwise: both add a row's slots in slot
    order, and a masked slot adds an exact zero. (The int8 forms differ
    from their twins in the last bits in interpret mode on CPU: XLA's CPU
    backend fuses the kernel's dequantizing multiply and the pooled add
    into one multiply-add, rounded once. Their tests above compare at
    rtol 1e-6; on a TPU they matched bitwise.)"""
    rng = np.random.default_rng(d + len(tier) + len(fmt))
    sizes, offsets, _, params, _, _, _ = _tiered_case(rng, d, fmt)
    h = 3
    ids = jnp.asarray(np.stack(
        [rng.integers(0, s, size=(40, h)) for s in sizes], axis=1),
        jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=ids.shape), jnp.float32)
    kind = {"dense": "dense", "two_level": "cached",
            "three_level": "host"}[tier]
    store = make_store(kind, sizes, d, fmt, capacity=96)
    np.testing.assert_array_equal(
        np.asarray(pooled_lookup(store, params[kind], ids, mask, offsets,
                                 "pallas")),
        np.asarray(pooled_lookup(store, params[kind], ids, mask, offsets,
                                 "jnp")))
