"""Main-path Pallas kernels compile for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks what the kernels compute; it
cannot see what Mosaic refuses on the chip — a block not aligned to the
(8, 128) tiling, a DMA slice the layout cannot express, more VMEM than a
kernel may use. These tests compile every main-path kernel with the TPU
compiler for a v5e that is described, not attached, at the paper's
serving widths: b=1024 requests, k=39 fields, d ∈ {16, 32}, over the full
synthetic Criteo rows, a 65,536-row cache and an 8,192-row staging buffer.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every xdist
worker imports this module.
"""

import math
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.data.synthetic import CRITEO
from repro.kernels.multi_table_lookup import (
    LANES, mtl_gather, mtl_gather_multihot, mtl_gather_three_level,
    mtl_gather_three_level_q8, mtl_gather_two_level,
    mtl_gather_two_level_q8, rows_per_line)
from repro.kernels.dense_matmul import dmm_q8
from repro.kernels.fused_cross import fused_cross_v1, fused_cross_v2
from repro.kernels.fused_fm import fused_fm_second_order

B, K, HOT = 1024, 39, 3
ROWS = sum(CRITEO.field_sizes) + 1
CACHE_ROWS, STAGING_ROWS = 65_536, 8_192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _packed(rows: int, d: int, fmt: str):
    """Shape of a packed ``rows``-row table (whole lines)."""
    dtype = np.int8 if fmt == "int8" else np.float32
    rpl = rows_per_line(d, dtype)
    word = jnp.int32 if fmt == "int8" else jnp.float32
    return (math.ceil(rows / rpl), LANES), word


GATHERS = [(tier, fmt, hot)
           for tier in ("dense", "two_level", "three_level")
           for fmt in ("fp32", "int8")
           for hot in (1, HOT)
           if not (tier == "dense" and fmt == "int8" and hot > 1)]


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("tier,fmt,hot", GATHERS)
def test_gather_compiles_for_v5e(one_chip, tier, fmt, hot, d):
    """dense = DenseStore (int8: the raw-row gather refreshes use);
    two_level = CachedStore; three_level = HostBackedStore."""
    r = B * K * hot
    ids = ((r,), jnp.int32)
    if tier == "dense":
        dtype = np.int8 if fmt == "int8" else np.float32
        table = _packed(ROWS, d, fmt)
        if hot == 1:
            fn = lambda rows, t: mtl_gather(rows, t, dim=d, dtype=dtype)
        else:
            fn = lambda rows, t: mtl_gather_multihot(rows, t, hot=hot,
                                                         dim=d)
        text = _compile_text(fn, one_chip, ids, table)
    else:
        cache = _packed(CACHE_ROWS, d, fmt)
        other_rows = ROWS if tier == "two_level" else STAGING_ROWS
        other = _packed(other_rows, d, fmt)
        if fmt == "fp32":
            kern = (mtl_gather_two_level if tier == "two_level"
                    else mtl_gather_three_level)
            fn = lambda a, b, c, t: kern(a, b, c, t, dim=d, hot=hot)
            text = _compile_text(fn, one_chip, ids, ids, cache, other)
        else:
            kern = (mtl_gather_two_level_q8 if tier == "two_level"
                    else mtl_gather_three_level_q8)
            fn = lambda a, b, c, cs, t, ts: kern(a, b, c, cs, t, ts, dim=d,
                                                 hot=hot)
            text = _compile_text(
                fn, one_chip, ids, ids, cache, ((CACHE_ROWS, 1), jnp.float32),
                other, ((other_rows, 1), jnp.float32))
    assert "tpu_custom_call" in text


def test_tiered_gather_keeps_its_kernel_name(one_chip):
    """The chip's trace names an op after its HLO instruction, and the
    benchmark finds the gather by ``mtl_gather_tiered``: the Pallas call's
    ``name`` keeps that name inside the op graph's named scopes."""
    d = 32
    ids = ((B * K,), jnp.int32)

    def lookup(a, b, c, t):
        with jax.named_scope("emb_lookup"):
            return mtl_gather_two_level(a, b, c, t, dim=d, hot=1)
    text = _compile_text(lookup, one_chip, ids, ids,
                         _packed(CACHE_ROWS, d, "fp32"),
                         _packed(ROWS, d, "fp32"))
    assert re.search(r"%mtl_gather_tiered(\.\d+)? = \S+ custom-call\(",
                     text)


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("kernel", ["dmm_q8", "fused_cross_v1",
                                    "fused_cross_v2", "fused_fm_second_order"])
def test_dense_kernel_compiles_for_v5e(one_chip, kernel, d):
    width = K * d
    f32 = jnp.float32
    if kernel == "dmm_q8":
        text = _compile_text(
            lambda hq, hs, wq, ws, bias: dmm_q8(hq, hs, wq, ws, bias),
            one_chip, ((B, width), jnp.int8), ((B, 1), f32),
            ((width, 1024), jnp.int8), ((1, 1024), f32), ((1, 1024), f32))
    elif kernel == "fused_cross_v1":
        text = _compile_text(fused_cross_v1, one_chip, ((B, width), f32),
                             ((B, 1), f32), ((width,), f32),
                             ((B, width), f32))
    elif kernel == "fused_cross_v2":
        text = _compile_text(fused_cross_v2, one_chip, ((B, width), f32),
                             ((B, width), f32), ((B, width), f32))
    else:
        text = _compile_text(fused_fm_second_order, one_chip,
                             ((B, K, d), f32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [3456, 4992])
def test_cross_tail_compiles_past_one_block(one_chip, width):
    """DLRM-DCNv2's 3,456-wide cross (and 39 fields at d=128): a 512-row
    block of the whole width would pass the scoped VMEM limit, so the
    kernel's grid splits the width too."""
    f32 = jnp.float32
    text = _compile_text(fused_cross_v2, one_chip, ((512, width), f32),
                         ((512, width), f32), ((512, width), f32))
    assert re.search(r"%fused_cross_v2(\.\d+)? = \S+ custom-call\(", text)


def test_slot_gather_compiles_at_dlrm_width(one_chip):
    """A 512-request DLRM-DCNv2 batch: 214 id slots a request, one d=128
    row each, through the cached store's two tiers of a 25.5M-row table."""
    r, d, rows = 512 * 214, 128, 25_523_084
    fn = lambda a, b, c, t: mtl_gather_two_level(a, b, c, t, dim=d, hot=1)
    text = _compile_text(fn, one_chip, ((r,), jnp.int32), ((r,), jnp.int32),
                         _packed(CACHE_ROWS, d, "fp32"),
                         _packed(rows, d, "fp32"))
    assert "tpu_custom_call" in text
