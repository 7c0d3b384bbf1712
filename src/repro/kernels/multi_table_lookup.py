"""Pallas TPU kernels for DPIFrame's multi-table embedding lookup (Alg. 1).

TPU adaptation of the paper's GPU design (DESIGN.md §2):

* GPU: one CUDA *thread* per output element, output-first allocation so a
  warp's 32 threads write coalesced addresses.
* TPU: one Pallas *program* per block of output rows. The k per-field
  tables are concatenated into a single HBM-resident mega-table; each
  program reads its rows' global ids (the TPU analogue of the in-thread
  ``emb_row`` computation in Alg. 1 lines 6–8) from SMEM, DMAs the rows
  into VMEM and writes one dense output block — output blocks map 1:1 to
  grid steps, so writes are perfectly sequential (output-first, C3).

One production kernel, :func:`mtl_gather_tiered` (body
:func:`_gather_kernel`): each slot reads one row from whichever of 1–2
packed table operands its tier names (dense; cache + backing; cache +
staging), or zero for tier -1; int8 rows dequantize in-kernel by a
per-slot fp32 scale; ``hot > 1`` sum-pools that many slots per output
row. The stores decide each slot's tier, row and scale
(``EmbeddingStore._route``) and reach the kernel through
``ops.gather_tiers``.

Strawmen kept for the benchmarks only:

  ``mtl_onehot``       TPU-only alternative with *no GPU analogue*: small
                       fields are batched into a dense ``one_hot(ids) @ table``
                       executed on the MXU.
  ``mtl_input_first``  the paper's Fig.-11 strawman: grid ordered by *input*
                       (field-major output layout) so consecutive programs
                       write strided addresses; needs a final transpose pass.

Table layout (why the gathers take *packed* tables): a TPU vector register
is 8 sublanes × 128 lanes of 32-bit words, and Mosaic only moves HBM data
in whole lanes. A ``(1, d)`` row block with ``d < 128`` is refused, and an
``(N, d)`` table in HBM is laid out column-major by XLA anyway. So every
table the gathers read is stored **lane-dense**: :func:`pack_rows` turns
``(N, d)`` rows into ``(N·d / (128·word), 128)`` lines of 32-bit words
(fp32 rows as-is, int8/bf16 rows viewed as 32-bit words), ``128 / words``
rows per line. A program DMAs each of its rows' whole line from the
``ANY``-space table into VMEM, then a barrel shift (log2 static lane
rolls, selected per sublane) moves each row's words to lanes ``[0, w)``;
int8 words are unpacked to fp32 with one small 0/1 MXU matmul per byte
plane, which is exact (int8 values are exact in bf16). Stores keep their
tables packed, so no call pays a relayout.

All kernels are validated in ``interpret=True`` mode against
``repro.kernels.ref`` oracles (tests/test_kernels.py) and compiled for a
described v5e at the paper's widths (tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes of one TPU vector register (and of one packed table line)
LANES = 128
#: output rows one program gathers (rounded down to the batch for tiny R)
BLOCK_ROWS = 128


# ---------------------------------------------------------------------------
# Lane-dense table layout
# ---------------------------------------------------------------------------

def words_per_row(dim: int, dtype) -> int:
    """32-bit words one ``dim``-wide row of ``dtype`` occupies in a packed
    line; rows must tile a 128-lane line exactly."""
    nbytes = dim * np.dtype(dtype).itemsize
    words = nbytes // 4
    if nbytes % 4 or LANES % words:
        raise ValueError(
            f"a {dim}-wide {np.dtype(dtype).name} row is {nbytes} bytes; the "
            "packed layout needs a whole number of 32-bit words that divides "
            f"{LANES} lanes")
    return words


def rows_per_line(dim: int, dtype) -> int:
    """Rows held by one packed 128-lane line."""
    return LANES // words_per_row(dim, dtype)


def _word_dtype(dtype):
    dtype = np.dtype(dtype)
    return dtype if dtype.itemsize == 4 else np.dtype(np.int32)


def _as_words(rows):
    """``(n, d)`` rows -> ``(n, words)`` 32-bit words (a view for fp32)."""
    n, d = rows.shape
    dtype = np.dtype(rows.dtype)
    words = words_per_row(d, dtype)
    if dtype.itemsize == 4:
        return rows
    if isinstance(rows, np.ndarray):
        return np.ascontiguousarray(rows).view(np.int32)
    return jax.lax.bitcast_convert_type(
        rows.reshape(n, words, 4 // dtype.itemsize), jnp.int32)


def pack_rows(table):
    """``(N, d)`` rows -> ``(lines, 128)`` lane-dense 32-bit words.

    fp32 rows keep their dtype; narrower rows (int8, bf16) are viewed as
    int32 words. ``N`` is zero-padded up to whole lines. Works on numpy
    arrays (host staging buffers) and jax arrays alike; on a row-major host
    array it is a view plus the padding.
    """
    xp = np if isinstance(table, np.ndarray) else jnp
    table = _as_words(table)
    n, words = table.shape
    rpl = LANES // words
    pad = -n % rpl
    if pad:
        table = xp.concatenate(
            [table, xp.zeros((pad, words), dtype=table.dtype)], axis=0)
    return table.reshape((n + pad) // rpl, LANES)


def set_rows(lines: jax.Array, row_ids, rows) -> jax.Array:
    """Functional update of a packed table: logical rows ``row_ids``
    (unique, host ints) replaced by ``rows`` ``(n, d)``. Only the touched
    lines are read and rewritten — no relayout of the table."""
    row_ids = np.asarray(row_ids, dtype=np.int64).reshape(-1)
    words = _as_words(jnp.asarray(rows))
    w = words.shape[1]
    rpl = LANES // w
    uniq, inv = np.unique(row_ids // rpl, return_inverse=True)
    cur = jnp.take(lines, jnp.asarray(uniq), axis=0).reshape(-1, rpl, w)
    cur = cur.at[jnp.asarray(inv), jnp.asarray(row_ids % rpl)].set(words)
    return lines.at[jnp.asarray(uniq)].set(cur.reshape(-1, LANES))


def unpack_rows(lines, dim: int, dtype):
    """Inverse of :func:`pack_rows`: ``(lines, 128)`` -> ``(lines·rpl, dim)``
    rows of ``dtype`` (padding rows included)."""
    dtype = np.dtype(dtype)
    if dtype.itemsize == 4:
        if np.dtype(lines.dtype) != dtype:
            raise ValueError(f"packed {lines.dtype} lines hold "
                             f"{lines.dtype} rows, not {dtype}")
        return lines.reshape(-1, dim)
    if isinstance(lines, np.ndarray):
        return lines.view(dtype).reshape(-1, dim)
    return jax.lax.bitcast_convert_type(lines, dtype).reshape(-1, dim)


# ---------------------------------------------------------------------------
# The gather kernel (Alg. 1, C2 + C3) — every tier and format
# ---------------------------------------------------------------------------

def _gather_kernel(tier_ref, line_ref, sub_ref, *refs, n_tiers: int,
                   hot: int, block: int, words: int, dim: int, q8: bool):
    """One program = ``block`` output rows × ``hot`` pooled slots.

    ``tier_ref``/``line_ref`` (SMEM): per slot, which table operand holds
    the row (-1 = none: the slot contributes zero) and its packed line.
    ``sub_ref`` (VMEM, ``(block, hot)``): the row's position inside its
    line, -1 for a zero slot. For q8, ``scale_ref`` (VMEM) carries each
    slot's tier-selected fp32 scale.
    """
    if q8:
        scale_ref, refs = refs[0], refs[1:]
    tables = refs[:n_tiers]
    out_ref, buf, sem = refs[n_tiers:]

    def for_each_slot(action):
        # one DMA per slot, from whichever tier the slot map chose — the
        # other tiers are never touched (HugeCTR's address indirection)
        def body(g, carry):
            for j in range(hot):
                s = g * hot + j
                tier = tier_ref[0, s]
                for t in range(n_tiers):
                    @pl.when(tier == t)
                    def _():
                        action(pltpu.make_async_copy(
                            tables[t].at[pl.ds(line_ref[0, s], 1)],
                            buf.at[j, pl.ds(g, 1)], sem))
            return carry
        jax.lax.fori_loop(0, block, body, 0)

    for_each_slot(lambda copy: copy.start())
    for_each_slot(lambda copy: copy.wait())

    if q8:
        # byte plane p of word i is element 4i+p: spread each plane to its
        # lanes with a 0/1 matmul (exact — int8 values are exact in bf16)
        src = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        dst = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        spread = [((dst == 4 * src + p) & (src < words)).astype(jnp.float32)
                  for p in range(4)]
    acc = None
    for j in range(hot):
        x = buf[j]                                     # (block, 128) words
        sub = sub_ref[:, j:j + 1]                      # (block, 1)
        # barrel shift: move each row's words from lane sub*words to 0
        step, bit = words, 0
        while step < LANES:
            x = jnp.where(((sub >> bit) & 1) == 1,
                          pltpu.roll(x, LANES - step, 1), x)
            step, bit = step * 2, bit + 1
        if q8:
            q = None
            for p in range(4):
                plane = ((x << (24 - 8 * p)) >> 24).astype(jnp.float32)
                part = jnp.dot(plane, spread[p],
                               preferred_element_type=jnp.float32)
                q = part if q is None else q + part
            val = q[:, :dim] * scale_ref[:, j:j + 1]
        else:
            val = x[:, :words]
        val = jnp.where(sub >= 0, val, jnp.zeros_like(val))
        acc = val if acc is None else acc + val
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("hot", "dim", "dtype",
                                             "interpret"))
def mtl_gather_tiered(tier: jax.Array, row: jax.Array, tables: tuple,
                      scale=None, *, hot: int, dim: int, dtype,
                      interpret: bool) -> jax.Array:
    """Shared implementation of every production gather (and, called
    directly, the per-shard gather of the vocab-parallel mesh lookup).

    Args:
        tier:   (R*hot,) int32 table operand per slot, -1 = zero slot.
        row:    (R*hot,) int32 row within that table (ignored for -1).
        tables: packed tables (:func:`pack_rows`), one per tier.
        scale:  (R*hot,) fp32 tier-selected scales — int8 tables only.
        dtype:  row dtype of ``tables`` (int8 when ``scale`` is given).

    Returns:
        (R, dim) rows of ``dtype`` (fp32 when dequantizing), slot-pooled.
    """
    q8 = scale is not None
    words = words_per_row(dim, dtype)
    word_dtype = _word_dtype(dtype)
    if hot > 1 and not q8 and word_dtype != np.dtype(np.float32):
        raise ValueError(f"pooling needs fp32 rows, got {np.dtype(dtype)}")
    rpl = LANES // words
    r = tier.shape[0] // hot
    block = min(BLOCK_ROWS, -(-r // 8) * 8)
    rp = -(-r // block) * block
    pad = (rp - r) * hot
    tier = jnp.pad(tier.astype(jnp.int32), (0, pad), constant_values=-1)
    row = jnp.pad(row.astype(jnp.int32), (0, pad))
    # clamp like jnp.take: a DMA past the end of a table faults the chip
    last = jnp.asarray([t.shape[0] - 1 for t in tables], jnp.int32)
    line = jnp.clip(row >> (rpl.bit_length() - 1), 0,
                    last[jnp.maximum(tier, 0)])
    sub = jnp.where(tier >= 0, row & (rpl - 1), -1).reshape(rp, hot)

    smem = pl.BlockSpec((None, 1, block * hot), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec((block, hot), lambda i: (i, 0))
    in_specs = [smem, smem, vmem]
    args = [tier.reshape(rp // block, 1, block * hot),
            line.reshape(rp // block, 1, block * hot), sub]
    if q8:
        in_specs.append(vmem)
        args.append(jnp.pad(scale, (0, pad)).reshape(rp, hot))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(tables)
    out_w = dim if q8 else words
    out_dtype = jnp.float32 if q8 else word_dtype
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n_tiers=len(tables), hot=hot,
                          block=block, words=words, dim=dim, q8=q8),
        grid=(rp // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block, out_w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, out_w), out_dtype),
        scratch_shapes=[pltpu.VMEM((hot, block, LANES), word_dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="mtl_gather_tiered",
    )(*args, *tables)[:r]
    if q8 or np.dtype(dtype).itemsize == 4:
        return out
    return jax.lax.bitcast_convert_type(out, dtype).reshape(r, dim)


# ---------------------------------------------------------------------------
# One-hot MXU variant (TPU-only; no GPU analogue)
# ---------------------------------------------------------------------------

def _onehot_kernel(ids_ref, table_ref, out_ref):
    # ids_ref:   (bm, 1) int32 local ids for this (batch-tile, field)
    # table_ref: (1, n_pad, d) this field's (padded) table
    # out_ref:   (bm, 1, d)
    n_pad = table_ref.shape[1]
    ids = ids_ref[...]                                        # (bm, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], n_pad), 1)
    onehot = (iota == ids).astype(table_ref.dtype)            # (bm, n_pad)
    # MXU matmul: (bm, n_pad) @ (n_pad, d)
    out = jnp.dot(onehot, table_ref[0], preferred_element_type=jnp.float32)
    out_ref[...] = out[:, None, :].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def mtl_onehot(ids: jax.Array, stacked_tables: jax.Array, *,
               block_b: int = 128, interpret: bool = False) -> jax.Array:
    """Dense one-hot matmul lookup for small fields.

    Args:
        ids:            (b, k) int32 local ids (each < n_pad).
        stacked_tables: (k, n_pad, d) small tables padded to a common height.

    Returns:
        (b, k, d) embedding output.
    """
    b, k = ids.shape
    _, n_pad, d = stacked_tables.shape
    bm = min(block_b, b)
    grid = (pl.cdiv(b, bm), k)
    return pl.pallas_call(
        _onehot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, f: (i, f)),
            pl.BlockSpec((1, n_pad, d), lambda i, f: (f, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1, d), lambda i, f: (i, f, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k, d), stacked_tables.dtype),
        interpret=interpret,
        name="mtl_onehot",
    )(ids, stacked_tables)


# ---------------------------------------------------------------------------
# Input-first strawman (paper Fig. 11 ablation)
# ---------------------------------------------------------------------------

def _copy_row_3d_kernel(ids_ref, table_ref, out_ref):
    del ids_ref
    out_ref[...] = table_ref[...][None]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def mtl_input_first(flat_rows: jax.Array, mega_table: jax.Array, *,
                    k: int, interpret: bool = False) -> jax.Array:
    """Input-first allocation: programs ordered by input sample.

    Consecutive programs write to a *field-major* (k, b, d) output — a
    stride of b·d elements between successive writes (the TPU reflection of
    the GPU's uncoalesced-warp penalty) — and a final transpose pass
    restores (b, k*d). Exists only to reproduce the Fig.-11 comparison.
    """
    r = flat_rows.shape[0]
    b = r // k
    d = mega_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k),                       # input-sample-major traversal
        in_specs=[pl.BlockSpec((1, d), lambda s, f, ids: (ids[s * k + f], 0))],
        # field-major output: consecutive inner steps jump b rows apart
        out_specs=pl.BlockSpec((1, 1, d), lambda s, f, ids: (f, s, 0)),
    )
    out_fmajor = pl.pallas_call(
        _copy_row_3d_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, b, d), mega_table.dtype),
        interpret=interpret,
        name="mtl_input_first",
    )(flat_rows, mega_table)
    # the extra reorganization pass input-first designs pay for:
    return jnp.transpose(out_fmajor, (1, 0, 2)).reshape(b, k * d)
