"""FusedEmbeddingSpec — static description of a CTR embedding module.

Lives in the ``repro.embedding`` subsystem (it is the contract every
:class:`~repro.embedding.store.EmbeddingStore` is built against);
``repro.core`` re-exports it for convenience.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

__all__ = ["FusedEmbeddingSpec"]


@dataclasses.dataclass(frozen=True)
class FusedEmbeddingSpec:
    """Static description of a CTR embedding module.

    Attributes:
        field_sizes: number of features n_i per field (len = k).
        dim:         shared embedding dimension d.
        multi_hot:   max ids per field of the masked ``(b, k, h)`` lookup
                     (1 = one-hot fields).
        hotness:     fixed ids per field of a request row, ``(h_1, ..., h_k)``
                     (default: one each). A row then holds ``Σh`` id
                     slots, field ``i``'s ``h_i`` side by side, and a
                     lookup sums each field's slots (:attr:`slot_offsets`).
        dtype:       parameter dtype.
        pad_rows_to: pad the mega-table height to a multiple (sharding);
                     the height is also rounded up to whole packed lines
                     (:attr:`line_rows`).
        row_dtype:   *wire* dtype of stored rows — ``None`` (default) keeps
                     rows in ``dtype`` (bit-exact); ``"int8"`` stores rows
                     symmetrically quantized with one fp32 scale per row
                     (``repro.quant``), dequantized inside the gather.
                     A store-side memory-system choice: two specs differing
                     only in ``row_dtype`` describe the same model.
    """
    field_sizes: tuple[int, ...]
    dim: int
    multi_hot: int = 1
    dtype: str = "float32"
    pad_rows_to: int = 1
    row_dtype: str | None = None
    hotness: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.row_dtype not in (None, "int8"):
            raise ValueError(f"row_dtype must be None or 'int8', "
                             f"got {self.row_dtype!r}")
        if self.hotness is not None and (
                len(self.hotness) != self.k or min(self.hotness) < 1):
            raise ValueError(f"hotness {self.hotness} does not give each of "
                             f"the {self.k} fields one id or more")

    @property
    def k(self) -> int:
        return len(self.field_sizes)

    @property
    def line_rows(self) -> int:
        """Rows per 128-lane line of the packed table layout
        (``kernels.multi_table_lookup.pack_rows``), common to the fp32 and
        (where ``dim`` allows it) int8 formats, so every store's table
        packs into whole lines without slicing."""
        n = 1
        for nbytes in (4 * self.dim, self.dim):     # fp32 rows, int8 rows
            if nbytes % 4 == 0 and 512 % nbytes == 0:
                n = math.lcm(n, 512 // nbytes)
        return n

    @property
    def rows(self) -> int:
        """Mega-table height: all fields + 1 zero row (multi-hot masking),
        padded up for even sharding and to whole packed lines."""
        n = int(sum(self.field_sizes)) + 1
        pad = math.lcm(self.pad_rows_to, self.line_rows)
        return ((n + pad - 1) // pad) * pad

    @property
    def offsets(self) -> np.ndarray:
        return np.concatenate(
            [[0], np.cumsum(self.field_sizes)[:-1]]).astype(np.int32)

    @property
    def slots(self) -> int:
        """Id slots of a request row: ``Σ hotness``, or ``k``."""
        return self.k if self.hotness is None else int(sum(self.hotness))

    @property
    def slot_offsets(self) -> np.ndarray:
        """First table row of each id slot's field: :attr:`offsets`, each
        repeated ``h_i`` times."""
        if self.hotness is None:
            return self.offsets
        return np.repeat(self.offsets, self.hotness)

    @property
    def zero_row(self) -> int:
        return int(sum(self.field_sizes))

    @property
    def quantized(self) -> bool:
        """True when stored rows travel as int8 + per-row fp32 scale."""
        return self.row_dtype == "int8"

    @property
    def wire_row_bytes(self) -> int:
        """Bytes one row costs on the wire (gather / host→device staging):
        ``4·d`` for fp32 rows, ``d + 4`` for int8 rows (payload + scale)."""
        if self.quantized:
            return self.dim + 4
        return self.dim * np.dtype(self.dtype).itemsize

    @property
    def n_params(self) -> int:
        return self.rows * self.dim
