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
        multi_hot:   max ids per field (1 = one-hot fields).
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

    def __post_init__(self):
        if self.row_dtype not in (None, "int8"):
            raise ValueError(f"row_dtype must be None or 'int8', "
                             f"got {self.row_dtype!r}")

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
