"""Per-chip hardware peaks used by the roofline model, keyed by the
``device_kind`` JAX reports.

A device missing from :data:`CHIPS` is an error, never a default: a
roofline against the wrong chip's peaks is a wrong number.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Chip", "CHIPS", "chip", "DTYPE_BYTES"]


@dataclasses.dataclass(frozen=True)
class Chip:
    peak_flops_bf16: float      # FLOP/s
    peak_ops_int8: float        # OP/s
    hbm_bw: float               # B/s
    hbm_bytes: int
    ici_bw_per_link: float      # B/s, one direction of one link
    source: str


CHIPS: dict[str, Chip] = {
    # TPU v5e reports itself as "TPU v5 lite"
    "TPU v5 lite": Chip(
        peak_flops_bf16=197e12,
        peak_ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 2**30,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bw_per_link=50e9,
        source="Google Cloud documentation, 'TPU v5e' (system "
               "architecture: peak compute, HBM capacity and bandwidth, "
               "interchip interconnect)"),
}


def chip(device_kind: str) -> Chip:
    """The peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIPS)}") from None


DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
