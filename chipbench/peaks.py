"""Published peaks of each chip, keyed by the ``device_kind`` JAX
reports. A device missing here is an error, never a default: a share of
the wrong chip's peak is a wrong number."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    peak_flops_bf16: float      # FLOP/s
    peak_ops_int8: float        # OP/s
    hbm_bw: float               # B/s
    hbm_bytes: int
    source: str


CHIPS: dict[str, Chip] = {
    # TPU v5e reports itself as "TPU v5 lite"
    "TPU v5 lite": Chip(
        peak_flops_bf16=197e12,
        peak_ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 2**30,
        source="Google Cloud documentation, 'TPU v5e' (system "
               "architecture: peak compute, HBM capacity and bandwidth)"),
}


def chip(device_kind: str) -> Chip:
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(CHIPS)}") from None
