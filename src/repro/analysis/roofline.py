"""Roofline assembly: three terms per (arch × shape × mesh) from the
compiled dry-run artifact + the analytic model.

  compute_s    = corrected per-device dot FLOPs / peak bf16 FLOP/s
  memory_s     = analytic per-device HBM bytes / HBM bandwidth
  collective_s = corrected per-device collective bytes / per-link ICI

with the chip's peaks from ``hw.CHIPS`` (v5e: 197 TF/s, 819 GB/s,
50 GB/s per link).

Corrected = loop-trip multiplied (repro.analysis.hlo_parse); raw
cost_analysis numbers are reported alongside for transparency. The
MODEL_FLOPS / corrected-FLOPs ratio surfaces remat & redundancy waste
(remat alone puts it near 3/4 for training: 6ND useful vs ~8ND executed).
"""

from __future__ import annotations

import dataclasses
import json

from . import hw
from .analytic import analytic_cost
from .hlo_parse import parse_hlo

__all__ = ["RooflineReport", "analyze_cell"]


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # three terms (seconds per step)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    # accounting
    hlo_dot_flops_per_device: float
    raw_cost_analysis_flops: float
    model_flops_global: float
    useful_ratio: float             # MODEL_FLOPS / corrected HLO flops
    collective_bytes_per_device: float
    collective_breakdown: dict
    hbm_bytes_per_device: float
    hbm_components: dict
    # memory feasibility (from memory_analysis)
    arg_bytes: int
    temp_bytes: int
    out_bytes: int
    fits_hbm: bool
    n_micro: int
    note: str = ""
    # int8 companion terms (quantized compute, ``compute_dtype="int8"``):
    # the MXU's int8 peak doubles bf16, and the weights-read HBM component
    # shrinks to ~1/4 (int8 payload + per-channel scales). Arithmetic
    # intensity (FLOP per HBM byte) for both dtypes locates each cell
    # against the machine balance point (PEAK / HBM_BW); defaulted so
    # pre-existing dry-run records still deserialize.
    compute_s_int8: float = 0.0
    memory_s_int8: float = 0.0
    arith_intensity: float = 0.0
    arith_intensity_int8: float = 0.0

    def step_time_bound_s(self) -> float:
        """Roofline lower bound on step time (no overlap assumption)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """compute_term / max-term: 1.0 = perfectly compute-bound."""
        t = self.step_time_bound_s()
        return self.compute_s / t if t > 0 else 0.0

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1, default=str)


#: device kind of the production pods the dry runs describe (v5e). The
#: dry runs compile on the host, so the executable cannot name its chip.
TARGET = "TPU v5 lite"


def analyze_cell(arch: str, shape: str, mesh_name: str, chips: int,
                 compiled, n_micro: int = 1) -> RooflineReport:
    """Roofline terms of one compiled cell against the peaks of
    :data:`TARGET` (``hw.CHIPS``)."""
    peaks = hw.chip(TARGET)
    text = compiled.as_text()
    stats = parse_hlo(text)
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    an = analytic_cost(arch, shape, chips, n_micro)

    compute_s = stats.dot_flops / peaks.peak_flops_bf16
    memory_s = an.hbm_bytes_per_device / peaks.hbm_bw
    collective_s = stats.total_collective_bytes / peaks.ici_bw_per_link

    # int8 twin: matmuls at the doubled MXU peak, weight reads at ~1/4 the
    # bytes (the only HBM component quantized compute shrinks — activations
    # and embedding gathers are unchanged by the matmul dtype)
    w_read = float(an.components.get("weights_read", 0.0))
    hbm_int8 = an.hbm_bytes_per_device - 0.75 * w_read
    compute_s_int8 = stats.dot_flops / peaks.peak_ops_int8
    memory_s_int8 = hbm_int8 / peaks.hbm_bw
    ai = (stats.dot_flops / an.hbm_bytes_per_device
          if an.hbm_bytes_per_device else 0.0)
    ai_int8 = stats.dot_flops / hbm_int8 if hbm_int8 else 0.0
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    per_dev_model = an.model_flops / chips
    useful = per_dev_model / stats.dot_flops if stats.dot_flops else 0.0

    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant,
        hlo_dot_flops_per_device=stats.dot_flops,
        raw_cost_analysis_flops=float(ca.get("flops", 0.0)),
        model_flops_global=an.model_flops,
        useful_ratio=useful,
        collective_bytes_per_device=stats.total_collective_bytes,
        collective_breakdown={k: v for k, v in
                              stats.collective_bytes.items()},
        hbm_bytes_per_device=an.hbm_bytes_per_device,
        hbm_components=an.components,
        arg_bytes=ma.argument_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        out_bytes=ma.output_size_in_bytes,
        fits_hbm=live <= peaks.hbm_bytes,
        n_micro=n_micro,
        compute_s_int8=compute_s_int8,
        memory_s_int8=memory_s_int8,
        arith_intensity=ai,
        arith_intensity_int8=ai_int8,
    )
