"""The whole step's share of the chip's bf16 peak: matrix-product FLOPs
per scored request (from shapes, padding rows not counted) times the
requests scored in the traced window, over the traced seconds times the
peak. The bf16 peak applies because the program's float32 products run
at default precision, one bf16 pass."""

UNIT = "%"
LAYER = "whole step (core/plan.py)"
MOVES = "scored_per_s"
SOURCE = "device_trace"


def read(ctx):
    t = ctx.trace
    if t is None or ctx.trace_t is None or t.window_s <= 0:
        return None
    lo, hi = ctx.trace_t
    n = ctx.scored(lo, hi)
    if n == 0:
        return None
    flops = ctx.ref_model.flops_per_request(ctx.cfg) * n
    return 100.0 * flops / (t.window_s * ctx.chip.peak_flops_bf16)
