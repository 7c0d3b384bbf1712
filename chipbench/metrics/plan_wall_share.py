"""Share of the window's wall time the engine spent inside plan calls:
``EngineStats.compute_ms_total`` (host clock around each batch's
stage + dispatch + step + readback, measured by the engine) as a
difference over the window, over the window's length."""

UNIT = "%"
LAYER = "plan call (core/plan.py)"
MOVES = "scored_per_s"
SOURCE = "program_counter"


def read(ctx):
    ms = ctx.stats1.compute_ms_total - ctx.stats0.compute_ms_total
    if ms <= 0:
        return None
    return 100.0 * ms / (ctx.seconds * 1e3)
