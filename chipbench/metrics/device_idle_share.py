"""Share of the traced window in which no operation ran on the device:
1 − (union of the device-op intervals) / (window), from the profiler's
trace of a few seconds in the middle of the window."""

UNIT = "%"
LAYER = "device (TPU v5e)"
MOVES = "scored_per_s"
SOURCE = "device_trace"


def read(ctx):
    t = ctx.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
