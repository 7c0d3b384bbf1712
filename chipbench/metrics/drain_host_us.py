"""Host time the engine's drain spends on each request outside the wait
for the device: ``EngineStats.batch_ms_total`` less
``device_wait_ms_total``, over ``n_requests``, all taken as differences
over the window. It covers stacking, observing, staging, dispatch,
readback and resolving the futures; intake and the load generator's own
work are outside it. A program without the counters reads nothing."""

UNIT = "us"
LAYER = "engine drain (serving/engine.py)"
MOVES = "scored_per_s"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    got = [getattr(s, k, None) for s in (s0, s1)
           for k in ("batch_ms_total", "device_wait_ms_total")]
    n = s1.n_requests - s0.n_requests
    if None in got or n <= 0:
        return None
    b0, w0, b1, w1 = got
    return ((b1 - b0) - (w1 - w0)) * 1e3 / n
