"""Mean time a request waited in the engine's queue, from its submit to
the pop that took it into a batch: ``EngineStats.queue_wait_ms_total``
over ``n_requests``, both taken as differences over the window. A
program without the counter reads nothing."""

UNIT = "ms"
LAYER = "engine batching (serving/engine.py, batching.py)"
MOVES = "p50_ms"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    q0 = getattr(s0, "queue_wait_ms_total", None)
    q1 = getattr(s1, "queue_wait_ms_total", None)
    n = s1.n_requests - s0.n_requests
    if q0 is None or q1 is None or n <= 0:
        return None
    return (q1 - q0) / n
