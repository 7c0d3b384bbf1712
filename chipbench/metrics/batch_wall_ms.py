"""Mean host wall time of one served batch, from the pop that took its
requests to its last future resolved: ``EngineStats.batch_ms_total``
over ``n_batches``, both taken as differences over the window. A
program without the counter reads nothing."""

UNIT = "ms"
LAYER = "engine drain (serving/engine.py)"
MOVES = "p50_ms"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    b0 = getattr(s0, "batch_ms_total", None)
    b1 = getattr(s1, "batch_ms_total", None)
    n = s1.n_batches - s0.n_batches
    if b0 is None or b1 is None or n <= 0:
        return None
    return (b1 - b0) / n
