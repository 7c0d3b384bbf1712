"""Share of the window's requests whose submit woke the device
scheduler's pool, in the cells that report ``scored_per_s``:
``EngineStats.pool_wakes`` over ``n_requests``, both taken as
differences over the window. A submit wakes the pool only on a readiness
edge (a first request into an empty queue, or a full bucket) of an
engine no pool thread holds. A program without the counter reads
nothing."""

UNIT = "%"
LAYER = "engine batching (serving/engine.py, batching.py)"
MOVES = "scored_per_s"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    w0, w1 = getattr(s0, "pool_wakes", None), getattr(s1, "pool_wakes", None)
    n = s1.n_requests - s0.n_requests
    if w0 is None or w1 is None or n <= 0:
        return None
    return 100.0 * (w1 - w0) / n
