"""Share of the device rows the engine ran that held a request:
``EngineStats.n_requests`` over the sum of bucket size times batches per
bucket, both taken as differences over the window."""

UNIT = "%"
LAYER = "engine batching (serving/engine.py, batching.py)"
MOVES = "p50_ms"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    rows = sum(b * (n - s0.batches_per_bucket.get(b, 0))
               for b, n in s1.batches_per_bucket.items())
    if rows <= 0:
        return None
    return 100.0 * (s1.n_requests - s0.n_requests) / rows
