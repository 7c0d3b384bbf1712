"""Share of the window's batches that the engine launched while it held
an earlier batch not yet read back, so the device ran that one while the
host formed this one: ``EngineStats.n_overlapped_batches`` over
``n_batches``, both taken as differences over the window. A program
without the counter reads nothing."""

UNIT = "%"
LAYER = "engine drain (serving/engine.py)"
MOVES = "scored_per_s"
SOURCE = "program_counter"


def read(ctx):
    s0, s1 = ctx.stats0, ctx.stats1
    o0 = getattr(s0, "n_overlapped_batches", None)
    o1 = getattr(s1, "n_overlapped_batches", None)
    n = s1.n_batches - s0.n_batches
    if o0 is None or o1 is None or n <= 0:
        return None
    return 100.0 * (o1 - o0) / n
