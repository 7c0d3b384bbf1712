"""Share of the window's embedding-row lookups that the store's hot cache
served: ``EngineStats.emb_cache_hits`` over hits plus
``emb_cache_misses``, both taken as differences over the window (the
store counts each looked-up row against its index map as it observes a
batch). Zipf ids keep a hot set in the cache; uniform ids leave it to
the small fields alone. A program or store without the counters, or a
window without lookups, reads nothing."""

UNIT = "%"
LAYER = "embedding store (embedding/cached.py)"
MOVES = "scored_per_s"
SOURCE = "program_counter"


def read(ctx):
    got = [getattr(s, k, None) for s in (ctx.stats0, ctx.stats1)
           for k in ("emb_cache_hits", "emb_cache_misses")]
    if None in got:
        return None
    h0, m0, h1, m1 = got
    n = (h1 - h0) + (m1 - m0)
    if n <= 0:
        return None
    return 100.0 * (h1 - h0) / n
