"""The embedding gathers' share of their roofline: the bytes the step's
lookups need (rows read and written once, ids read, from shapes) for the
batches served in the traced window, over HBM bandwidth, divided by the
summed device time of the gather kernel's events. A gather does no
arithmetic to speak of, so bandwidth bounds it.

Batches per bucket come from ``EngineStats.batches_per_bucket`` taken at
the traced window's ends. The kernel is ``mtl_gather_tiered``'s Pallas
call. The trace names the op after the Python function that makes the
Pallas call: ``mtl_gather_tiered.<n> custom-call`` on a TPU v5e.
"""

UNIT = "%"
LAYER = "gather kernel (kernels/multi_table_lookup.py)"
MOVES = "scored_per_s"
SOURCE = "device_trace"
EVENTS = (r"mtl_gather_tiered(\.\d+)? custom-call",)


def read(ctx):
    t = ctx.trace
    if t is None or ctx.stats_ta is None:
        return None
    kernel_s = t.kernel_s(EVENTS)
    if not kernel_s:
        return None
    a, b = ctx.stats_ta.batches_per_bucket, ctx.stats_tb.batches_per_bucket
    need = sum(ctx.ref_model.gather_bytes(ctx.cfg, bucket)
               * (n - a.get(bucket, 0)) for bucket, n in b.items())
    if need <= 0:
        return None
    return 100.0 * need / ctx.chip.hbm_bw / kernel_s
