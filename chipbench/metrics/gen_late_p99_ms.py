"""99th percentile of how late the load generator sent a request: its
send time minus its due time on the schedule, over every request due in
the window. A starved generator shows here, not as a fast server.
Open-loop cells only."""

from chipbench.loadgen import latency_percentile

UNIT = "ms"
LAYER = "load generator (chipbench/loadgen.py)"
MOVES = "p50_ms"
SOURCE = "host_clock"


def read(ctx):
    if not ctx.open_loop:
        return None
    late = ctx.lateness_ms()
    return latency_percentile(late, 99) if late.size else None
