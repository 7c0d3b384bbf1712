"""Median request latency over every request due in the window: from the
request's due time on the open-loop schedule to its score resolving, by
the harness's clock; a failed or unanswered request counts as a miss,
longer than any limit. Open-loop cells only."""

from chipbench.loadgen import latency_percentile

UNIT = "ms"
LAYER = None
MOVES = None
SOURCE = "host_clock"


def read(ctx):
    if not ctx.open_loop:
        return None
    return latency_percentile(ctx.latency_ms(), 50)
