"""Requests whose scores resolved inside the window, over the window's
length, by the harness's clock."""

UNIT = "req/s"
LAYER = None
MOVES = None
SOURCE = "host_clock"


def read(ctx):
    return ctx.scored(ctx.t0, ctx.t1) / ctx.seconds
