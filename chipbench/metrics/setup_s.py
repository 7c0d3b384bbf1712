"""Set-up time: process start to window start. Imports and device start,
weights made on the device, the deployment built, its plans compiled or
read from the compile cache, the cache admitted and every bucket served
once. The program compiles its dense weights into each plan as
constants, so every new seed compiles its plans again (ROADMAP C1)."""

UNIT = "s"
LAYER = None
MOVES = None
SOURCE = "host_clock"


def read(ctx):
    return ctx.setup_s
