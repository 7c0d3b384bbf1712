"""Seconds the engine spent compiling (or loading from the compile
cache) its plans, summed over buckets: ``EngineStats.
compile_ms_per_bucket``. Part of ``setup_s``."""

UNIT = "s"
LAYER = "plan compile (core/plan.py)"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    ms = ctx.stats1.compile_ms_per_bucket
    return sum(ms.values()) / 1e3 if ms else None
