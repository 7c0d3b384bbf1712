"""``pool_wake_share`` in the cells that report ``p50_ms``: the share of
the window's requests whose submit woke the device scheduler's pool. A
program without the counter reads nothing."""

from chipbench.metrics.pool_wake_share import read  # noqa: F401

UNIT = "%"
LAYER = "engine batching (serving/engine.py, batching.py)"
MOVES = "p50_ms"
SOURCE = "program_counter"
