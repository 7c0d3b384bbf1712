"""The metrics that read the engine's stage counters: their arithmetic on
hand-made snapshots, and nothing read from a program that lacks the
counters."""

import types

import pytest

from chipbench import registry

STAGE_METRICS = ("queue_wait_ms", "batch_wall_ms", "drain_host_us")


def _snap(n_requests, n_batches, **counters):
    return types.SimpleNamespace(n_requests=n_requests, n_batches=n_batches,
                                 **counters)


def _ctx(s0, s1):
    return types.SimpleNamespace(stats0=s0, stats1=s1)


def test_stage_metrics_arithmetic():
    bench = registry.Benchmark()
    s0 = _snap(1_000, 10, queue_wait_ms_total=2_000.0, batch_ms_total=30.0,
               device_wait_ms_total=5.0)
    s1 = _snap(1_512, 14, queue_wait_ms_total=3_792.0, batch_ms_total=42.0,
               device_wait_ms_total=9.0)
    ctx = _ctx(s0, s1)
    # 1,792 ms of queueing over 512 requests
    assert bench.metric("queue_wait_ms").read(ctx) == pytest.approx(3.5)
    # 12 ms over 4 batches
    assert bench.metric("batch_wall_ms").read(ctx) == pytest.approx(3.0)
    # (12 - 4) ms of host time over 512 requests, in microseconds
    assert bench.metric("drain_host_us").read(ctx) == pytest.approx(
        8_000 / 512)


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_stage_metric_reads_nothing_without_its_counters(name):
    """A program from before the counters (only the older ones in its
    snapshots) reads nothing, and neither does an empty window."""
    metric = registry.Benchmark().metric(name)
    old = _ctx(_snap(10, 1), _snap(20, 2))
    assert metric.read(old) is None
    idle = _snap(10, 1, queue_wait_ms_total=1.0, batch_ms_total=1.0,
                 device_wait_ms_total=1.0)
    assert metric.read(_ctx(idle, idle)) is None
