"""The metrics that read the pool-wake counter: their arithmetic on
hand-made snapshots, and nothing read from a program that lacks the
counter."""

import types

import pytest

from chipbench import registry

WAKE_METRICS = ("pool_wake_share", "pool_wake_share.steady")


def _ctx(s0, s1):
    return types.SimpleNamespace(stats0=s0, stats1=s1)


def _snap(n_requests, **counters):
    return types.SimpleNamespace(n_requests=n_requests, **counters)


@pytest.mark.parametrize("name", WAKE_METRICS)
def test_pool_wake_share_reads_two_snapshots(name):
    metric = registry.Benchmark().metric(name)
    # 5 wakes over the window's 2,000 requests
    ctx = _ctx(_snap(1_000, pool_wakes=40), _snap(3_000, pool_wakes=45))
    assert metric.read(ctx) == pytest.approx(0.25)


@pytest.mark.parametrize("name", WAKE_METRICS)
def test_pool_wake_share_reads_nothing_without_its_counter(name):
    """A program from before the counter reads nothing, and neither does
    an empty window."""
    metric = registry.Benchmark().metric(name)
    assert metric.read(_ctx(_snap(10), _snap(20))) is None
    idle = _snap(10, pool_wakes=3)
    assert metric.read(_ctx(idle, idle)) is None
