"""The metric that reads the drain's overlap counter: its arithmetic on
hand-made snapshots, and nothing read from a program that lacks the
counter."""

import types

import pytest

from chipbench import registry


def _ctx(s0, s1):
    return types.SimpleNamespace(stats0=s0, stats1=s1)


def _snap(n_batches, **counters):
    return types.SimpleNamespace(n_batches=n_batches, **counters)


@pytest.mark.parametrize("overlapped,share", [(40, 100.0), (30, 75.0),
                                              (0, 0.0)])
def test_overlap_share_reads_two_snapshots(overlapped, share):
    metric = registry.Benchmark().metric("overlap_share")
    # the window's 40 batches, ``overlapped`` of them launched while an
    # earlier one was held
    ctx = _ctx(_snap(10, n_overlapped_batches=9),
               _snap(50, n_overlapped_batches=9 + overlapped))
    assert metric.read(ctx) == pytest.approx(share)


def test_overlap_share_reads_nothing_without_its_counter():
    """A program from before the counter reads nothing, and neither does
    a window that served no batch."""
    metric = registry.Benchmark().metric("overlap_share")
    assert metric.read(_ctx(_snap(10), _snap(50))) is None
    idle = _snap(10, n_overlapped_batches=9)
    assert metric.read(_ctx(idle, idle)) is None
