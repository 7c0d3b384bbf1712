"""The reduction from a profiler trace to busy time, idle gaps, kernel
time and the metrics that read them, on a synthesized trace."""

import types

import pytest

from chipbench import peaks, trace
from chipbench.trace import Event

DEV = "/device:TPU:0"
GATHER = ("%mtl_gather_tiered.1 = f32[19968,32]{1,0:T(8,128)S(1)} "
          "custom-call(s32[156,1,128]{2,1,0:T(1,128)S(1)} %a)")
FUSION = ("%fusion.3 = (s32[19968,1]{1,0:T(8,128)S(1)}, s32[19968]{0}) "
          "fusion(s32[19968,1]{1,0:T(8,128)S(1)} %reshape.55)")


def synthetic_trace():
    """A 10 ms window: device ops at 1-3 ms (gather) overlapping 2-4 ms
    (fusion), and 6-7 ms (gather); host spans label the gaps."""
    ms = 1e6
    return [
        Event(trace.HOST_PLANE, "python3", trace.WINDOW, 0, 10 * ms),
        Event(trace.HOST_PLANE, "python3", "gen.wait", 0, 5.5 * ms),
        Event(trace.HOST_PLANE, "python3", "gen.send", 5.5 * ms, 4.5 * ms),
        Event(trace.HOST_PLANE, "python3", "np.asarray(jax.Array)",
              7.5 * ms, 2 * ms),
        Event(DEV, "XLA Ops", GATHER, 1 * ms, 2 * ms),
        Event(DEV, "XLA Ops", FUSION, 2 * ms, 2 * ms),
        Event(DEV, "XLA Ops", GATHER, 6 * ms, 1 * ms),
        # outside the window: clipped away
        Event(DEV, "XLA Ops", GATHER, 11 * ms, 1 * ms),
        # another line of the device plane: not an op
        Event(DEV, "XLA Modules", "jit_whole(1)", 1 * ms, 6 * ms),
    ]


def test_union_merges_overlaps():
    assert trace.union_ns([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == \
        [(1, 4.5), (5, 6)]


def test_op_label_keeps_name_and_opcode():
    assert trace.op_label(GATHER) == "mtl_gather_tiered.1 custom-call"
    assert trace.op_label(FUSION) == "fusion.3 fusion"
    assert trace.op_label("jit_whole(1)") == "jit_whole(1)"


def test_summary_busy_kernel_and_gaps():
    s = trace.summarize(synthetic_trace())
    assert s.window_s == pytest.approx(0.010)
    # union of [1,4] and [6,7] ms
    assert s.busy_s == pytest.approx(0.004)
    assert s.n_devices == 1
    assert s.kernel_s([r"mtl_gather_tiered(\.\d+)? custom-call"]) == \
        pytest.approx(0.003)
    assert s.kernel_s(["no_such_op"]) is None
    # gaps: 4-6 (2 ms), 7-10 (3 ms), 0-1 (1 ms), longest first, labelled
    assert [round(g[0] * 1e3, 6) for g in s.gaps] == [3.0, 2.0, 1.0]
    assert s.gaps[0][1] == "gen.send; np.asarray(jax.Array)"
    assert s.gaps[1][1] == "gen.wait; no host op"
    b = s.breakdown()
    assert b["device_ops"][0] == ["mtl_gather_tiered.1 custom-call",
                                  pytest.approx(0.003)]
    assert len(b["idle_gaps"]) == 3


def test_summary_needs_one_window_marker():
    events = [e for e in synthetic_trace() if e.name != trace.WINDOW]
    with pytest.raises(RuntimeError):
        trace.summarize(events)


def _ctx(summary, **kw):
    from chipbench import registry
    bench = registry.Benchmark()
    cfg = bench.config("dcnv2-criteo-d32-h1024")
    base = dict(trace=summary, cfg=cfg, ref_model=bench.model("dcnv2"),
                chip=peaks.chip("TPU v5 lite"), trace_t=(0.0, 0.010),
                stats_ta=types.SimpleNamespace(batches_per_bucket={512: 10}),
                stats_tb=types.SimpleNamespace(batches_per_bucket={512: 12,
                                                                   128: 0}),
                scored=lambda lo, hi: 1024)
    base.update(kw)
    return types.SimpleNamespace(**base), bench


def test_trace_metrics_arithmetic():
    s = trace.summarize(synthetic_trace())
    ctx, bench = _ctx(s)
    assert bench.metric("device_idle_share").read(ctx) == pytest.approx(60.0)
    # two 512-row steps: 2 x 5,191,680 B at 819 GB/s over 3 ms of kernel
    want = 100 * 2 * 5_191_680 / 819e9 / 0.003
    assert bench.metric("gather_roofline").read(ctx) == pytest.approx(want)
    # 1024 requests x 16,099,776 FLOP over 10 ms at 197 TFLOP/s
    want = 100 * 1024 * 16_099_776 / (0.010 * 197e12)
    assert bench.metric("step_mfu").read(ctx) == pytest.approx(want)


def test_trace_metrics_read_nothing_without_trace():
    ctx, bench = _ctx(None)
    for name in ("device_idle_share", "gather_roofline", "step_mfu"):
        assert bench.metric(name).read(ctx) is None


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError):
        peaks.chip("TPU v9 imaginary")
