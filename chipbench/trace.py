"""Reduction of a profiler trace to device busy time, per-op time and
labelled idle gaps.

A trace is read into flat :class:`Event` records (plane, line, name,
start, duration in ns; one clock for host and device planes). The traced
window is the span of the harness's own marker annotation, ``WINDOW``, on
the host. Device operations are the events of the ``OP_LINE`` line of
every TPU plane; busy time is the union of their intervals inside the
window, per device, averaged over the devices that ran anything.

The TPU profiler names each device op by its HLO text
(``%mtl_gather_tiered.1 = f32[19968,32]{...} custom-call(...)``);
:func:`op_label` keeps the instruction's name and opcode
(``mtl_gather_tiered.1 custom-call``). A Pallas call is named after the
Python function that makes it (``mtl_gather_tiered``, ``fused_cross_v2``,
``fused_fm_second_order``), since the program sets no ``name=`` of its
own. The metric files list the names they read.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

#: host annotation spanning the traced window (opened by the harness)
WINDOW = "chipbench.trace_window"
#: harness spans on the load generator's thread, used to label idle gaps
GEN_PREFIX = "gen."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: idle gaps kept (longest first) for the breakdown
N_GAPS = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> list[Event]:
    """Every event of the one ``.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in data.planes for ln in p.lines for e in ln.events]


_HLO = re.compile(r"^%?([\w.\-]+) = .*?\s([\w\-]+)\(")


def op_label(name: str) -> str:
    """``name opcode`` of a device op's HLO text; other names as they
    are."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def union_ns(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same points."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clip(lo: float, hi: float, w: tuple[float, float]):
    return max(lo, w[0]), min(hi, w[1])


@dataclasses.dataclass
class Summary:
    """What one traced window shows.

    ``window_s``: length of the window; ``busy_s``: union of device-op
    intervals inside it, averaged over devices that ran ops; ``op_s``:
    clipped device time per op (:func:`op_label`), summed over devices; ``gaps``:
    the ``N_GAPS`` longest idle intervals of the first device as
    ``(seconds, label)``, longest first; ``n_devices``: devices that ran ops.
    """
    window_s: float
    busy_s: float
    op_s: dict[str, float]
    gaps: list[tuple[float, str]]
    n_devices: int

    def kernel_s(self, names) -> float | None:
        """Summed device time of the ops named in ``names`` (exact names
        or ``re`` patterns matched in full); None when none ran."""
        pats = [re.compile(n) for n in names]
        hit = [s for op, s in self.op_s.items()
               if any(p.fullmatch(op) for p in pats)]
        return sum(hit) if hit else None

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[label, s] for s, label in self.gaps[:n]]}


def summarize(events: list[Event]) -> Summary:
    marks = [e for e in events if e.name == WINDOW and e.plane == HOST_PLANE]
    if len(marks) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found "
                           f"{len(marks)}")
    w = (marks[0].start_ns, marks[0].end_ns)
    by_dev: dict[str, list[tuple[float, float]]] = {}
    op_s: dict[str, float] = {}
    for e in events:
        if e.line != OP_LINE or not DEVICE_PLANE.match(e.plane):
            continue
        lo, hi = _clip(e.start_ns, e.end_ns, w)
        if hi <= lo:
            continue
        by_dev.setdefault(e.plane, []).append((lo, hi))
        op = op_label(e.name)
        op_s[op] = op_s.get(op, 0.0) + (hi - lo) * 1e-9
    busy = {p: sum(hi - lo for lo, hi in union_ns(iv))
            for p, iv in by_dev.items()}
    gaps: list[tuple[float, str]] = []
    if by_dev:
        first = sorted(by_dev)[0]
        merged = union_ns(by_dev[first])
        edges = [w[0]] + [x for iv in merged for x in iv] + [w[1]]
        idle = sorted(((hi - lo, lo, hi) for lo, hi
                       in zip(edges[0::2], edges[1::2]) if hi > lo),
                      reverse=True)[:N_GAPS]
        host = [e for e in events if e.plane == HOST_PLANE
                and e.name != WINDOW and e.dur_ns > 0]
        gaps = [(d * 1e-9, label_gap(host, 0.5 * (lo + hi)))
                for d, lo, hi in idle]
    n = len(busy)
    return Summary(window_s=(w[1] - w[0]) * 1e-9,
                   busy_s=(sum(busy.values()) / n * 1e-9) if n else 0.0,
                   op_s=op_s, gaps=gaps, n_devices=n)


def label_gap(host: list[Event], t_ns: float) -> str:
    """What the host was doing at ``t_ns``: the load generator's span
    (``gen.*``) and the innermost other host event open then, if any."""
    open_ = [e for e in host if e.start_ns <= t_ns < e.end_ns]
    gen = [e.name for e in open_ if e.name.startswith(GEN_PREFIX)]
    other = sorted((e for e in open_ if not e.name.startswith(GEN_PREFIX)),
                   key=lambda e: e.dur_ns)
    return (f"{gen[0] if gen else 'gen.none'}; "
            f"{other[0].name if other else 'no host op'}")
