"""The one traffic generator: request ids, arrival schedules and the two
load loops, all driven by a mix file (``chipbench/traffic/<mix>.json``).

A mix file holds parameters only::

    {"loop": "closed", "outstanding": 2048, "pool": 262144,
     "ids": {"dist": "zipf", "exponent": 1.1}}

    {"loop": "open", "ids": {"dist": "zipf", "exponent": 1.1},
     "arrivals": {"process": "poisson",
                  "phases": [{"seconds": 1.0, "rate_per_s": 20000}]}}

``phases`` is a periodic piecewise-constant rate: one phase is a steady
Poisson stream, several make an on/off or diurnal pattern. Everything is a
pure function of the seed; the program only ever sees the generated rows.

Latency in the open loop runs from a request's *due* time on the schedule
to the moment its score resolves, so a stalled generator or server shows
up in every later request's latency (choosing-metrics §5).
"""

from __future__ import annotations

import collections
import functools
import math
import threading
import time

import numpy as np

#: request status codes in ``Requests.status``
PENDING, SCORED, FAILED = 0, 1, 2


def zipf_ids(rng: np.random.Generator, n: int, field_sizes,
             exponent: float) -> np.ndarray:
    """Per-field zipf ids, ``P(id = r) ∝ (r + 1)^-exponent``, ``id < n_i``.

    Inverse-CDF sampling of the continuous bounded power law (exact for
    ``exponent == 1``, the usual continuous surrogate otherwise), so it is
    O(n·k) and needs no per-field tables over multi-million vocabularies.
    ``exponent == 0`` gives uniform ids. Returns ``(n, k)`` int32.
    """
    sizes = np.asarray(field_sizes, dtype=np.float64)[None, :]
    u = rng.random((n, sizes.shape[1]))
    s = float(exponent)
    if abs(s - 1.0) < 1e-9:
        x = np.power(sizes, u)
    else:
        x = np.power(1.0 + u * (np.power(sizes, 1.0 - s) - 1.0),
                     1.0 / (1.0 - s))
    ids = np.floor(x).astype(np.int64) - 1
    return np.clip(ids, 0, sizes.astype(np.int64) - 1).astype(np.int32)


def request_rows(rng: np.random.Generator, n: int, schema: dict,
                 ids: dict) -> np.ndarray:
    """``n`` int32 request rows of a configuration's ``schema``, laid out
    as ``registry``'s docstring says: the numeric counts
    (``schema.numeric``), zipf with their own exponent, then each field's
    ``h_i`` ids (``schema.hotness``), each drawn on its own from the
    field's zipf under the mix's ``ids``. A schema with neither key draws
    exactly what one id per field always drew: the same generator calls
    in the same order.
    """
    if ids.get("dist") != "zipf":
        raise ValueError(f"unknown id distribution {ids.get('dist')!r}")
    sizes = schema["field_sizes"]
    hot = schema.get("hotness")
    if hot is not None:
        if len(hot) != len(sizes) or min(hot) < 1:
            raise ValueError(f"hotness {hot} does not give each of the "
                             f"{len(sizes)} fields one id or more")
        sizes = np.repeat(np.asarray(sizes, np.int64), hot)
    num = schema.get("numeric")
    if num is None:
        return zipf_ids(rng, n, sizes, ids["exponent"])
    counts = zipf_ids(rng, n, num["caps"], num["exponent"])
    return np.concatenate([counts, zipf_ids(rng, n, sizes, ids["exponent"])],
                          axis=1)


def arrival_offsets(rng: np.random.Generator, arrivals: dict,
                    seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson process whose rate is the
    periodic piecewise-constant profile in ``arrivals["phases"]``.

    Unit-rate points are mapped through the inverse of the cumulative
    intensity (time rescaling), so one draw serves any profile.
    """
    if arrivals.get("process") != "poisson":
        raise ValueError(f"unknown arrival process "
                         f"{arrivals.get('process')!r}")
    dur = np.array([p["seconds"] for p in arrivals["phases"]], np.float64)
    rate = np.array([p["rate_per_s"] for p in arrivals["phases"]],
                    np.float64)
    if (dur <= 0).any() or (rate < 0).any() or not (dur * rate).sum() > 0:
        raise ValueError(f"bad phases {arrivals['phases']}")
    t_edges = np.concatenate([[0.0], np.cumsum(dur)])
    lam_edges = np.concatenate([[0.0], np.cumsum(dur * rate)])
    period, lam_period = t_edges[-1], lam_edges[-1]
    expected = lam_period * seconds / period
    n_draw = int(expected + 8 * math.sqrt(expected) + 64)
    out = []
    s0 = 0.0
    while True:
        s = s0 + np.cumsum(rng.exponential(1.0, size=n_draw))
        s0 = float(s[-1])
        k, frac = np.divmod(s, lam_period)
        t = k * period + np.interp(frac, lam_edges, t_edges)
        out.append(t[t < seconds])
        if t[-1] >= seconds:
            return np.concatenate(out)


def latency_percentile(lat_ms: np.ndarray, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q ≤ 100) over every sample,
    misses (``inf``) included: no interpolation, so a percentile is always
    a latency some request had."""
    lat = np.sort(np.asarray(lat_ms, dtype=np.float64))
    if lat.size == 0:
        raise ValueError("no requests to take a percentile of")
    rank = max(1, math.ceil(q / 100.0 * lat.size))
    return float(lat[rank - 1])


class Requests:
    """Book of every request sent in a run: its row, due and send times,
    resolution time, score and status, in preallocated arrays. The done
    callback copies a future's outcome here and drops the future, so no
    resolved future outlives its batch."""

    def __init__(self, capacity: int):
        self.row = np.zeros(capacity, np.int64)
        self.due = np.zeros(capacity, np.float64)
        self.sent = np.zeros(capacity, np.float64)
        self.done = np.full(capacity, np.inf)
        self.score = np.full(capacity, np.nan, np.float32)
        self.status = np.zeros(capacity, np.int8)
        self.n = 0
        self.errors: list[str] = []

    def on_done(self, i: int, fut) -> None:
        self.done[i] = time.perf_counter()
        try:
            self.score[i] = fut.result(timeout=0)
            self.status[i] = SCORED
        except Exception as exc:            # the request failed: record it
            self.status[i] = FAILED
            if len(self.errors) < 4:
                self.errors.append(repr(exc))

    def wait(self, deadline: float) -> None:
        """Poll until every sent request resolved or ``deadline``."""
        while time.perf_counter() < deadline:
            if not (self.status[:self.n] == PENDING).any():
                return
            time.sleep(0.01)

    def latency_ms(self, lo: float, hi: float, miss_ms: float) -> np.ndarray:
        """Due-time latency of every request due in ``[lo, hi)``; a failed
        or unresolved request counts as ``miss_ms``, past any limit."""
        sel = slice(0, self.n)
        due, done, st = self.due[sel], self.done[sel], self.status[sel]
        inw = (due >= lo) & (due < hi)
        lat = (done[inw] - due[inw]) * 1e3
        lat[st[inw] != SCORED] = miss_ms
        return lat


class Loop:
    """Drives one mix against ``submit(row) -> future`` from its own
    thread. ``run(t0, t_end)`` sends until ``t_end``; the caller joins.

    Open loop: each request is sent at its due time (``t0`` + offset);
    when the thread falls behind it sends every overdue request at once
    and the lateness is recorded. Closed loop: ``outstanding`` requests
    are in flight; each resolution is replaced by a new request from the
    generator's own thread, cycling through the row pool.
    """

    def __init__(self, mix: dict, rows: np.ndarray, submit, book: Requests,
                 offsets: np.ndarray | None = None, annotate=None):
        self.mix = mix
        self.rows = rows
        self.submit = submit
        self.book = book
        self.offsets = offsets
        self.annotate = annotate          # span context factory, or None
        self._done_q: collections.deque = collections.deque()
        self.thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def _span(self, name: str):
        return self.annotate(name) if self.annotate else _NULL

    def _send(self, i: int, due: float, on_done) -> None:
        book = self.book
        book.row[i] = i % len(self.rows)
        book.due[i] = due
        book.sent[i] = time.perf_counter()
        fut = self.submit(self.rows[i % len(self.rows)])
        fut.add_done_callback(functools.partial(on_done, i))
        book.n = i + 1

    def _replace(self, i: int, fut) -> None:
        """Closed loop: record the outcome and queue a replacement."""
        self.book.on_done(i, fut)
        self._done_q.append(i)

    def start(self, t0: float, t_end: float) -> None:
        target = (self._open if self.mix["loop"] == "open"
                  else self._closed)

        def body():
            try:
                target(t0, t_end)
            except BaseException as exc:     # re-raised by join()
                self.error = exc
        self.thread = threading.Thread(target=body, name="chipbench-load")
        self.thread.start()

    def join(self) -> None:
        self.thread.join()
        if self.error is not None:
            raise self.error

    def _open(self, t0: float, t_end: float) -> None:
        due = t0 + self.offsets
        n = due.size
        i = 0
        while i < n:
            now = time.perf_counter()
            if due[i] <= now:
                with self._span("gen.send"):
                    while i < n and due[i] <= now:
                        self._send(i, due[i], self.book.on_done)
                        i += 1
                continue
            if now >= t_end:
                return
            with self._span("gen.wait"):
                time.sleep(min(due[i] - now, 0.002))

    def _closed(self, t0: float, t_end: float) -> None:
        i = 0
        with self._span("gen.send"):
            for _ in range(self.mix["outstanding"]):
                self._send(i, time.perf_counter(), self._replace)
                i += 1
        q = self._done_q
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            k = len(q)
            if not k:
                with self._span("gen.wait"):
                    time.sleep(0.0001)
                continue
            with self._span("gen.send"):
                for _ in range(k):
                    q.popleft()
                    self._send(i, time.perf_counter(), self._replace)
                    i += 1


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
