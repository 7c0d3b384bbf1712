#!/usr/bin/env python3
"""Find a configuration's knee: the highest open-loop rate it sustains.

    python chipbench/sweep.py --workload dcnv2-criteo.steady --seed 5 \\
        --rates 8000,16000,24000 --seconds 10 --repeats 3 \\
        --hold-seconds 60 --hold-repeats 2 --probe

One process, one set-up (the cell's configuration and mix), then
``--repeats`` windows per rate, lowest rate first, each a steady Poisson
stream at that rate with the mix's ids and a new draw of requests. A
rate is sustained when, in the median over its windows,

  * the backlog (requests sent but not resolved) does not grow: at the
    window's end it exceeds its level at mid-window by less than the
    largest bucket, and
  * the median due-time latency is within the mix's
    ``assumed.p50_limit_ms``: requests have begun to queue past it.

The p99 is printed beside it, and the number of windows whose p99 is
past ``assumed.latency_limit_ms``, but it does not decide: host stalls
of ~120 ms that come at any rate put it past the limit in about half of
all windows (``PERF.md``). The knee is the highest rate at which it and
every lower rate swept are sustained. With ``--hold-seconds``,
``--hold-repeats`` longer windows then run at 0.8 times the knee, to
show how the tail spreads there.

``--probe`` watches the host in every window: Python's garbage
collections (``gc.callbacks``) and a watchdog thread that sleeps 1 ms at
a time and records each wake-up 10 ms or more late (no Python thread got
the interpreter lock, or the host ran none), set beside the requests that
missed the latency limit. The watchdog wakes a thousand times a second,
so a probed window carries its small cost; the benchmark's runs have no
probe.

One JSON line per window, one per rate, then one with the knee, on
stdout. Run it on a machine with the chip; the steady cells' rates are
0.8 times the knee, written into their mix files by hand.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]


def backlog_growth(book, t0: float, t1: float) -> int:
    """Backlog (sent, not yet resolved) at ``t1`` minus at mid-window."""
    n = book.n
    sent, done = book.sent[:n], book.done[:n]

    def backlog(t):
        return int((sent <= t).sum() - (done <= t).sum())
    return backlog(t1) - backlog(0.5 * (t0 + t1))


class StallProbe:
    """Host stalls while it runs: garbage collections and late wake-ups of
    a 1 ms sleeper (see the module's docstring)."""

    def __init__(self, late_ms: float = 10.0):
        self.late_s = late_ms / 1e3
        self.gcs: list[tuple[float, float, int]] = []   # start, s, gen
        self.stalls: list[tuple[float, float]] = []     # start, s
        self._gc_t = None
        self._stop = threading.Event()
        self._thread = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gcs.append((self._gc_t, time.perf_counter() - self._gc_t,
                             info["generation"]))
            self._gc_t = None

    def _watch(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(0.001)
            late = time.perf_counter() - t - 0.001
            if late >= self.late_s:
                self.stalls.append((t, late))

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._watch,
                                        name="chipbench-probe", daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        return False

    def summary(self, book, t0: float, t1: float, limit_ms: float) -> dict:
        """Collections per generation; the longest stalls, each with the
        oldest generation collected during it; and the episodes of
        requests due in the window that missed ``limit_ms`` (due within
        50 ms of each other), each with the stall time that overlapped
        it. Times are seconds into the window, or milliseconds."""
        per_gen = {}
        for _, s, g in self.gcs:
            c = per_gen.setdefault(g, [0, 0.0, 0.0])
            c[0] += 1
            c[1] += s * 1e3
            c[2] = max(c[2], s * 1e3)

        def gc_gen(a, b):
            gens = [g for t, s, g in self.gcs if t < b and t + s > a]
            return max(gens) if gens else None

        def stall_ms(a, b):
            return sum(max(0.0, min(b, t + s) - max(a, t))
                       for t, s in self.stalls) * 1e3

        stalls = sorted(self.stalls, key=lambda x: -x[1])[:10]
        n = book.n
        due, done = book.due[:n], book.done[:n]
        inw = (due >= t0) & (due < t1)
        lat = (done - due) * 1e3
        slow = np.sort(due[inw & (lat > limit_ms)])
        episodes = []
        if slow.size:
            cut = np.flatnonzero(np.diff(slow) > 0.05) + 1
            for grp in np.split(slow, cut):
                a, b = float(grp[0]), float(grp[-1])
                sel = inw & (due >= a) & (due <= b)
                episodes.append([round(a - t0, 3), int(grp.size),
                                 round(float(lat[sel].max()), 1),
                                 round(stall_ms(a - 0.2, b + 0.05), 1)])
        episodes.sort(key=lambda e: -e[2])
        return {"gc_per_generation_n_total_max_ms":
                {str(g): [c[0], round(c[1], 1), round(c[2], 1)]
                 for g, c in sorted(per_gen.items())},
                "stalls_s_ms_gcgen": [[round(t - t0, 3), round(s * 1e3, 1),
                                       gc_gen(t, t + s)]
                                      for t, s in stalls],
                "stall_total_ms": round(stall_ms(t0, t1), 1),
                "slow_episodes_s_n_maxms_stallms": episodes[:10],
                "slow_episodes": len(episodes)}


def measure(bench, loadgen, s, rate: float, seconds: float, limit: float,
            probe: bool) -> dict:
    """One window at ``rate``; its row of numbers."""
    mix = {**s.mix, "loop": "open", "arrivals": {
        "process": "poisson",
        "phases": [{"seconds": 1.0, "rate_per_s": rate}]}}
    rows, offsets, cap = s.traffic(mix, seconds)
    p = StallProbe() if probe else None
    if p:
        with p:
            w = s.window(mix, rows, offsets, cap, seconds)
    else:
        w = s.window(mix, rows, offsets, cap, seconds)
    book = w.book
    lat = book.latency_ms(w.t0, w.t1, (seconds + bench.GRACE_S) * 1e3)
    late = (book.sent[:book.n] - book.due[:book.n]) * 1e3
    done = book.done[:book.n]
    row = {"rate_per_s": rate,
           "scored": float(((done >= w.t0) & (done < w.t1)).sum()
                           / seconds),
           "p50": loadgen.latency_percentile(lat, 50),
           "p99": loadgen.latency_percentile(lat, 99),
           "gen_late_p99": loadgen.latency_percentile(late, 99),
           "backlog_growth": backlog_growth(book, w.t0, w.t1)}
    if p:
        row["probe"] = p.summary(book, w.t0, w.t1, limit)
    print(json.dumps(row), flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated rates in requests per second")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--hold-seconds", type=float, default=0.0)
    ap.add_argument("--hold-repeats", type=int, default=2)
    ap.add_argument("--probe", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import bench, loadgen, registry
    b = registry.Benchmark()
    import jax
    if jax.devices()[0].platform != "tpu":
        bench.log("sweep: JAX's first device is not a TPU; nothing was run")
        return 3
    bench.enable_compile_cache(bench.CACHE_DIR)
    s = bench.Session(b, args.workload, args.seed)
    limit = s.mix["assumed"]["latency_limit_ms"]
    p50_limit = s.mix["assumed"]["p50_limit_ms"]
    largest = max(s.dep.buckets)
    rates = sorted(float(r) for r in args.rates.split(","))
    knee, held = None, True
    try:
        bench.log(f"[sweep] set-up {time.perf_counter() - T_START:.1f} s")
        for rate in rates:
            rows = [measure(bench, loadgen, s, rate, args.seconds, limit,
                            args.probe)
                    for _ in range(args.repeats)]
            p50 = statistics.median(r["p50"] for r in rows)
            grow = statistics.median(r["backlog_growth"] for r in rows)
            ok = grow < largest and p50 <= p50_limit
            print(json.dumps({
                "rate_per_s": rate, "median_p50": p50,
                "median_p99": statistics.median(r["p99"] for r in rows),
                "windows_p99_past_limit": sum(r["p99"] > limit
                                              for r in rows),
                "median_backlog_growth": grow, "sustained": ok}),
                flush=True)
            held = held and ok
            if held:
                knee = rate
        print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                          "p50_limit_ms": p50_limit,
                          "rate_at_0.8_knee": None if knee is None
                          else round(0.8 * knee)}), flush=True)
        if knee is not None and args.hold_seconds > 0:
            for _ in range(args.hold_repeats):
                measure(bench, loadgen, s, round(0.8 * knee),
                        args.hold_seconds, limit, args.probe)
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
