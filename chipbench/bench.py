"""One run of one cell: set up, measure for ``--seconds``, check, report.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start to window start):
weights made on the device from the seed in one jitted call, the
deployment built through ``ServingRuntime``, its plans compiled (always:
they hold the seed's weights, see ``kept_out_of_cache``; every other
program is read from the compile cache after a cell's first run), the
store's cache admitted once from a warm-up
stream of the cell's own traffic, then every bucket served once more.
The window then drives the mix for ``--seconds``; nothing compiles in it
(counted and printed). Afterwards the outstanding requests are awaited,
the device's peak memory read, the program freed, and every request sent
in the window compared with the float32 reference (``check.py``).

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` its
``busy_s`` / ``window_s`` and a ``breakdown``), and last ``checks``, the
compared numbers with their limits, which also end stderr.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import check, loadgen, peaks, registry, trace

#: how long after the window closes the run waits for outstanding answers
GRACE_S = 60.0
#: compile-cache directory, fixed inside the checkout
CACHE_DIR = os.path.join(registry.ROOT, ".chipbench_cache", "jax")

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the executables JAX makes, one per jit cache miss (``n``),
    and how many of them it loaded from its persistent cache (``loaded``),
    so a window can show that nothing compiled in it."""

    def __init__(self):
        import jax.monitoring
        self.n = self.loaded = 0
        self._listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENTS[0]:
            self.n += 1
        elif event == _COMPILE_EVENTS[1]:
            self.loaded += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listener)


def enable_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept (no minimum compile time)."""
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", 2 << 30)


@contextlib.contextmanager
def kept_out_of_cache():
    """Compile without writing to the persistent cache. The program builds
    its dense weights into its plans as constants, so every seed has plans
    of its own: cached, they would make a seed seen before set up faster
    than a new one. Kept out, every run compiles its plans."""
    import jax
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, float("inf"))
    try:
        yield
    finally:
        jax.config.update(key, old)


def seed_streams(seed: int) -> dict:
    """Independent numpy streams and the weights' JAX key, all from the
    one seed (any non-negative integer)."""
    import jax
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    weights, window, admit, spare = ss.spawn(4)
    return {"key": jax.random.PRNGKey(int(weights.generate_state(1)[0])),
            "window": np.random.default_rng(window),
            "admit": np.random.default_rng(admit)}


@dataclasses.dataclass
class Context:
    """What a metric's ``read(ctx)`` may read. Times are host-clock
    seconds (``time.perf_counter``); ``stats_*`` are ``EngineStats``
    snapshots: at window start and end, and at the traced window's ends."""
    cell: dict
    cfg: dict
    mix: dict
    ref_model: object
    chip: object
    seconds: float
    setup_s: float
    book: loadgen.Requests
    t0: float
    t1: float
    stats0: object
    stats1: object
    trace: trace.Summary | None = None
    trace_t: tuple[float, float] | None = None
    stats_ta: object = None
    stats_tb: object = None

    @property
    def open_loop(self) -> bool:
        return self.mix["loop"] == "open"

    def latency_ms(self) -> np.ndarray:
        """Due-time latency of every request due in the window."""
        return self.book.latency_ms(self.t0, self.t1,
                                    (self.seconds + GRACE_S) * 1e3)

    def lateness_ms(self) -> np.ndarray:
        """How late the generator sent each request due in the window."""
        b = self.book
        due, sent = b.due[:b.n], b.sent[:b.n]
        inw = (due >= self.t0) & (due < self.t1)
        return (sent[inw] - due[inw]) * 1e3

    def scored(self, lo: float, hi: float) -> int:
        """Requests whose scores resolved in ``[lo, hi)``."""
        b = self.book
        done, st = b.done[:b.n], b.status[:b.n]
        return int(((done >= lo) & (done < hi)
                    & (st == loadgen.SCORED)).sum())


def _serve_and_wait(dep, rows: np.ndarray, timeout: float = 600.0):
    futs = [dep.submit(r) for r in rows]
    return np.array([f.result(timeout=timeout) for f in futs])


def _device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def window_traffic(streams: dict, mix: dict, sizes, seconds: float):
    """``(rows, offsets, capacity)`` of one window of ``mix``: the request
    rows (the schedule's, or the closed loop's pool), the due offsets of
    an open loop (else None), and the size of the requests' book."""
    rng = streams["window"]
    if mix["loop"] == "open":
        offsets = loadgen.arrival_offsets(rng, mix["arrivals"], seconds)
        rows = loadgen.request_rows(rng, offsets.size, sizes, mix["ids"])
        return rows, offsets, offsets.size
    rows = loadgen.request_rows(rng, mix["pool"], sizes, mix["ids"])
    return rows, None, int(mix["max_rate_per_s"] * seconds) \
        + mix["outstanding"]


@dataclasses.dataclass
class Window:
    """One measured window: the requests' book, its host-clock ends, the
    engine's counters at both ends, and the traced part, if any."""
    book: loadgen.Requests
    t0: float
    t1: float
    stats0: object
    stats1: object
    compiles: int
    trace_dir: str | None = None
    trace_t: tuple[float, float] | None = None
    stats_ta: object = None
    stats_tb: object = None


class Session:
    """One deployment of a cell's configuration, set up from a seed:
    weights made on the device, the runtime built, plans compiled, the
    store's cache admitted once from a warm-up stream of the cell's own
    traffic, and every bucket served once on the admitted cache.
    :meth:`window` then drives a mix over it; :meth:`close` frees it."""

    def __init__(self, bench: registry.Benchmark, workload: str, seed: int):
        import jax
        from chipbench import program
        self.cell = bench.workload(workload)
        self.cfg = bench.config(self.cell["config"])
        self.mix = bench.mix(self.cell["traffic"])
        self.ref_model = bench.model(self.cfg["model"])
        self.streams = seed_streams(seed)
        self.sizes = self.cfg["schema"]["field_sizes"]
        self.counter = CompileCounter()
        self.dep = None
        phases = {}
        t = time.perf_counter()
        c = self.counter
        seen = (0, 0)

        def lap(name):
            nonlocal t, seen
            now = time.perf_counter()
            phases[name] = (round(now - t, 3), c.n - seen[0],
                            c.loaded - seen[1])
            t, seen = now, (c.n, c.loaded)
        try:
            params = program.make_params(self.cfg, self.ref_model,
                                         self.streams["key"])
            jax.block_until_ready(params)
            lap("weights")
            self.dep = program.Deployment(self.cfg, params)
            del params
            lap("deployment")
            with kept_out_of_cache():
                self.dep.warm_plans()
            lap("plans")
            admit_rows = loadgen.request_rows(
                self.streams["admit"], self.cfg["store"]["admit_requests"],
                self.sizes, self.mix["ids"])
            self.dep.start()
            _serve_and_wait(self.dep, admit_rows)
            lap("admission_stream")
            self.dep.admit()
            lap("admit")
            for b in self.dep.buckets:            # every bucket, new cache
                _serve_and_wait(self.dep, admit_rows[:b])
            _serve_and_wait(self.dep, admit_rows[:1])   # a timed-out partial
            lap("warm_buckets")
        except BaseException:
            self.close()
            raise
        log(f"[setup] per phase (seconds, executables made, of them loaded "
            f"from the compile cache): {phases}")

    def traffic(self, mix: dict, seconds: float):
        return window_traffic(self.streams, mix, self.sizes, seconds)

    def window(self, mix: dict, rows, offsets, capacity: int,
               seconds: float, traced: bool = False) -> Window:
        """Drive ``mix`` for ``seconds``, then wait for what is
        outstanding (up to ``GRACE_S``)."""
        import jax
        book = loadgen.Requests(capacity)
        annotate = jax.profiler.TraceAnnotation if traced else None
        loop = loadgen.Loop(mix, rows, self.dep.submit, book, offsets,
                            annotate)
        gc.collect()
        gc.freeze()
        try:
            stats0 = self.dep.stats()
            n0 = self.counter.n
            t0 = time.perf_counter() + 0.01
            t1 = t0 + seconds
            loop.start(t0, t1)
            w = Window(book=book, t0=t0, t1=t1, stats0=stats0, stats1=None,
                       compiles=0)
            if traced:
                w.trace_dir, w.trace_t, w.stats_ta, w.stats_tb = \
                    _traced_part(jax, self.dep, t0, seconds)
            loop.join()
            w.stats1 = self.dep.stats()
            w.compiles = self.counter.n - n0
            book.wait(t1 + GRACE_S)
        finally:
            gc.unfreeze()
        done = book.done[:book.n]
        per_s = np.histogram(done[np.isfinite(done)] - t0,
                             bins=np.arange(0.0, seconds + 1e-9, 1.0))[0]
        batches = {b: n - stats0.batches_per_bucket.get(b, 0)
                   for b, n in w.stats1.batches_per_bucket.items()}
        log(f"[window] {book.n} requests sent, {w.compiles} compilations "
            f"inside the window; scored per second {per_s.tolist()}; "
            f"batches per bucket {batches}")
        if offsets is not None:
            lat = book.latency_ms(t0, t1, (seconds + GRACE_S) * 1e3)
            log("[window] latency ms p50 / p99 / p99.9 / max " + " / ".join(
                f"{loadgen.latency_percentile(lat, q):.3f}"
                for q in (50, 99, 99.9, 100)))
        return w

    def close(self) -> None:
        if self.dep is not None:
            self.dep.stop()
            self.dep = None
        self.counter.close()


def run_cell(bench: registry.Benchmark, workload: str, seed: int,
             seconds: float, traced: bool, t_start: float) -> dict:
    """One run of ``workload``; returns the result line's object. Reaches
    for whatever device JAX gives it: the caller checks for the chip."""
    import jax
    chip = peaks.chip(jax.devices()[0].device_kind) if traced else None
    log(f"[setup] {time.perf_counter() - t_start:.3f} s to the session "
        "(imports, device start)")
    s = Session(bench, workload, seed)
    try:
        rows, offsets, capacity = s.traffic(s.mix, seconds)
        w = s.window(s.mix, rows, offsets, capacity, seconds, traced)
        device = _device_info(jax)
    finally:
        s.close()
    gc.collect()
    book, cfg = w.book, s.cfg

    trace_sum = None
    if w.trace_dir is not None:
        try:
            trace_sum = trace.summarize(trace.load(w.trace_dir))
        finally:
            shutil.rmtree(w.trace_dir, ignore_errors=True)
        device["busy_s"] = trace_sum.busy_s
        device["window_s"] = trace_sum.window_s

    # the check: every request sent, against the reference
    n = book.n
    weights = jax.jit(lambda k: s.ref_model.init_weights(cfg, k))(
        s.streams["key"])
    used, inv = np.unique(book.row[:n], return_inverse=True)
    ref = check.reference_scores(cfg, s.ref_model, weights,
                                 rows[used])[inv]
    del weights
    ok = book.status[:n] == loadgen.SCORED
    checks = check.compare(book.score[:n], ok, ref, cfg["limits"])

    ctx = Context(cell=s.cell, cfg=cfg, mix=s.mix, ref_model=s.ref_model,
                  chip=chip, seconds=seconds, setup_s=w.t0 - t_start,
                  book=book, t0=w.t0, t1=w.t1, stats0=w.stats0,
                  stats1=w.stats1, trace=trace_sum, trace_t=w.trace_t,
                  stats_ta=w.stats_ta, stats_tb=w.stats_tb)
    metrics = {}
    for m in bench.metrics_for(workload, traced):
        value = bench.metric(m["name"]).read(ctx)
        if value is None:
            if not traced:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": check.passed(checks) and not book.errors,
           "attempted": int(n),
           "failed": int((~ok).sum()),
           "metrics": metrics,
           "device": device}
    if trace_sum is not None:
        out["breakdown"] = trace_sum.breakdown()
    if book.errors:
        log(f"[errors] {book.errors}")
    out["compiles_in_window"] = w.compiles
    out["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return out


def _traced_part(jax, dep, t0: float, seconds: float):
    """Trace a few seconds from a third of the way into the window; the
    host marker ``trace.WINDOW`` spans exactly the traced part."""
    span = min(3.0, seconds / 3.0)
    _sleep_until(t0 + seconds / 3.0)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            stats_ta = dep.stats()
            ta = time.perf_counter()
            _sleep_until(ta + span)
            tb = time.perf_counter()
            stats_tb = dep.stats()
    finally:
        jax.profiler.stop_trace()
    return trace_dir, (ta, tb), stats_ta, stats_tb


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = registry.Benchmark()
    cell = bench.workload(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"chipbench: {args.workload} needs {cell['chips']} TPU "
            f"chip(s); JAX found {len(devs)} {devs[0].platform!r} "
            "device(s). Nothing was run.")
        return 3
    enable_compile_cache(CACHE_DIR)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start)
    print(json.dumps(out), flush=True)
    return 0
