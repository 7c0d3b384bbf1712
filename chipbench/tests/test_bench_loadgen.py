"""The traffic generator and the latency arithmetic."""

import threading
import time

import numpy as np
import pytest

from chipbench import loadgen


def test_zipf_ids_in_range_and_skewed():
    rng = np.random.default_rng(0)
    sizes = [5_000_000, 1000, 2]
    ids = loadgen.zipf_ids(rng, 20000, sizes, 1.1)
    assert ids.dtype == np.int32 and ids.shape == (20000, 3)
    assert (ids >= 0).all() and (ids < np.array(sizes)).all()
    # the head is heavy: id 0 of the big field far above uniform's 4e-3%
    assert (ids[:, 0] == 0).mean() > 0.05
    uni = loadgen.zipf_ids(rng, 20000, [1000], 0.0)
    assert abs(uni.mean() - 499.5) < 15


def test_same_seed_same_rows():
    schema = {"field_sizes": [50, 60]}
    a = loadgen.request_rows(np.random.default_rng(7), 100, schema,
                             {"dist": "zipf", "exponent": 1.1})
    b = loadgen.request_rows(np.random.default_rng(7), 100, schema,
                             {"dist": "zipf", "exponent": 1.1})
    assert np.array_equal(a, b)
    with pytest.raises(ValueError):
        loadgen.request_rows(np.random.default_rng(7), 1,
                             {"field_sizes": [5]}, {"dist": "lognormal"})


def test_poisson_offsets_rate_and_phases():
    rng = np.random.default_rng(1)
    steady = {"process": "poisson",
              "phases": [{"seconds": 1.0, "rate_per_s": 20000}]}
    t = loadgen.arrival_offsets(rng, steady, 5.0)
    assert (np.diff(t) >= 0).all() and t[0] >= 0 and t[-1] < 5.0
    assert abs(t.size - 100000) < 5 * np.sqrt(100000)
    onoff = {"process": "poisson",
             "phases": [{"seconds": 0.25, "rate_per_s": 30000},
                        {"seconds": 0.75, "rate_per_s": 2000}]}
    t = loadgen.arrival_offsets(rng, onoff, 4.0)
    on = ((t % 1.0) < 0.25).sum()
    assert abs(on - 30000) < 5 * np.sqrt(30000)
    assert abs((t.size - on) - 6000) < 5 * np.sqrt(6000)


def test_percentile_is_nearest_rank_over_all_samples():
    lat = np.arange(1, 101, dtype=float)           # 1..100 ms
    assert loadgen.latency_percentile(lat, 50) == 50.0
    assert loadgen.latency_percentile(lat, 99) == 99.0
    assert loadgen.latency_percentile(lat, 100) == 100.0
    with pytest.raises(ValueError):
        loadgen.latency_percentile(np.array([]), 99)


class _Fut:
    def __init__(self, score=None, exc=None):
        self.score, self.exc = score, exc

    def result(self, timeout=None):
        if self.exc is not None:
            raise self.exc
        return self.score


def test_latency_from_due_time_and_misses():
    book = loadgen.Requests(4)
    book.n = 4
    book.due[:4] = [1.0, 1.5, 2.0, 9.0]       # the last is not in [1, 3)
    for i in range(4):
        book.done[i] = book.due[i] + 0.002 * (i + 1)
    book.on_done(0, _Fut(0.5))
    book.on_done(1, _Fut(exc=RuntimeError("boom")))   # failed: a miss
    book.on_done(3, _Fut(0.1))
    book.done[0] = 1.004                      # resolved 4 ms after due
    # request 2 never resolved: a miss too
    lat = book.latency_ms(1.0, 3.0, miss_ms=60000.0)
    assert lat.tolist() == [pytest.approx(4.0), 60000.0, 60000.0]
    assert book.errors == ["RuntimeError('boom')"]
    assert loadgen.latency_percentile(lat, 50) == 60000.0


class _Future:
    """A future with ``RequestFuture``'s callback rule: a callback added
    after resolution runs at once."""

    def __init__(self, score):
        self.score = score
        self.done = False
        self.cbs = []
        self.lock = threading.Lock()

    def result(self, timeout=None):
        return self.score

    def add_done_callback(self, fn):
        with self.lock:
            if not self.done:
                self.cbs.append(fn)
                return
        fn(self)

    def resolve(self):
        with self.lock:
            self.done = True
            cbs, self.cbs = self.cbs, []
        for fn in cbs:
            fn(self)


class _Server:
    """Resolves each submitted request after ``delay`` on its own thread."""

    def __init__(self, delay=0.001):
        self.delay = delay
        self.n = 0
        self.lock = threading.Lock()
        self.threads = []

    def submit(self, row):
        fut = _Future(float(row.sum()))

        def finish():
            time.sleep(self.delay)
            fut.resolve()
        with self.lock:
            self.n += 1
        t = threading.Thread(target=finish)
        self.threads.append(t)
        t.start()
        return fut


def test_open_loop_sends_on_schedule():
    rows = np.arange(200, dtype=np.int32).reshape(100, 2)
    offsets = np.linspace(0.0, 0.2, 100, endpoint=False)
    book = loadgen.Requests(100)
    srv = _Server()
    loop = loadgen.Loop({"loop": "open"}, rows, srv.submit, book, offsets)
    t0 = time.perf_counter() + 0.01
    loop.start(t0, t0 + 0.2)
    loop.join()
    for t in srv.threads:
        t.join(timeout=5)
    assert book.n == 100 and srv.n == 100
    assert np.allclose(book.due[:100], t0 + offsets)
    assert (book.sent[:100] >= book.due[:100]).all()
    assert (book.status[:100] == loadgen.SCORED).all()
    assert np.array_equal(book.score[:100], rows.sum(axis=1))


def test_closed_loop_keeps_outstanding_and_cycles_pool():
    rows = np.arange(20, dtype=np.int32).reshape(10, 2)
    book = loadgen.Requests(100000)
    srv = _Server(delay=0.002)
    loop = loadgen.Loop({"loop": "closed", "outstanding": 4}, rows,
                        srv.submit, book)
    t0 = time.perf_counter()
    loop.start(t0, t0 + 0.3)
    loop.join()
    for t in srv.threads:
        t.join(timeout=5)
    n = book.n
    assert n > 20                               # replaced as they resolved
    assert (book.status[:n] == loadgen.SCORED).all()
    assert np.array_equal(book.row[:n], np.arange(n) % 10)
    assert np.array_equal(book.score[:n], rows.sum(axis=1)[np.arange(n) % 10])
