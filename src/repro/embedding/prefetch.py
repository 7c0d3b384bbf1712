"""PrefetchPipeline — async host→device miss resolution for HostBackedStore.

The HugeCTR inference-parameter-server pattern (arXiv:2210.08804): when the
backing embedding table lives out of device memory, cache misses must not
stall the gather. This module owns the host side of that pipeline:

  * a **staging area** of ``S`` host-resident row slots mirroring the
    device staging buffer, with an LRU map ``row -> slot``;
  * an **async worker** that takes hints (the id rows of not-yet-served
    batches) and resolves their cache misses — gathers the missed rows
    from the host backing into staging slots — *while the previous
    batch's dense compute runs on device*;
  * a synchronous ``ensure`` used at serve time to close any remaining
    gap, so the device lookup never sees an unresolved row.

The store (``repro.embedding.host.HostBackedStore``) snapshots the staging
area into two runtime tensors per served batch — ``staging (S, d)`` and
``staging_slot_of_row (rows,)`` — published through the same
double-buffered swap as a cache refresh, so compiled plans survive every
batch with zero recompiles. When a batch's distinct miss set cannot fit
the ``S`` slots, ``ensure`` raises :class:`StagingOverflowError` and the
caller falls back to a synchronous chunked host gather
(``HostBackedStore.split_for_staging``) instead of serving wrong scores.

Thread safety: one lock guards the staging area (the serve thread's
``ensure``/``snapshot`` vs the worker's speculative staging); counters are
read under the same lock. Snapshots copy, so tensors already uploaded for
an in-flight batch can never be mutated behind the device's back.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque

import numpy as np

__all__ = ["StagingOverflowError", "PrefetchPipeline"]


class StagingOverflowError(RuntimeError):
    """A batch's distinct miss set exceeds the staging buffer's capacity.

    Raised by :meth:`PrefetchPipeline.ensure` (and surfaced through
    ``HostBackedStore.stage``). Callers must fall back to a synchronous
    chunked host gather — never serve the batch with unresolved rows.
    """


class PrefetchPipeline:
    """Host-side staging area + async miss-resolution worker.

    Args:
        store: the owning ``HostBackedStore`` — read for the live host
            backing table and the current cache index map (both change on
            adopt/refresh, so they are read per operation, never bound).
        capacity: number of staging row slots ``S``.

    The pipeline never touches the device: it fills a host staging buffer
    and bumps a version counter; the store turns dirty snapshots into
    fresh device tensors (and reuses the previous upload when nothing
    changed — an all-hit batch moves zero bytes).
    """

    def __init__(self, store, capacity: int):
        if capacity < 1:
            raise ValueError(f"staging capacity must be >= 1, got {capacity}")
        self._store = store
        self.capacity = int(capacity)
        spec = store.spec
        # the buffer holds *wire*-format rows: int8 payload (+ fp32 scale
        # sidecar) for quantized stores, full-precision rows otherwise —
        # so staging h2d traffic shrinks with the representation
        wire_dtype = np.int8 if spec.quantized else np.dtype(spec.dtype)
        self._buf = np.zeros((self.capacity, spec.dim), dtype=wire_dtype)
        self._sbuf = (np.zeros((self.capacity, 1), dtype=np.float32)
                      if spec.quantized else None)
        self._slot_of_staged = np.full(spec.rows, -1, dtype=np.int32)
        self._lru: OrderedDict[int, int] = OrderedDict()   # row -> slot
        self._free = list(range(self.capacity - 1, -1, -1))
        self._lock = threading.Lock()
        self._version = 0          # bumps on any buffer/map change
        # async worker
        self._q: deque[np.ndarray] = deque()
        self._cv = threading.Condition()
        self._thread: threading.Thread | None = None
        self._running = False
        self._idle = threading.Event()
        self._idle.set()
        # counters (read under _lock; mirrored into StoreStats by the store)
        self.n_prefetched = 0      # rows staged by the async worker
        self.n_hinted_batches = 0
        self.n_errors = 0          # hinted batches the worker failed on
        self.last_error: BaseException | None = None

    # -- staging area --------------------------------------------------------
    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def staged_rows(self) -> int:
        with self._lock:
            return len(self._lru)

    def _stage_rows_locked(self, need: np.ndarray, miss_set: set) -> int:
        """Gather ``need`` backing rows into free/evicted slots. Caller
        holds the lock and has verified the miss set fits."""
        backing = self._store.host_view()
        scales = self._store.host_scale_view() if self._sbuf is not None \
            else None
        staged = 0
        for row in need:
            row = int(row)
            if self._slot_of_staged[row] >= 0:      # raced with the worker
                self._lru.move_to_end(row)
                continue
            if self._free:
                slot = self._free.pop()
            else:
                # evict the least-recently-used row NOT in this miss set
                victim = next(r for r in self._lru if r not in miss_set)
                slot = self._lru.pop(victim)
                self._slot_of_staged[victim] = -1
            self._buf[slot] = backing[row]
            if scales is not None:
                self._sbuf[slot] = scales[row]
            self._slot_of_staged[row] = slot
            self._lru[row] = slot
            staged += 1
        if staged:
            self._version += 1
        return staged

    def ensure(self, miss_rows: np.ndarray) -> tuple[int, int]:
        """Make every row in ``miss_rows`` staged; returns
        ``(n_newly_staged, n_already_staged)``.

        ``miss_rows`` are unique global rows absent from the device cache.
        Rows already resolved (by a previous batch or the async worker)
        are free — they count as prefetch hits. Raises
        :class:`StagingOverflowError` when the set cannot fit ``S`` slots.
        """
        miss_rows = np.asarray(miss_rows).reshape(-1)
        if miss_rows.size > self.capacity:
            raise StagingOverflowError(
                f"batch misses {miss_rows.size} distinct uncached rows; "
                f"staging buffer holds {self.capacity} — serve in chunks "
                "(split_for_staging) or raise staging_capacity")
        with self._lock:
            need = miss_rows[self._slot_of_staged[miss_rows] < 0]
            already = int(miss_rows.size - need.size)
            # refresh LRU position of reused rows so hot staged rows survive
            for row in miss_rows[self._slot_of_staged[miss_rows] >= 0]:
                self._lru.move_to_end(int(row))
            staged = self._stage_rows_locked(need, set(miss_rows.tolist()))
        return staged, already

    def snapshot(self) -> tuple[np.ndarray, np.ndarray | None,
                                np.ndarray, int]:
        """Copy of ``(staging_buf, scale_buf_or_None, slot_of_staged,
        version)`` — safe to upload while the worker keeps staging for
        later batches. The scale sidecar is ``None`` for full-precision
        stores."""
        with self._lock:
            sbuf = self._sbuf.copy() if self._sbuf is not None else None
            return self._buf.copy(), sbuf, self._slot_of_staged.copy(), \
                self._version

    def apply_backing_update(self, rows: np.ndarray, write) -> int:
        """Run ``write()`` (a host-backing mutation covering ``rows``)
        under the staging lock, then re-gather any of those rows already
        sitting in staging slots so the buffer never serves stale values.

        The lock ordering is the point: the worker's speculative staging
        and the serve thread's ``ensure`` gather backing rows under this
        same lock, so the in-place backing write can never be observed
        half-done — a staged row is either entirely pre-delta or entirely
        post-delta. Returns how many staged slots were refreshed; any
        refresh bumps the version so the store's next snapshot re-uploads.
        """
        rows = np.asarray(rows).reshape(-1)
        with self._lock:
            write()
            backing = self._store.host_view()
            scales = self._store.host_scale_view() if self._sbuf is not None \
                else None
            refreshed = 0
            for row in rows:
                slot = int(self._slot_of_staged[int(row)])
                if slot < 0:
                    continue
                self._buf[slot] = backing[int(row)]
                if scales is not None:
                    self._sbuf[slot] = scales[int(row)]
                refreshed += 1
            if refreshed:
                self._version += 1
            return refreshed

    def drop(self, rows: np.ndarray) -> int:
        """Evict ``rows`` from staging (refresh promoted them into the
        device cache — their slots are better spent on cold rows)."""
        dropped = 0
        with self._lock:
            for row in np.asarray(rows).reshape(-1):
                row = int(row)
                slot = self._lru.pop(row, None)
                if slot is not None:
                    self._slot_of_staged[row] = -1
                    self._free.append(slot)
                    dropped += 1
            if dropped:
                self._version += 1
        return dropped

    # -- async worker --------------------------------------------------------
    def hint(self, miss_rows: np.ndarray) -> None:
        """Queue candidate rows for speculative staging off-thread.

        Best-effort: the worker stages what fits into currently-free (or
        LRU-evictable) slots and silently skips the rest — ``ensure`` at
        serve time closes any gap. Starts the daemon worker lazily and
        restarts it after a ``stop``.
        """
        rows = np.asarray(miss_rows).reshape(-1)
        if rows.size == 0:
            return
        with self._cv:
            self._q.append(rows)
            self._idle.clear()
            if self._thread is None or not self._thread.is_alive():
                self._running = True
                self._thread = threading.Thread(
                    target=self._worker_loop, daemon=True,
                    name="embedding-prefetch")
                self._thread.start()
            self._cv.notify()

    def stop(self) -> None:
        """Stop the worker thread (joins). Later hints restart it."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        t, self._thread = self._thread, None
        if t is not None and t.is_alive():
            t.join()
        self._idle.set()

    def wait_idle(self, timeout: float | None = 5.0) -> bool:
        """Block until the hint queue is drained (tests/benchmarks use
        this to make prefetch counters deterministic)."""
        return self._idle.wait(timeout)

    def _worker_loop(self) -> None:
        while True:
            with self._cv:
                while self._running and not self._q:
                    self._idle.set()
                    self._cv.wait()
                if not self._running:
                    self._idle.set()
                    return
                rows = self._q.popleft()
            try:
                self._prefetch(rows)
            except Exception as exc:
                # speculative work only — ensure() redoes anything missed —
                # but counted, so a serving run can fail on it
                with self._lock:
                    self.n_errors += 1
                    self.last_error = exc

    def _prefetch(self, rows: np.ndarray) -> None:
        """Stage the cache misses of a hinted batch, capped at what fits."""
        slot_of_row = self._store.cache_map_view()
        rows = np.unique(rows)
        miss = rows[slot_of_row[rows] < 0]
        if miss.size == 0:
            return
        with self._lock:
            need = miss[self._slot_of_staged[miss] < 0]
            # cap at free + evictable (never evict rows this hint needs)
            budget = len(self._free) + max(
                0, len(self._lru) - int((self._slot_of_staged[miss] >= 0)
                                        .sum()))
            need = need[:budget]
            n = self._stage_rows_locked(need, set(miss.tolist()))
            self.n_prefetched += n
            self.n_hinted_batches += 1
