"""Batched request/response serving for the streaming manifold mapper.

``serve.py --manifold`` used to be a fixed batch loop; this module is the
real serving surface in front of :class:`repro.core.streaming.StreamingMapper`
(local or mesh backend - the mapper is backend-agnostic, so the queue is
too):

* :class:`BatchedMapperService` - an arrival queue drained by a scheduler
  thread under the classic two-knob policy: flush when ``max_batch`` points
  have accumulated OR when the oldest waiting request has been queued for
  ``max_latency_ms`` (whichever first).  Callers get a
  :class:`concurrent.futures.Future` per request, so open-loop load
  generators and RPC frontends compose naturally.
* Fixed-shape execution: coalesced batches are zero-padded to ``max_batch``
  rows by default so the device executable is compiled exactly once, not
  once per coalesced size - p99 latency is jitter, not recompilation.
* Pipelined dispatch (``pipeline_depth > 1``): flushes run on a small
  worker pool behind a bounded in-flight window, so a slow mesh flush
  overlaps the *next* batch's coalescing instead of serializing with it -
  the read path keeps the device busy while the scheduler thread is only
  ever batching.  Depth 1 (default) is the original strictly-serial
  dispatch.  Absorbs still never run concurrently with a mapped batch:
  the scheduler drains the in-flight window (acquiring every permit)
  before executing write work.
* :meth:`BatchedMapperService.stats` - per-request latency percentiles
  (p50/p99) and batch occupancy over a bounded rolling window (memory
  stays flat under sustained traffic), plus lifetime request/point
  counters and sustained points/s - the numbers the serving benchmark
  (``benchmarks/bench_serving.py``) reports.  Lifetime time counters
  split each request's latency into ``queue_wait_s`` (submit to the
  start of its flush) and ``service_s`` (start of its flush to its
  reply), and each flush into ``pack_s``, ``map_call_s``, ``fetch_s``
  and ``reply_s``; the same phases are host spans
  (``repro:serve:*``, :mod:`repro.core.telemetry`).
* Write path: :meth:`BatchedMapperService.submit_absorb` coordinates
  geodesic absorbs (:meth:`StreamingMapper.absorb`) with the read path -
  updates run on the scheduler thread *between* flushes (never
  concurrently with a mapped batch), and admission control rejects
  absorption outright while the read queue is hot, so a slow O(n^2)
  expansion can never head-of-line block interactive traffic that is
  already backed up.  Reads themselves never block on a write: the
  mapper serves from an atomically-versioned snapshot.
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from concurrent.futures import Future

import numpy as np

from repro.core import telemetry

#: lifetime time counters of :meth:`BatchedMapperService.stats`, seconds:
#: summed over requests (queue wait, service) or over flushes (the rest)
TIME_COUNTERS = (
    "queue_wait_s", "service_s", "pack_s", "map_call_s", "fetch_s",
    "reply_s",
)


class AbsorbRejected(RuntimeError):
    """Absorption was refused by admission control (read queue hot)."""


@dataclasses.dataclass
class _Request:
    x: np.ndarray          # (n_i, D) arrival group
    future: Future
    t_submit: float        # monotonic seconds


class BatchedMapperService:
    """Queue + scheduler in front of a ``mapper(x) -> y`` callable.

    mapper: anything mapping an (m, D) array to an (m, d) array - in this
    repo a StreamingMapper on either pipeline backend.
    max_batch: flush as soon as this many points are waiting.
    max_latency_ms: flush when the oldest waiting request has been queued
    this long, even if the batch is not full (bounds tail latency under
    light load).
    pad_batches: zero-pad every coalesced batch to exactly ``max_batch``
    rows before calling the mapper (one compiled shape; padding rows are
    sliced off the result).  Coalescing never mixes requests past
    ``max_batch`` - an overflowing request opens the next batch instead -
    so only a single request larger than ``max_batch`` ever produces an
    off-shape (unpadded) flush.
    stats_window: how many recent requests/batches the latency and
    occupancy statistics cover.  Bounded deques, not unbounded lists:
    a long-lived server's stats memory stays flat no matter how much
    traffic it has served (lifetime counters are plain ints).
    absorb_admission: reject ``submit_absorb`` while more than this many
    *requests* are waiting in the read queue (None: ``max_batch``,
    i.e. roughly one flush worth of backlog).
    pipeline_depth: maximum flushes in flight at once.  1 (default)
    dispatches on the scheduler thread exactly as before; >1 dispatches
    each coalesced batch to a worker pool behind a semaphore window of
    this many permits, so batching the next flush overlaps a slow
    current one.  Absorbs drain the window first (write work stays
    strictly serialized against every mapped batch).
    """

    def __init__(
        self,
        mapper,
        *,
        max_batch: int = 64,
        max_latency_ms: float = 10.0,
        pad_batches: bool = True,
        stats_window: int = 4096,
        absorb_admission: int | None = None,
        pipeline_depth: int = 1,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if stats_window < 1:
            raise ValueError(
                f"stats_window must be >= 1, got {stats_window}"
            )
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self.mapper = mapper
        self.max_batch = max_batch
        self.max_latency_s = max_latency_ms / 1e3
        self.pad_batches = pad_batches
        self.absorb_admission = (
            absorb_admission if absorb_admission is not None else max_batch
        )
        self.pipeline_depth = pipeline_depth
        self._queue: queue.Queue[_Request] = queue.Queue()
        self._absorbs: collections.deque = collections.deque()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._executor = None              # worker pool when depth > 1
        self._inflight_sem = threading.BoundedSemaphore(pipeline_depth)
        self._inflight = 0
        self._inflight_peak = 0
        self._lock = threading.Lock()
        # rolling stats windows (bounded) + lifetime counters
        self._latencies: collections.deque[float] = collections.deque(
            maxlen=stats_window
        )
        self._batch_sizes: collections.deque[int] = collections.deque(
            maxlen=stats_window
        )
        self._t_first: float | None = None
        self._t_last: float | None = None
        self._n_points = 0
        self._n_requests = 0
        self._n_batches = 0
        self._n_absorbed = 0
        self._n_absorb_calls = 0
        self._times = dict.fromkeys(TIME_COUNTERS, 0.0)

    # --------------------------------------------------------- lifecycle --

    def start(self) -> "BatchedMapperService":
        if self._thread is not None:
            raise RuntimeError("service already started")
        if self.pipeline_depth > 1:
            from concurrent.futures import ThreadPoolExecutor

            self._executor = ThreadPoolExecutor(
                max_workers=self.pipeline_depth,
                thread_name_prefix="mapper-flush",
            )
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        """Stop the scheduler; pending requests (and admitted absorbs)
        are drained first, including any in-flight pipelined flushes."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def warmup(self, dim: int):
        """Compile the fixed-shape executable before taking traffic."""
        self.mapper(np.zeros((self.max_batch, dim), np.float32))

    # ----------------------------------------------------------- clients --

    def submit(self, x) -> Future:
        """Enqueue one arrival (D,) or arrival group (g, D); returns a
        Future resolving to the (g, d) manifold coordinates."""
        if self._thread is None:
            raise RuntimeError("service not started (use `with service:`)")
        x = np.atleast_2d(np.asarray(x))
        req = _Request(x=x, future=Future(), t_submit=time.monotonic())
        with self._lock:
            if self._t_first is None:
                self._t_first = req.t_submit
        self._queue.put(req)
        return req.future

    def map(self, x) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(x).result()

    def submit_absorb(self, x) -> Future:
        """Request that an arrival batch be folded into the base
        geodesics (``mapper.absorb``).  Returns a Future resolving to
        the :class:`repro.core.update.AbsorbReport`.

        Admission control: if the read queue currently holds more than
        ``absorb_admission`` waiting requests, the Future fails
        immediately with :class:`AbsorbRejected` - under pressure the
        service sheds the (deferrable) write work, never the reads.
        Admitted absorbs execute on the scheduler thread between
        flushes.
        """
        if self._thread is None:
            raise RuntimeError("service not started (use `with service:`)")
        fut: Future = Future()
        if self._queue.qsize() > self.absorb_admission:
            fut.set_exception(AbsorbRejected(
                f"read queue hot ({self._queue.qsize()} requests waiting "
                f"> admission limit {self.absorb_admission}); retry later"
            ))
            return fut
        self._absorbs.append(
            (np.atleast_2d(np.asarray(x)), fut, time.monotonic())
        )
        return fut

    def absorb(self, x):
        """Blocking convenience wrapper around :meth:`submit_absorb`."""
        return self.submit_absorb(x).result()

    # --------------------------------------------------------- scheduler --

    def _loop(self):
        pending: _Request | None = None   # overflow carried to next batch
        while True:
            if pending is not None:
                first, pending = pending, None
            else:
                try:
                    first = self._queue.get(timeout=0.01)
                except queue.Empty:
                    # idle gap: run deferred write work between flushes
                    self._run_absorbs()
                    if (
                        self._stop.is_set()
                        and self._queue.empty()
                        and not self._absorbs
                    ):
                        return
                    continue
            with telemetry.span("serve:coalesce"):
                batch, pending = self._coalesce(first)
            self._dispatch(batch)
            if pending is None and self._queue.empty():
                # between flushes with no backlog: absorb window
                self._run_absorbs()
            elif self._absorb_overdue():
                # sustained read traffic must not starve an *admitted*
                # absorb forever: once the oldest has aged well past the
                # batching deadline, run exactly one between flushes
                # (bounding the per-flush read-latency impact)
                self._run_absorbs(limit=1)

    def _coalesce(self, first: _Request):
        """-> (the batch opened by ``first``, the request that would
        have overflowed it or None)."""
        batch = [first]
        count = first.x.shape[0]
        deadline = first.t_submit + self.max_latency_s
        while count < self.max_batch:
            timeout = deadline - time.monotonic()
            try:
                # past the deadline, still drain whatever is already
                # queued (a slow flush must not collapse the next
                # batch to size 1 under backlog)
                req = (
                    self._queue.get(timeout=timeout)
                    if timeout > 0
                    else self._queue.get_nowait()
                )
            except queue.Empty:
                return batch, None
            if count + req.x.shape[0] > self.max_batch:
                # would overflow the fixed compiled shape: flush now,
                # open the next batch with this request
                return batch, req
            batch.append(req)
            count += req.x.shape[0]
        return batch, None

    def _dispatch(self, batch: list[_Request]):
        """Run one coalesced flush: inline at depth 1, else on the worker
        pool behind the bounded in-flight window (the acquire here is the
        backpressure - the scheduler stalls batching only when the whole
        window is busy)."""
        if self._executor is None:
            self._flush(batch)
            return
        self._inflight_sem.acquire()
        with self._lock:
            self._inflight += 1
            self._inflight_peak = max(self._inflight_peak, self._inflight)

        def run():
            try:
                self._flush(batch)
            finally:
                with self._lock:
                    self._inflight -= 1
                self._inflight_sem.release()

        self._executor.submit(run)

    def _drain_inflight(self):
        """Wait until no flush is in flight (scheduler thread only):
        acquire every window permit, then hand them all back.  This is
        the barrier that keeps absorbs strictly serialized against
        mapped batches under pipelined dispatch."""
        if self._executor is None:
            return
        for _ in range(self.pipeline_depth):
            self._inflight_sem.acquire()
        for _ in range(self.pipeline_depth):
            self._inflight_sem.release()

    def _absorb_overdue(self) -> bool:
        if not self._absorbs:
            return False
        waited = time.monotonic() - self._absorbs[0][2]
        return waited > max(10.0 * self.max_latency_s, 0.25)

    def _run_absorbs(self, limit: int | None = None):
        """Execute admitted absorbs (scheduler thread only, so updates
        are strictly serialized with read flushes)."""
        if not self._absorbs:
            return
        self._drain_inflight()
        while self._absorbs and (limit is None or limit > 0):
            x, fut, _ = self._absorbs.popleft()
            if limit is not None:
                limit -= 1
            try:
                with telemetry.span("serve:absorb"):
                    report = self.mapper.absorb(x)
            except Exception as e:
                fut.set_exception(e)
                continue
            with self._lock:
                self._n_absorb_calls += 1
                self._n_absorbed += getattr(report, "absorbed", 0)
            fut.set_result(report)

    def _flush(self, reqs: list[_Request]):
        t_flush = time.monotonic()
        try:
            with telemetry.span("serve:pack"):
                xs = np.concatenate([r.x for r in reqs], axis=0)
                n = xs.shape[0]
                if self.pad_batches and 0 < n < self.max_batch:
                    pad = np.zeros(
                        (self.max_batch - n, xs.shape[1]), xs.dtype
                    )
                    xs = np.concatenate([xs, pad])
            t_packed = time.monotonic()
            with telemetry.span("serve:map"):
                out = self.mapper(xs)
            t_mapped = time.monotonic()
            with telemetry.span("serve:fetch"):
                y = np.asarray(out)[:n]
        except Exception as e:
            # a failed flush fails every request in it, through the future
            # each caller reads: nothing escapes to the scheduler thread or
            # to a worker-pool future no one reads
            for r in reqs:
                r.future.set_exception(e)
            return
        t_done = time.monotonic()
        with telemetry.span("serve:reply"):
            off = 0
            for r in reqs:
                g = r.x.shape[0]
                r.future.set_result(y[off : off + g])
                off += g
        t_replied = time.monotonic()
        with self._lock:
            lat = [t_done - r.t_submit for r in reqs]
            self._latencies.extend(lat)
            service = len(reqs) * (t_done - t_flush)
            times = self._times
            times["queue_wait_s"] += sum(lat) - service
            times["service_s"] += service
            times["pack_s"] += t_packed - t_flush
            times["map_call_s"] += t_mapped - t_packed
            times["fetch_s"] += t_done - t_mapped
            times["reply_s"] += t_replied - t_done
            self._batch_sizes.append(n)
            self._n_requests += len(reqs)
            self._n_points += n
            self._n_batches += 1
            self._t_last = t_done

    # ------------------------------------------------------------- stats --

    def stats(self) -> dict:
        """Latency/occupancy percentiles over the rolling window, plus
        lifetime counters and sustained throughput."""
        with self._lock:
            lat = np.asarray(self._latencies)
            sizes = np.asarray(self._batch_sizes)
            n_requests = self._n_requests
            n_points = self._n_points
            n_batches = self._n_batches
            absorbed = self._n_absorbed
            absorb_calls = self._n_absorb_calls
            inflight_peak = self._inflight_peak
            times = dict(self._times)
            wall = (
                (self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0
            )
        if lat.size == 0:
            return {
                "requests": n_requests, "points": n_points, "batches": 0,
                "latency_p50_ms": float("nan"),
                "latency_p99_ms": float("nan"),
                "mean_batch": float("nan"), "points_per_s": 0.0,
                "window": 0, "absorbed": absorbed,
                "absorb_calls": absorb_calls,
                "pipeline_depth": self.pipeline_depth,
                "inflight_peak": inflight_peak,
                **times,
            }
        return {
            "requests": n_requests,
            "points": n_points,
            "batches": n_batches,
            "latency_p50_ms": float(np.percentile(lat, 50) * 1e3),
            "latency_p99_ms": float(np.percentile(lat, 99) * 1e3),
            "mean_batch": float(sizes.mean()),
            "points_per_s": n_points / max(wall, 1e-9),
            "window": int(lat.size),
            "absorbed": absorbed,
            "absorb_calls": absorb_calls,
            "pipeline_depth": self.pipeline_depth,
            "inflight_peak": inflight_peak,
            **times,
        }
