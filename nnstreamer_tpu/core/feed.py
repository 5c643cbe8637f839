"""Async device feed: the completion-driven dispatch window and the
double-buffered host->device staging lane.

The problem both pieces attack is the same (ROADMAP item 1): the filter
hot path used to *block the dispatch thread* on device I/O — once the
in-flight window filled it sat inside the oldest batch's ``device_get``,
and every host-sourced batch paid its host->device transfer inline before
dispatch.  Either wait idles the only thread that can stack and dispatch
the next batch, so depth-4 pipelining barely beat depth-1 on TPU
(BENCH_r05: 1821 vs 1806 fps against a 13.5k fps raw ceiling).

* :class:`CompletionWindow` parks dispatched micro-batches FIFO and hands
  the blocking device->host materialization to a dedicated **reaper
  thread** per window (≙ one per fused filter segment).  The dispatch
  thread only ever *polls* completed entries off the front; when the
  window is full it waits on a completion event — never inside
  ``device_get`` — and the wait is cooperatively interruptible, which the
  old in-C blocking sync was not.
* :class:`HostStagingLane` runs host-side batch stacking and the
  (async) ``device_put`` on a lane worker thread, double-buffered through
  :class:`~.buffer.DeviceBufferPool` staging arrays: while batch k
  computes, batch k+1 is stacked and its transfer issued.  The filter
  defers dispatch by exactly one batch, so by the time it needs batch k's
  device arrays the transfer has been overlapping with k-1's compute.

Emission order stays strictly FIFO through both; drain()/stop()/hot-swap
boundary contracts account every parked frame (the filter's
``pending_frames`` hook sums window payloads plus the staged batch).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from .buffer import DEVICE_POOL, materialize as _materialize
from .liveness import ThreadBeat
from .telemetry import Log2Histogram
from .tracer import name_os_thread, span


class _WindowEntry:
    __slots__ = ("out_b", "payload", "seq", "mats", "error", "done",
                 "claimed", "t_park")

    def __init__(self, out_b, payload, seq=None):
        self.out_b = out_b
        self.payload = payload
        self.seq = seq  # the batch's sequence number, for its reap span
        self.mats: Optional[List[np.ndarray]] = None
        self.error: Optional[BaseException] = None
        self.done = False
        self.claimed = False
        self.t_park = time.perf_counter()


class CompletionWindow:
    """FIFO window of in-flight micro-batches, drained by completion.

    ``park()`` appends a dispatched batch's (device) outputs; a lazy
    **reaper thread** materializes entries strictly in park order — the
    blocking device->host sync happens there, overlapped with whatever
    the dispatch thread does next.  ``pop_ready()`` returns the completed
    prefix without blocking; ``wait_oldest()`` is the bounded backpressure
    wait for a full window (completion-event wait, not ``device_get``).

    A materialization error is stored on its entry and re-raised from
    ``pop_ready()`` on the *dispatch* thread, once the completed entries
    ahead of it have been handed out — so supervision attributes the
    failure to the owning element exactly as a synchronous invoke error.

    ``clear()`` discards all entries (Flush semantics); a reaper mid-sync
    on a cleared entry finishes harmlessly into the discarded carcass.
    ``close()`` additionally stops the reaper thread; a later ``park()``
    transparently reopens (restart-after-stop).
    """

    __slots__ = ("name", "_materialize", "_dq", "_cv", "_reaper", "_closed",
                 "reaped", "dispatch_waits", "dwell", "heartbeat")

    def __init__(self, name: str = "window",
                 materialize: Optional[Callable] = None):
        self.name = name
        self._materialize = materialize or _materialize
        self._dq: "deque[_WindowEntry]" = deque()
        self._cv = threading.Condition()
        self._reaper: Optional[threading.Thread] = None
        self._closed = False
        # background-thread liveness: the reaper beats once per loop —
        # a reaper with parked entries and a stale beat is wedged
        # inside a device sync (named-thread census in filter health)
        self.heartbeat = ThreadBeat(f"{name}-reaper")
        # stats (exact under the cv; perf smoke reads them)
        self.reaped = 0
        self.dispatch_waits = 0
        # park -> pop_ready dwell distribution (always on: one
        # perf_counter per micro-batch pop, off the per-frame path;
        # single-writer — only the dispatch thread pops)
        self.dwell = Log2Histogram()

    def __len__(self) -> int:
        return len(self._dq)

    def park(self, out_b: Sequence[Any], payload: Any,
             seq: Optional[int] = None) -> None:
        with self._cv:
            self._closed = False
            self._dq.append(_WindowEntry(out_b, payload, seq))
            if self._reaper is None or not self._reaper.is_alive():
                self._reaper = threading.Thread(
                    target=self._reap_loop,
                    name=f"{self.name}-reaper", daemon=True,
                )
                self.heartbeat.bind(self._reaper)
                self.heartbeat.beat()
                self._reaper.start()
            self._cv.notify_all()

    def _reap_loop(self) -> None:
        name_os_thread()
        while True:
            self.heartbeat.beat()
            with self._cv:
                entry = None
                while entry is None:
                    if self._closed:
                        return
                    for cand in self._dq:
                        if not cand.claimed:
                            entry = cand
                            break
                    if entry is None:
                        self._cv.wait()
                entry.claimed = True
            # beat AFTER claiming, before the blocking sync: the loop-top
            # beat precedes an unbounded idle wait, so without this a
            # healthy first job after a long idle would show the exact
            # stale-beat-while-busy signature the census calls wedged
            self.heartbeat.beat()
            try:
                # ready-wait and device-to-host of one parked batch
                with span("nns.feed.reap", seq=entry.seq):
                    mats = self._materialize(entry.out_b)
                err = None
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — crosses threads
                mats, err = None, e
            with self._cv:
                entry.mats, entry.error, entry.done = mats, err, True
                entry.out_b = None  # device refs released as soon as synced
                self.reaped += 1
                self._cv.notify_all()

    def pop_ready(self) -> List[Tuple[Optional[List[np.ndarray]], Any]]:
        """(materialized outputs, payload) for every completed entry at
        the FRONT of the window, in order; never blocks.  An errored
        entry at the front raises (after any completed entries ahead of
        it were returned by the previous call)."""
        popped: List[_WindowEntry] = []
        err: Optional[BaseException] = None
        with self._cv:
            while self._dq and self._dq[0].done:
                if self._dq[0].error is not None:
                    if popped:
                        break  # deliver the good prefix first
                    err = self._dq.popleft().error
                    break
                popped.append(self._dq.popleft())
        if err is not None:
            raise err
        if popped:
            now = time.perf_counter()
            for e in popped:
                self.dwell.record(now - e.t_park)
        return [(e.mats, e.payload) for e in popped]

    def oldest_ready(self) -> bool:
        with self._cv:
            return not self._dq or self._dq[0].done

    def wait_oldest(self, timeout: float = 0.1) -> bool:
        """Bounded wait for the oldest entry's completion EVENT (the
        backpressure path for a full window).  True when the front is
        ready (or the window emptied)."""
        with self._cv:
            if self._dq and not self._dq[0].done:
                self.dispatch_waits += 1
            return self._cv.wait_for(
                lambda: not self._dq or self._dq[0].done, timeout=timeout
            )

    def payloads(self) -> List[Any]:
        """Snapshot of parked payloads, oldest first (drain accounting)."""
        with self._cv:
            return [e.payload for e in self._dq]

    def clear(self) -> List[Any]:
        """Discard every parked entry (Flush); returns their payloads."""
        with self._cv:
            dropped = [e.payload for e in self._dq]
            self._dq.clear()
            self._cv.notify_all()
        return dropped

    def close(self) -> None:
        """Drop all entries and stop the reaper thread (element stop)."""
        with self._cv:
            self._dq.clear()
            self._closed = True
            self._cv.notify_all()
            reaper, self._reaper = self._reaper, None
        if reaper is not None and reaper.is_alive():
            reaper.join(timeout=2.0)


class StagedBatch:
    """Handle for one in-flight staging job: the lane thread stacks the
    frames into pooled staging buffers, runs ``to_device`` (which must
    return only once the buffer contents are fully copied/staged — the
    aliasing rule below), releases the buffers back to the pool, and
    publishes the device arrays here.  The dispatch thread collects them
    via :meth:`wait` / :meth:`result`; ``discard()`` drops the result of
    a job whose batch will never be dispatched (Flush/stop)."""

    __slots__ = ("_cv", "_dev", "_err", "_done", "_discarded")

    def __init__(self):
        self._cv = threading.Condition()
        self._dev: Optional[List[Any]] = None
        self._err: Optional[BaseException] = None
        self._done = False
        self._discarded = False

    # -- lane side ----------------------------------------------------------
    def _finish(self, dev, err) -> None:
        with self._cv:
            self._dev = None if self._discarded else dev
            self._err = err
            self._done = True
            self._cv.notify_all()

    # -- dispatch side ------------------------------------------------------
    def wait(self, timeout: Optional[float] = None) -> bool:
        with self._cv:
            return self._cv.wait_for(lambda: self._done, timeout=timeout)

    def result(self) -> List[Any]:
        """The staged device arrays; raises the staging error if any.
        Callers wanting interruptibility poll :meth:`wait` first."""
        with self._cv:
            self._cv.wait_for(lambda: self._done)
            if self._err is not None:
                raise self._err
            return self._dev

    def discard(self) -> None:
        """The job's batch will never be dispatched (Flush/stop): drop
        the device references as soon as they exist."""
        with self._cv:
            self._discarded = True
            self._dev = None


class HostStagingLane:
    """Double-buffered host->device staging on a dedicated lane thread.

    ``submit(per_frame_tensors)`` enqueues one micro-batch: the lane
    thread stacks each tensor index into a pooled staging buffer
    (``np.stack(..., out=buf)`` — no per-batch allocation once warm) and
    calls ``to_device`` (the backend's placement hook) on the stacked
    buffers.  The dispatch thread collects the device arrays one batch
    *later* (the filter's staged double-buffer), so the transfer overlaps
    the previous batch's compute instead of serializing with it.

    Aliasing rule: ``to_device`` must return only once the buffer
    contents have been fully copied/staged off the host arrays (jax-xla
    runs ``device_put`` + ``block_until_ready`` ON THE LANE THREAD — the
    wait is exactly the overlapped transfer).  The lane releases each
    staging buffer back to the pool the moment ``to_device`` returns, so
    steady state reuses the same ring of buffers with zero allocations.
    """

    __slots__ = ("name", "_to_device", "_pool", "_placement", "_q", "_cv",
                 "_worker", "_closed", "staged", "heartbeat")

    def __init__(self, to_device: Callable[[List[np.ndarray]], List[Any]],
                 pool=None, name: str = "lane", placement=None):
        self.name = name
        self._to_device = to_device
        self._pool = pool if pool is not None else DEVICE_POOL
        # placement-domain token (FilterBackend.staging_placement): the
        # pool keys its rings on it so this lane's buffers never recycle
        # into a lane staging for a different device/mesh
        self._placement = placement
        self._q: "deque[Tuple[StagedBatch, List[List[np.ndarray]], Any]]" = (
            deque())
        self._cv = threading.Condition()
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        self.staged = 0  # stats
        # background-thread liveness: the worker beats once per job —
        # a lane with work and a stale beat is wedged inside to_device
        # (named-thread census in filter health)
        self.heartbeat = ThreadBeat(f"{name}-stage")

    def submit(self, per_frame: List[List[np.ndarray]],
               seq: Optional[int] = None) -> StagedBatch:
        """Stage one micro-batch: ``per_frame`` is a list of per-frame
        tensor lists (all host arrays, uniform shapes/dtypes); ``seq``
        is the batch's sequence number, for its stage span."""
        job = StagedBatch()
        with self._cv:
            self._closed = False
            self._q.append((job, per_frame, seq))
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._run, name=f"{self.name}-stage", daemon=True,
                )
                self.heartbeat.bind(self._worker)
                self.heartbeat.beat()
                self._worker.start()
            self._cv.notify_all()
        return job

    def _run(self) -> None:
        name_os_thread()
        while True:
            self.heartbeat.beat()
            with self._cv:
                while not self._q:
                    if self._closed:
                        return
                    self._cv.wait()
                job, per_frame, seq = self._q.popleft()
            # beat after the (possibly long-idle) dequeue — see the
            # reaper's matching comment
            self.heartbeat.beat()
            bufs: List[np.ndarray] = []
            try:
                n = len(per_frame)
                ntensors = len(per_frame[0])
                # one micro-batch's stack and host-to-device placement
                with span("nns.feed.stage", seq=seq, frames=n) as sp:
                    for t in range(ntensors):
                        rows = [pf[t] for pf in per_frame]
                        a0 = np.asarray(rows[0])
                        buf = self._pool.acquire(
                            (n,) + a0.shape, a0.dtype,
                            placement=self._placement)
                        np.stack([np.asarray(r) for r in rows], out=buf)
                        bufs.append(buf)
                    if sp.live:
                        sp.set(bytes=sum(int(b.nbytes) for b in bufs))
                    dev = self._to_device(bufs)
                self.staged += 1
                job._finish(list(dev), None)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — crosses threads
                job._finish(None, e)
            finally:
                # to_device returned (or failed): the staging buffers are
                # no longer readable by anyone — back to the ring
                for b in bufs:
                    self._pool.release(b, placement=self._placement)

    def pending(self) -> int:
        with self._cv:
            return len(self._q)

    def close(self) -> None:
        with self._cv:
            abandoned = [job for job, _, _ in self._q]
            self._q.clear()
            self._closed = True
            self._cv.notify_all()
            worker, self._worker = self._worker, None
        for job in abandoned:
            job._finish(None, RuntimeError("staging lane closed"))
        if worker is not None and worker.is_alive():
            worker.join(timeout=2.0)
