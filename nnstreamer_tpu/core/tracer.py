"""Pipeline-wide tracing: the GstShark-analog observability layer.

The reference delegates pipeline profiling to GStreamer ecosystem tracers —
GstShark's proctime / interlatency / framerate / queuelevel / bitrate
hooks (SURVEY §5.1, ``tools/tracing/README.md`` in the reference) — plus
per-filter latency/throughput props.  Here the same five measurements are
a built-in: the pipeline calls ``frame_in``/``frame_out`` around every
element's processing when a tracer is attached (one ``is not None`` test
per frame when disabled).

Measurements per element:
  * **proctime** — wall time inside the element's handler (µs; avg/p50/p99
    over a bounded ring).
  * **framerate** — logical frames/sec out of the element (micro-batches
    count as their batch size).
  * **interlatency** — source-to-here latency: elements see the wall-clock
    stamp the tracer put on the frame when it left its source.
  * **queuelevel** — mailbox depth sampled at dequeue (backpressure view).
  * **bitrate** — payload bytes/sec through the element.

``report()`` returns plain dicts; ``summary_lines()`` renders the
gst-shark-style table.

**Spans on the profiler's clock** (second half of this module):
:func:`span` is the program's ONE span entry point.  It is armed exactly
while a jax profiler session is live — whoever started it (the filter's
``trace=1``, an operator's TensorBoard capture, the benchmark's trace
window) — and then does two things: it opens a
``jax.profiler.TraceAnnotation`` so the span lands in the profiler's own
trace beside the device's events, and on exit it appends one
:class:`SpanRecord` to a process-global bounded ring that
:func:`spans_between` reads back.  Off, it is one static call and a
shared no-op.  What gets a span: WORK, on the thread that does it — a
thread that merely sleeps on a queue opens none (a trace reducer would
blame device gaps on it); the waits of the thread that feeds the device
are the exception and are named as waits.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import numpy as np

from .telemetry import TRACE_ID_META, Log2Histogram, new_trace_id

META_SRC_TS = "_nns_trace_src_ts"  # wall stamp set when a frame leaves a source
#: a device-resident micro-batch's sequence number, stamped by the filter
#: while a profiler session is live so that the element that brings the
#: batch to the host names its span by it
BATCH_SEQ_META = "_nns_batch_seq"


class _ElementStats:
    __slots__ = (
        "frames", "calls", "proc_ring", "t_first", "t_last",
        "inter_sum", "inter_max", "inter_n", "bytes", "q_sum", "q_max",
        "q_n", "q_cap", "sched_ring", "t_prev_in", "lat_hist",
    )

    def __init__(self) -> None:
        self.frames = 0
        self.calls = 0
        self.proc_ring: deque = deque(maxlen=1024)  # seconds per call
        # full-history fixed-memory handle-latency distribution (the
        # proc ring keeps only the last 1024 calls; percentile EVIDENCE
        # needs every observation) — lock-free: frame_out is
        # single-writer per element by the scheduler's threading model
        self.lat_hist = Log2Histogram()
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.inter_sum = 0.0
        self.inter_max = 0.0
        self.inter_n = 0
        self.bytes = 0
        self.q_sum = 0
        self.q_max = 0
        self.q_n = 0
        self.q_cap = 0
        # scheduletime: gap between consecutive call starts (GstShark's
        # scheduling-jitter view)
        self.sched_ring: deque = deque(maxlen=1024)
        self.t_prev_in: Optional[float] = None


class PipelineTracer:
    """Attach via ``Pipeline(..., tracer=PipelineTracer())`` or
    ``pipeline.enable_tracing()``; read ``report()`` any time (thread-safe,
    including while the pipeline runs).

    A :class:`~.telemetry.FlightRecorder` may ride along (``recorder``
    attr, set by ``Pipeline.enable_flight_recorder``): the scheduler's
    single ``tracer is not None`` branch then also feeds the incident
    ring — the disabled path still costs exactly one branch per frame."""

    def __init__(self, detail: bool = False, recorder=None) -> None:
        self._stats: Dict[str, _ElementStats] = {}
        # mailbox queue-wait distributions (enqueue -> dequeue), one per
        # consuming element; single-writer: each mailbox has exactly one
        # consumer thread
        self._qwait: Dict[str, Log2Histogram] = {}
        self._lock = threading.Lock()
        self.t_started = time.perf_counter()
        # cpuusage: process CPU time vs wall time over the traced window
        self._cpu_started = time.process_time()
        # detail mode additionally records every element call into the
        # process-wide span ring (:func:`record`), so export_chrome_trace
        # renders a real timeline, not just aggregates
        self._detail = detail
        # optional flight recorder (core/telemetry.py)
        self.recorder = recorder

    # -- hot-path hooks (called from element worker threads) ---------------
    def stamp_source(self, frame) -> None:
        """Stamp a frame leaving a source element (interlatency origin);
        with a flight recorder attached, also mint the frame's trace id
        (it propagates through meta copies — and across the query wire,
        see core/telemetry.py)."""
        frame.meta.setdefault(META_SRC_TS, time.perf_counter())
        if self.recorder is not None:
            frame.meta.setdefault(TRACE_ID_META, new_trace_id())

    def frame_begin(self, name: str, frame) -> None:
        """Mark a frame ENTERING an element's handler.  Only meaningful
        with a flight recorder attached (a frame stuck inside a hung
        element is identified by its open span); otherwise a no-op."""
        if self.recorder is not None:
            self.recorder.begin(name, frame)

    def queue_wait(self, name: str, wait_s: float) -> None:
        """One frame's mailbox wait, recorded by the consuming streaming
        thread.  The origin stamp is the producer's handoff ATTEMPT
        (``_push``/``_put_many``), so time spent blocked on a full
        mailbox counts too — backpressure IS queue pressure; p99 here
        can therefore exceed capacity x service time.  On a fan-out pad
        the shared stamp yields ONE observation per frame, attributed to
        whichever consumer dequeues first."""
        h = self._qwait.get(name)
        if h is None:
            with self._lock:
                h = self._qwait.setdefault(name, Log2Histogram())
        h.record(wait_s)

    def queue_level(self, name: str, depth: int, cap: int) -> None:
        st = self._get(name)
        st.q_sum += depth
        st.q_n += 1
        st.q_cap = cap
        if depth > st.q_max:
            st.q_max = depth

    def frame_out(
        self, name: str, t_in: float, t_out: float,
        nframes: int, nbytes: int, src_ts: Optional[float],
        frame=None,
    ) -> None:
        if self._detail:
            record(name, t_in, t_out,
                   request=(frame.meta.get(TRACE_ID_META)
                            if frame is not None else None),
                   frames=nframes)
        if self.recorder is not None:
            self.recorder.end(name, frame, t_in, t_out, nframes)
        st = self._get(name)
        st.calls += 1
        st.frames += nframes
        st.proc_ring.append(t_out - t_in)
        st.lat_hist.record(t_out - t_in)
        if st.t_prev_in is not None:
            st.sched_ring.append(t_in - st.t_prev_in)
        st.t_prev_in = t_in
        if st.t_first is None:
            st.t_first = t_out
        st.t_last = t_out
        st.bytes += nbytes
        if src_ts is not None:
            lat = t_out - src_ts
            st.inter_sum += lat
            st.inter_n += 1
            if lat > st.inter_max:
                st.inter_max = lat

    def _get(self, name: str) -> _ElementStats:
        st = self._stats.get(name)
        if st is None:
            with self._lock:
                st = self._stats.setdefault(name, _ElementStats())
        return st

    # -- reporting ----------------------------------------------------------
    def latency_histograms(self):
        """``[(element, metric_name, Log2Histogram)]`` for the always-on
        log2 instruments: per-element handle latency
        (``nns.element.handle_seconds``) and mailbox queue-wait
        (``nns.element.queue_wait_seconds``).  The telemetry collector
        exports these (buckets + derived p50/p95/p99 gauges) at scrape
        time."""
        with self._lock:
            stats = list(self._stats.items())
            qwait = list(self._qwait.items())
        out = [
            (name, "nns.element.handle_seconds", st.lat_hist)
            for name, st in stats
        ]
        out.extend(
            (name, "nns.element.queue_wait_seconds", h)
            for name, h in qwait
        )
        return out

    def cpu_usage(self) -> float:
        """Process CPU seconds per wall second since tracing began
        (GstShark cpuusage analog; >1.0 = more than one busy core)."""
        wall = time.perf_counter() - self.t_started
        if wall <= 0:
            return 0.0
        return (time.process_time() - self._cpu_started) / wall

    @staticmethod
    def _snap(dq: deque) -> list:
        """Copy a ring that worker threads append to without locks: a
        full ring's append also evicts, which makes a concurrent
        list(deque) raise — retry, then settle for empty."""
        for _ in range(4):
            try:
                return list(dq)
            except RuntimeError:
                continue
        return []

    def report(self) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:  # _get() inserts concurrently from worker threads
            items = list(self._stats.items())
        for name, st in items:
            ring = self._snap(st.proc_ring)
            span = (
                (st.t_last - st.t_first)
                if st.t_first is not None and st.t_last != st.t_first
                else 0.0
            )
            proc = np.asarray(ring) if ring else np.zeros(1)
            sched = self._snap(st.sched_ring)
            out[name] = {
                "frames": st.frames,
                "calls": st.calls,
                "proctime_us_avg": float(proc.mean()) * 1e6,
                "proctime_us_p50": float(np.percentile(proc, 50)) * 1e6,
                "proctime_us_p99": float(np.percentile(proc, 99)) * 1e6,
                "scheduletime_us_avg": (
                    float(np.mean(sched)) * 1e6 if sched else None
                ),
                "framerate_fps": (st.frames / span) if span else 0.0,
                "interlatency_ms_avg": (
                    st.inter_sum / st.inter_n * 1e3 if st.inter_n else None
                ),
                "interlatency_ms_max": (
                    st.inter_max * 1e3 if st.inter_n else None
                ),
                "bitrate_mbps": (st.bytes * 8 / 1e6 / span) if span else 0.0,
                "queuelevel_avg": (st.q_sum / st.q_n) if st.q_n else 0.0,
                "queuelevel_max": st.q_max,
                "queue_capacity": st.q_cap,
            }
        return out

    def summary_lines(self) -> List[str]:
        rows = self.report()
        lines = [
            f"{'element':<20} {'frames':>8} {'fps':>9} {'proc µs':>9} "
            f"{'p99 µs':>9} {'inter ms':>9} {'Mb/s':>8} {'queue':>7}"
        ]
        for name, r in rows.items():
            inter = (
                f"{r['interlatency_ms_avg']:.2f}"
                if r["interlatency_ms_avg"] is not None else "-"
            )
            lines.append(
                f"{name:<20} {r['frames']:>8} {r['framerate_fps']:>9.1f} "
                f"{r['proctime_us_avg']:>9.1f} {r['proctime_us_p99']:>9.1f} "
                f"{inter:>9} {r['bitrate_mbps']:>8.2f} "
                f"{r['queuelevel_avg']:>4.1f}/{r['queue_capacity']}"
            )
        lines.append(f"cpu usage: {self.cpu_usage():.2f} cores")
        return lines


    def export_chrome_trace(self, path: str) -> None:
        """Write a Chrome-trace JSON (``chrome://tracing`` / Perfetto) of
        the host side: the GstShark→tracing-UI hop the reference gets
        from HawkTracer (SURVEY §5.1).  With ``detail=True`` every
        element call since this tracer began is a real timeline span
        (one lane per element), read from the process-wide span ring —
        along with any ``nns.*`` layer span a live profiler session put
        there (one lane per thread); otherwise one summary span per
        element plus fps counters.  To see the host beside the DEVICE,
        use the profiler's own trace (``trace=1`` on the filter): the
        layer spans are written into it on the device's clock."""
        import json

        t0 = self.t_started
        with self._lock:
            names = list(self._stats)
        lanes = {name: i for i, name in enumerate(names)}
        recs = (spans_between(t0, time.perf_counter())
                if self._detail else [])
        spans = []
        for r in recs:
            if r.t0 < t0:
                continue
            # an element's own calls sit in its lane; layer spans in
            # their thread's
            lane = (r.name if r.name in lanes
                    else f"thread {r.thread}" if r.thread else "waits")
            tid = lanes.setdefault(lane, len(lanes))
            args = dict(r.attrs)
            if r.request is not None:
                args["request"] = r.request
            spans.append({
                "name": r.name, "ph": "X", "pid": 0, "tid": tid,
                "ts": (r.t0 - t0) * 1e6,
                "dur": max(0.1, (r.t1 - r.t0) * 1e6),
                "args": args,
            })
        events = [
            {
                "name": "process_name", "ph": "M", "pid": 0,
                "args": {"name": "nnstreamer_tpu pipeline"},
            }
        ] + [
            {
                "name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                "args": {"name": name},
            }
            for name, tid in lanes.items()
        ] + spans
        for name, r in self.report().items():
            if not spans:
                events.append({
                    "name": name, "ph": "X", "pid": 0,
                    "tid": lanes.get(name, 0), "ts": 0,
                    "dur": max(1, int(r["proctime_us_avg"] * r["calls"])),
                    "args": {k: v for k, v in r.items() if v is not None},
                })
            events.append({
                "name": f"{name}/fps", "ph": "C", "pid": 0,
                "ts": 0, "args": {"fps": round(r["framerate_fps"], 1)},
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)


def frame_nbytes(item) -> int:
    """Payload size of a frame (host or device tensors)."""
    try:
        return sum(int(getattr(t, "nbytes", 0)) for t in item.tensors)
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# Spans on the profiler's clock: one entry point, one ring
# ---------------------------------------------------------------------------
class SpanRecord(NamedTuple):
    """One closed span.  Times are ``time.perf_counter()`` seconds;
    ``thread`` is the name of the thread the span ran on, and ``parent``
    the name of the span open on that thread when this one opened (both
    None for an interval that crossed threads, :func:`record`);
    ``request`` is what the spans of one request share (a frame's
    ``TRACE_ID_META``, a generation stream's ``sid``)."""

    name: str
    t0: float
    t1: float
    thread: Optional[str]
    parent: Optional[str]
    request: Any
    attrs: Dict[str, Any]


#: bound on the process-global ring (oldest records fall off)
SPAN_RING = 200_000
_ring: "deque[SpanRecord]" = deque(maxlen=SPAN_RING)
_tls = threading.local()
_bind_lock = threading.Lock()
# TraceAnnotation and its static is_enabled, bound at the first span()
# (this module imports without jax)
_annotation = None
_enabled: Optional[Callable[[], bool]] = None
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _bind() -> Callable[[], bool]:
    global _annotation, _enabled
    with _bind_lock:
        if _enabled is None:
            try:
                import jax.monitoring
                import jax.profiler

                _annotation = jax.profiler.TraceAnnotation
                _annotation.is_enabled()
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile)
                _enabled = _annotation.is_enabled
            except Exception:  # noqa: BLE001 — no jax, or one without it
                _enabled = bool  # bool() is False: never armed
    return _enabled


def armed() -> bool:
    """True exactly while a jax profiler session is live in this
    process, whoever started it.  One static call."""
    return (_enabled or _bind())()


class _NoSpan:
    """What :func:`span` returns while no profiler session is live."""

    __slots__ = ()
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NO_SPAN = _NoSpan()


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        name_os_thread()
        return _tls.stack


def name_os_thread() -> None:
    """Give the calling thread an OS-level name of its own.  The profiler
    names a host thread's line in the trace by the thread's OS name as
    it stands at the thread's first event of the session, and Python
    (before 3.14) leaves every thread with the process's: a trace then
    shows a dozen lines all called "python3", and a reader that keys the
    lines by name keeps one of them.  The threads that feed the device
    (segment workers, the staging lane, the reaper, the slot pump) call
    this as they start; any other thread gets it at its first span.  The
    name is the tail of the Python name (where the role is: ``-slots``,
    ``-stage``, ``-reaper``) and the native id, within the kernel's 15
    bytes.  The main thread keeps its name: it is the process's."""
    t = threading.current_thread()
    if t is threading.main_thread() or getattr(_tls, "named", False):
        return
    _tls.named = True
    name = f"{t.name[-9:]}-{threading.get_native_id() % 100000}"
    try:
        import ctypes

        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):  # no prctl here (not Linux)
        pass


class _Span:
    __slots__ = ("name", "request", "attrs", "parent", "t0", "_ann")
    live = True

    def __init__(self, name, request, attrs):
        self.name, self.request, self.attrs = name, request, attrs

    def set(self, request=None, **attrs) -> None:
        """Attributes (and the request id) learned inside the span."""
        if request is not None:
            self.request = request
        self.attrs.update(attrs)

    def __enter__(self):
        stack = _stack()
        top = stack[-1] if stack else None
        self.parent = top.name if top is not None else None
        if self.request is None and top is not None:
            self.request = top.request
        stack.append(self)
        # the name alone: attributes stay in the ring, so the name the
        # trace (and a ledger's breakdown) shows is a stable string
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _ring.append(SpanRecord(
            self.name, self.t0, t1, threading.current_thread().name,
            self.parent, self.request, self.attrs))
        return False


def span(name: str, request=None, **attrs):
    """Context manager around one piece of WORK at a layer boundary.

    No profiler session live: one static call, the shared
    :data:`NO_SPAN`, nothing allocated and no clock read (per-frame
    sites pass no keywords and use ``sp.set(...)`` under ``sp.live``).
    Live: a ``TraceAnnotation(name)`` on the calling thread, and at exit
    one :class:`SpanRecord` in the ring.  A span without a ``request``
    inherits its parent's."""
    if not (_enabled or _bind())():
        return NO_SPAN
    return _Span(name, request, attrs)


def note(**attrs) -> None:
    """Attributes for the span the calling thread has open, from a callee
    that knows what its caller's span should say (the backend's compile
    bucket on the filter's invoke span).  No span open: nothing."""
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1].set(**attrs)


def record(name: str, t0: float, t1: float, request=None, **attrs) -> None:
    """The ring record alone, for an interval that belongs to no thread's
    call stack and is only known at its end: a request's wait across
    threads, a thread asleep on back-pressure.  No annotation, no thread
    and no parent, and no test of :func:`armed` — per-request callers
    make it themselves."""
    _ring.append(SpanRecord(name, t0, t1, None, None, request, attrs))


def _on_compile(event: str, duration: float, **_) -> None:
    """Compiles by cause: jax calls this on the compiling thread, so the
    record's parent is the span that thread has open."""
    if event == _COMPILE_EVENT and _enabled():
        now = time.perf_counter()
        stack = _stack()
        top = stack[-1] if stack else None
        _ring.append(SpanRecord(
            "nns.compile", now - float(duration), now,
            threading.current_thread().name,
            top.name if top is not None else None,
            top.request if top is not None else None, {}))


def spans_between(t0: float, t1: float) -> List[SpanRecord]:
    """A copy of the ring's records that END in ``[t0, t1]``.  The ring
    is process-global and outlives any pipeline."""
    return [r for r in PipelineTracer._snap(_ring) if t0 <= r.t1 <= t1]


#: trace_offset_ns: how many of a trace line's first span events are
#: matched, and how near (ns) an event must lie to where an offset puts a
#: ring span of its name for the two to be one span
_OFFSET_EVENTS = 64
_OFFSET_TOL_NS = 50_000.0


def trace_offset_ns(host_lines) -> Optional[float]:
    """What to add to a ring instant (``perf_counter`` seconds x 1e9) to
    place it on a profiler trace's clock, so that a ring-only record (a
    :func:`record`, which has no annotation) can be laid beside the
    device's events.  ``host_lines``: a trace's host threads as
    ``{line name: [(event name, start_ns, end_ns), ...]}`` (what
    ``benchmark/xplane.py::read`` gives under ``"host"``).

    A span is in both places.  A line's first span event is tried
    against every ring span of its name; each try is an offset, and it
    is kept where the most of the line's first ``_OFFSET_EVENTS`` span
    events find a span of their name on the SAME ring thread within
    ``_OFFSET_TOL_NS`` of where the offset puts them (name and order on
    one thread's line).
    The result is the median, over all lines' matched pairs, of the
    event's start minus the span's ``t0``; None where nothing
    matches."""
    starts: Dict[Any, List[float]] = {}
    for r in PipelineTracer._snap(_ring):
        if r.thread is not None:  # a _Span: it opened an annotation
            starts.setdefault((r.thread, r.name), []).append(r.t0 * 1e9)
    for ts in starts.values():
        ts.sort()

    def near(thread, name, t):
        ts = starts.get((thread, name))
        if not ts:
            return None
        i = bisect.bisect_left(ts, t)
        best = min(ts[max(0, i - 1):i + 1], key=lambda u: abs(u - t))
        return best if abs(best - t) <= _OFFSET_TOL_NS else None

    names = {n for _, n in starts}
    diffs: List[float] = []
    for events in host_lines.values():
        evs = sorted((s, n) for n, s, _ in events
                     if n in names)[:_OFFSET_EVENTS]
        if not evs:
            continue
        s0, n0 = evs[0]
        best: List[float] = []
        for (thread, name), ts in starts.items():
            if name != n0:
                continue
            for t in ts:
                off = s0 - t
                if len(evs) > 1 and near(thread, evs[1][1],
                                         evs[1][0] - off) is None:
                    continue
                got = [s - m for s, n in evs
                       for m in (near(thread, n, s - off),) if m is not None]
                if len(got) > len(best):
                    best = got
        diffs.extend(best)
    if not diffs:
        return None
    return float(np.median(diffs))
