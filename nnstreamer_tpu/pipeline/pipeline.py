"""Pipeline scheduler: fused streaming threads with bounded queues.

Reference analog: GStreamer's execution model (L0 in SURVEY.md) — elements
run on streaming threads connected by pads, and a linear chain SHARES one
streaming thread unless an explicit ``queue`` element inserts a thread
boundary.  The scheduler fuses each maximal linear chain into one worker
(eliding the per-frame mailbox handoffs entirely — the per-buffer-overhead
bottleneck the NNStreamer papers attack with shared streaming threads);
branches, muxes, micro-batching elements, and explicit ``queue``s keep
their own threads and bounded mailboxes, so pipeline parallelism remains
available exactly where it pays, and a full mailbox blocks the upstream
thread — the backpressure analog.  ``Pipeline(fuse=False)`` (or
``NNS_FUSE=0``) restores the one-thread-per-element seed model.

Lifecycle ≙ NULL→PLAYING: ``start()`` negotiates schemas (CapsEvents flow
before data), spawns workers; ``stop()`` tears down; ``wait()`` joins until
EOS has reached every sink (≙ bus EOS message), re-raising element errors.

The bus carries out-of-band messages (errors, element custom messages like
training stats) to the application (≙ GstBus).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core.buffer import (
    EOS,
    FRAME_POOL,
    BatchFrame,
    CapsEvent,
    Event,
    Flush,
    TensorFrame,
)
from ..core.liveness import DEADLINE_META, StallError, Watchdog, stamp_deadline
from ..core.log import get_logger
from ..core.resilience import FAULTS
from ..core.telemetry import TL_QPUT_META
from ..core.tracer import (
    META_SRC_TS, PipelineTracer, armed, frame_nbytes, name_os_thread,
    record,
)
from .element import WAKE, Element, ElementError, SinkElement, SourceElement

_STOP = object()  # out-of-band worker shutdown sentinel


class _LeakyMailbox:
    """Bounded mailbox with GstQueue leaky semantics, all decisions taken
    atomically under one lock: a frame arriving at a full box either
    replaces the oldest queued FRAME (``downstream`` — events keep their
    exact position) or is itself discarded (``upstream``).  Events go
    through ``put``, which requires a bounded timeout (there is no
    stop-flag escape here); callers retry in a loop so events are never
    dropped or reordered."""

    def __init__(self, maxsize: int, policy: str):
        import collections

        self._dq = collections.deque()
        self._max = max(1, maxsize)
        self.policy = policy  # "upstream" | "downstream"
        self._mtx = threading.Lock()
        self._not_empty = threading.Condition(self._mtx)
        self._not_full = threading.Condition(self._mtx)

    def _put_frame_locked(self, item) -> None:
        """Leaky policy for ONE frame entry; caller holds the lock.  A
        frame arriving at a full box either evicts the oldest queued
        FRAME (``downstream`` — events keep their exact position) or is
        itself the loss (``upstream``); either way the frame is
        'consumed' without blocking."""
        if len(self._dq) >= self._max:
            if self.policy == "upstream":
                return  # live semantics: lose the newest frame
            # downstream: drop the oldest FRAME in place; if only
            # events are queued, the incoming frame is the loss
            for i, old in enumerate(self._dq):
                if isinstance(old[1], TensorFrame):
                    del self._dq[i]
                    break
            else:
                return
        self._dq.append(item)

    def put_frame(self, item) -> None:
        """Non-blocking frame delivery with the leaky policy."""
        with self._mtx:
            self._put_frame_locked(item)
            self._not_empty.notify()

    # -- queue.Queue-compatible subset (events, sentinel, worker get) ----
    def put(self, item, timeout: Optional[float] = None) -> None:
        # no stop-flag escape exists here, so an unbounded block on a full
        # box could hang shutdown; Pipeline._push loops with bounded waits
        if timeout is None:
            raise ValueError("_LeakyMailbox.put requires a bounded timeout")
        with self._mtx:
            if len(self._dq) >= self._max:
                self._not_full.wait_for(
                    lambda: len(self._dq) < self._max, timeout=timeout
                )
                if len(self._dq) >= self._max:
                    raise queue.Full
            self._dq.append(item)
            self._not_empty.notify()

    def put_nowait(self, item) -> None:
        self.put(item, timeout=0.0)

    def put_many(self, items, timeout: float = 0.0) -> int:
        """Block handoff: deliver a RUN of ``(pad, item)`` entries under ONE
        lock acquisition, applying the leaky policy per frame.  Frames never
        block (drop semantics); the run stops at the first EVENT that does
        not fit (events must block — the caller retries the remainder).
        Returns the number of leading items consumed."""
        n = 0
        with self._mtx:
            for entry in items:
                if isinstance(entry[1], TensorFrame):
                    self._put_frame_locked(entry)  # never blocks: drop policy
                    n += 1
                    continue
                # event: only append when space exists; otherwise stop the
                # run — the caller falls back to the blocking put loop
                if len(self._dq) >= self._max:
                    break
                self._dq.append(entry)
                n += 1
            if n:
                self._not_empty.notify()
        return n

    def get(self, timeout: Optional[float] = None):
        with self._mtx:
            if not self._dq:
                self._not_empty.wait_for(
                    lambda: bool(self._dq), timeout=timeout
                )
                if not self._dq:
                    raise queue.Empty
            item = self._dq.popleft()
            self._not_full.notify()
            return item

    def get_nowait(self):
        return self.get(timeout=0.0)

    def qsize(self) -> int:
        with self._mtx:
            return len(self._dq)

    @property
    def maxsize(self) -> int:
        return self._max


@dataclass
class BusMessage:
    """Out-of-band message to the application (≙ GstMessage)."""

    kind: str  # "error" | "eos" | "element" | "warning" | "health"
    source: str
    data: Any = None


@dataclass
class ElementHealth:
    """Supervision record for one element (see ``Pipeline.health()``).

    ``dead_letters`` counts every frame dropped under the ``skip``
    policy for the element's lifetime; ``dlq`` retains only the most
    recent ``dead-letter-max`` of them as ``(frame, error_repr)`` pairs
    for post-mortem inspection."""

    state: str = "idle"  # idle|running|restarting|degraded|failed|finished|stalled
    restarts: int = 0  # within the current restart-window (gates the budget)
    restarts_total: int = 0  # lifetime, for health reporting
    last_restart_ts: float = 0.0
    dead_letters: int = 0
    deadline_drops: int = 0  # frames expired before this element processed them
    last_error: str = ""
    dlq: deque = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.dlq is None:
            self.dlq = deque(maxlen=16)


class _ElemState:
    """Per-element dispatch state inside one streaming-thread worker.

    Exists for every element (fused or solo) so the dispatch loop touches
    precomputed locals instead of re-deriving graph facts per frame — part
    of the hot-path allocation diet."""

    __slots__ = (
        "el", "connected", "eos_pads", "caps_pads", "finished",
        "next_state", "next_pad", "out_pad", "watch",
        "terminal", "delivered", "in_call",
    )

    def __init__(self, el: Element):
        self.el = el
        self.connected: set = {0}
        self.eos_pads: set = set()
        self.caps_pads: set = set()
        self.finished = False
        # drain accounting: terminal elements (stream endpoints) count
        # the logical frames they consume — one int add per frame, only
        # at endpoints, single-writer per streaming thread (summed by
        # Pipeline.delivered_frames)
        self.terminal = False
        self.delivered = 0
        # logical frames consumed from a queue but not yet fully routed
        # (exact dropped accounting for a halt that lands mid-call)
        self.in_call = 0
        # in-segment routing: the fused downstream element (None = outputs
        # leave through mailboxes), the src pad carrying that link, and the
        # downstream sink pad it lands on
        self.next_state: Optional["_ElemState"] = None
        self.next_pad = 0
        self.out_pad = 0
        self.watch = None  # liveness watch, bound at worker start


class _Seg:
    """One streaming thread: a maximal fusable linear chain of elements.

    ``chain[0]`` is the head (a source, or the one element with a mailbox);
    every later element receives its input inline on the head's thread —
    GStreamer semantics: elements share a streaming thread unless an
    explicit ``queue`` boundary is inserted."""

    __slots__ = ("chain", "states", "stash")

    def __init__(self, chain: List[Element]):
        self.chain = chain
        self.states: Dict[str, _ElemState] = {}
        # items popped from the head mailbox but not yet processed (bulk
        # pops past a batch boundary); lives on the segment so halt-time
        # accounting (_count_abandoned) can see it
        self.stash: deque = deque()


def _env_fuse() -> bool:
    return os.environ.get("NNS_FUSE", "1").lower() not in ("0", "false", "no")


class Pipeline:
    """A running graph of elements."""

    def __init__(
        self,
        name: str = "pipeline",
        default_queue_size: int = 16,
        tracer=None,
        fuse: Optional[bool] = None,
    ):
        self.name = name
        self.log = get_logger(name)
        self.elements: Dict[str, Element] = {}
        self.default_queue_size = default_queue_size
        self._threads: List[threading.Thread] = []
        self._stop_flag = threading.Event()
        # graceful drain (core/lifecycle.py "Zero-downtime operations"):
        # set by drain() — sources stop producing and flush EOS so every
        # in-flight frame reaches the sinks before teardown
        self._drain_flag = threading.Event()
        self._started = False
        self.errors: List[BaseException] = []
        self._bus: "queue.Queue[BusMessage]" = queue.Queue()
        self._bus_watchers: List[Callable[[BusMessage], None]] = []
        self._sinks_done = threading.Event()
        self._pending_sinks = 0
        self._sink_lock = threading.Lock()
        # supervision: per-element health records (error-policy support)
        self.health_map: Dict[str, ElementHealth] = {}
        # liveness (core/liveness.py): built at start() iff any element
        # arms stall-timeout/frame-deadline; the sweeper thread polls it
        self._watchdog: Optional[Watchdog] = None
        self._watches: Dict[str, Any] = {}
        self._wd_thread: Optional[threading.Thread] = None
        self._upstream: Dict[str, List[Element]] = {}  # QoS feedback routing
        self._qos_warn_ts: Dict[str, float] = {}  # per-element warn throttle
        # GstShark-analog tracing (core/tracer.py): None = zero-overhead off
        self.tracer = tracer
        # fleet telemetry (core/telemetry.py): the registry collector is
        # registered at start() and the exposition endpoint is opened by
        # serve_metrics() / NNS_METRICS_PORT; the flight recorder rides
        # the tracer so the disabled hot path stays one branch per frame
        self._recorder = None
        self._metrics_server = None
        self._collector_registered = False
        # memory-pressure watermark monitor (core/liveness.py): polled
        # on the watchdog-sweeper cadence; None = zero cost everywhere
        self._mem_monitor = None
        # generic sweeper hooks (fn, min_poll_s): slow-cadence pollers
        # elements register at start() (the serversrc's telemetry-digest
        # publisher) — called from the watchdog sweeper thread, NEVER on
        # a per-frame path; hooks rate-limit internally
        self._sweep_hooks: List[Tuple[Callable[[], Any], float]] = []
        # registry label: claimed lazily (names default to "pipeline", so
        # the label must be unique among LIVE pipelines or one stop()
        # would evict a concurrent namesake's instruments)
        self._telemetry_label: Optional[str] = None
        # streaming-thread fusion (GStreamer semantics): linear chains share
        # one worker unless a boundary (queue / batcher / branch) intervenes
        self._fuse = _env_fuse() if fuse is None else bool(fuse)
        self._segments: List[_Seg] = []
        self._seg_of: Dict[str, _Seg] = {}

    def to_dot(self) -> str:
        """Graphviz DOT of the element graph (≙ GStreamer's
        GST_DEBUG_DUMP_DOT_DIR pipeline dumps): one node per element
        (shape by role), one edge per pad link, negotiated schemas as
        edge labels when known."""
        def esc(s: str) -> str:  # DOT quoted strings: no raw double quotes
            return str(s).replace('"', "'")

        lines = [
            "digraph pipeline {",
            "  rankdir=LR;",
            "  node [fontsize=10 shape=box style=rounded];",
        ]
        for el in self.elements.values():
            kind = type(el).__name__
            shape = (
                "invhouse" if isinstance(el, SourceElement)
                else "house" if isinstance(el, SinkElement)
                else "box"
            )
            lines.append(
                f'  "{esc(el.name)}" '
                f'[label="{esc(el.name)}\\n({kind})" shape={shape}];'
            )
        for el in self.elements.values():
            for sp_i, sp in enumerate(el.srcpads):
                for dst, sink_pad in sp.links:
                    spec = dst.sink_specs.get(sink_pad)
                    label = (
                        esc(spec.to_string())
                        if spec is not None and getattr(spec, "tensors", None)
                        else ""
                    )
                    lines.append(
                        f'  "{esc(el.name)}" -> "{esc(dst.name)}" '
                        f'[taillabel="{sp_i}" headlabel="{sink_pad}" '
                        f'label="{label}" fontsize=8];'
                    )
        lines.append("}")
        return "\n".join(lines)

    def enable_tracing(self, detail: bool = False) -> PipelineTracer:
        """Attach a fresh PipelineTracer (before start()); returns it.
        ``detail=True`` also records per-call spans for
        ``export_chrome_trace``."""
        recorder = self.tracer.recorder if self.tracer is not None else None
        self.tracer = PipelineTracer(detail=detail, recorder=recorder)
        return self.tracer

    # -- fleet telemetry (core/telemetry.py) ---------------------------------
    def enable_flight_recorder(self, capacity: int = 4096,
                               dump_dir: Optional[str] = None,
                               min_dump_interval_s: float = 5.0,
                               profile_incidents: bool = True,
                               profile_duration_s: float = 0.2):
        """Attach a flight recorder: a bounded ring of recent per-frame
        span timelines, dumped automatically (rate-limited, to log + a
        JSON file) on watchdog stall, dead-letter, swap rollback, or
        breaker trip.  Rides the tracer (one is attached if absent), so
        pipelines without it keep the one-branch-per-frame disabled
        path.  ``profile_incidents`` (default on) additionally attaches
        an incident-time thread profile — collapsed top-stacks of the
        named framework threads over a ``profile_duration_s`` sampling
        window — to every dump (core/profiler.py).  Returns the
        recorder."""
        from ..core.telemetry import FlightRecorder

        if self.tracer is None:
            self.enable_tracing()
        self._recorder = FlightRecorder(
            capacity=capacity, dump_dir=dump_dir,
            min_dump_interval_s=min_dump_interval_s,
            profile_incidents=profile_incidents,
            profile_duration_s=profile_duration_s,
        )
        self.tracer.recorder = self._recorder
        return self._recorder

    @property
    def flight_recorder(self):
        return self._recorder

    # -- memory-pressure watermarks (core/liveness.py) -----------------------
    def enable_memory_monitor(self, high: float = 0.90, low: float = 0.75,
                              sustain_s: float = 2.0,
                              host_limit_bytes: int = 0,
                              sample=None, clock=None,
                              min_poll_s: float = 0.25):
        """Attach a :class:`~..core.liveness.MemoryPressureMonitor`:
        device HBM (and host RSS) watermarks polled on the watchdog-
        sweeper cadence — NEVER on a per-frame path.  Crossing the high
        watermark trims the process frame/staging pools and every
        owned filter backend's compiled-program cache; pressure
        sustained for ``sustain_s`` fires a rate-limited
        ``memory_pressure`` flight-recorder incident (with the
        incident-time thread profiler attached when the recorder has
        one); a query serversrc on this pipeline couples the monitor
        into admission, shedding BUSY *before* the chip OOMs.  Returns
        the monitor (``sample``/``clock`` injectable for tests)."""
        from ..core.buffer import DEVICE_POOL, FRAME_POOL
        from ..core.liveness import MemoryPressureMonitor

        def trim_prefixes() -> int:
            # cold shared-prefix entries are the cheapest HBM to give
            # back (refcounted pages under live readers are never
            # touched) — so they go FIRST on the trim ladder, before
            # frame/staging pools and compiled-program caches.
            freed = 0
            for el in self.elements.values():
                trim = getattr(el, "trim_prefix_cache", None)
                if trim is not None:
                    try:
                        freed += int(trim() or 0)
                    except Exception:
                        self.log.exception(
                            "trim_prefix_cache failed for %s", el.name)
            return freed

        def trim_backends() -> int:
            freed = 0
            for el in self.elements.values():
                be = getattr(el, "backend", None)
                trim = getattr(be, "trim_caches", None)
                if trim is not None:
                    try:
                        freed += int(trim() or 0)
                    except Exception:
                        self.log.exception(
                            "trim_caches failed for %s", el.name)
            return freed

        kwargs = {}
        if sample is not None:
            kwargs["sample"] = sample
        if clock is not None:
            kwargs["clock"] = clock
        mon = MemoryPressureMonitor(
            high=high, low=low, sustain_s=sustain_s,
            min_poll_s=min_poll_s, host_limit_bytes=host_limit_bytes,
            on_pressure=lambda snap: self.incident(
                "memory_pressure", self.name, snap),
            trim_hooks=(trim_prefixes, FRAME_POOL.trim, DEVICE_POOL.trim,
                        trim_backends),
            **kwargs,
        )
        self._mem_monitor = mon
        if self._started and (self._wd_thread is None
                              or not self._wd_thread.is_alive()):
            # armed mid-run with no sweeper: start one for the monitor
            self._wd_thread = threading.Thread(
                target=self._watchdog_loop, args=(mon.min_poll_s,),
                name=f"{self.name}-watchdog", daemon=True,
            )
            self._wd_thread.start()
        return mon

    @property
    def memory_monitor(self):
        return self._mem_monitor

    # -- degraded-capacity feedback (device loss) ----------------------------
    def degraded_feedback(self, source: str, detail: str = "") -> None:
        """An element of THIS pipeline lost a device and re-sharded onto
        survivors: tell every element exposing ``note_degraded`` (the
        query serversrc) so the discovery plane announces
        ``degraded:true`` and fleet routing deprioritizes this server
        ahead of its next failure.  Also posted on the bus."""
        self.post(BusMessage("warning", source, {"degraded": detail}))
        for el in self.elements.values():
            note = getattr(el, "note_degraded", None)
            if note is None:
                continue
            try:
                note(detail)
            except Exception:
                self.log.exception("note_degraded failed for %s", el.name)

    def incident(self, kind: str, source: str, detail: Any = None
                 ) -> Optional[str]:
        """Incident hook (watchdog stall / dead-letter / swap rollback /
        breaker trip land here): dump the flight recorder, post the dump
        path on the bus.  No-op without a recorder; rate-limited by the
        recorder itself.  Returns the dump path, if one was written."""
        rec = self._recorder
        if rec is None:
            return None
        path = rec.dump(kind, source, detail, logger=self.log)
        if path is not None:
            self.post(BusMessage("warning", source, {
                "incident": kind, "flight_dump": path,
            }))
        return path

    @property
    def telemetry_label(self) -> str:
        """The ``pipeline=`` label this pipeline's registry series carry:
        the name when it is unique among live pipelines, else
        ``name#N``.  Claimed at start(), released at stop(); a pipeline
        that is not running reads as its bare name WITHOUT claiming — a
        scrape must never be the claimant (a registry scrape racing
        stop(), or walking the collector of a pipeline a sloppy caller
        abandoned, would otherwise hold the label forever)."""
        return self._telemetry_label or self.name

    def metrics_snapshot(self):
        """Pollable telemetry snapshot of THIS pipeline: every signal
        source under its stable dotted name (see
        Documentation/observability.md).  Cheap enough to poll."""
        from ..core.telemetry import (
            REGISTRY,
            TelemetrySnapshot,
            collect_pipeline,
        )

        return TelemetrySnapshot(
            collect_pipeline(self)
            + REGISTRY.collect_labeled(pipeline=self.telemetry_label)
        )

    def telemetry_summary(self) -> Dict[str, float]:
        """Compact {metric_name: value} dump of this pipeline's labeled
        snapshot (counters summed across elements, gauges maxed)."""
        return self.metrics_snapshot().flat()

    def serve_metrics(self, port: int = 0, host: str = "127.0.0.1") -> int:
        """Open the Prometheus text exposition endpoint (process-wide
        registry — every running pipeline's series, labeled).  Returns
        the bound port; ``stop()`` shuts the endpoint down.  Also armed
        by ``NNS_METRICS_PORT`` at start()."""
        from ..core.telemetry import MetricsServer

        if self._metrics_server is not None:
            return self._metrics_server.port
        self._metrics_server = MetricsServer(
            port=port, host=host, name=self.name)
        return self._metrics_server.port

    @property
    def metrics_port(self) -> Optional[int]:
        srv = self._metrics_server
        return srv.port if srv is not None else None

    def _register_telemetry(self) -> None:
        from ..core.telemetry import REGISTRY, collect_pipeline

        if not self._collector_registered:
            self._collector = lambda: collect_pipeline(self)
            REGISTRY.register_collector(self._collector)
            self._collector_registered = True
        env_port = os.environ.get("NNS_METRICS_PORT", "")
        if env_port and self._metrics_server is None:
            try:
                self.serve_metrics(int(env_port))
            except (OSError, ValueError) as e:
                # another pipeline already owns the port (its endpoint
                # serves the shared registry, so nothing is lost)
                self.log.info(
                    "NNS_METRICS_PORT=%s not bound by this pipeline: %s",
                    env_port, e)

    def _unregister_telemetry(self) -> None:
        from ..core.telemetry import REGISTRY, release_pipeline_label

        if self._collector_registered:
            REGISTRY.unregister_collector(self._collector)
            self._collector_registered = False
        if self._telemetry_label is not None:
            REGISTRY.remove_labeled(pipeline=self._telemetry_label)
            release_pipeline_label(self._telemetry_label)
            self._telemetry_label = None
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None

    # -- construction -------------------------------------------------------
    def add(self, *elements: Element) -> Element:
        for el in elements:
            if el.name in self.elements and self.elements[el.name] is not el:
                raise ElementError(f"duplicate element name {el.name!r}")
            self.elements[el.name] = el
            el._pipeline = self
        return elements[-1]

    def chain(self, *elements: Element) -> Element:
        """add + link a linear chain; returns the last element."""
        self.add(*elements)
        for a, b in zip(elements, elements[1:]):
            a.link(b)
        return elements[-1]

    def __getitem__(self, name: str) -> Element:
        return self.elements[name]

    # -- bus ----------------------------------------------------------------
    def post(self, msg: BusMessage) -> None:
        self._bus.put(msg)
        for cb in list(self._bus_watchers):
            try:
                cb(msg)
            except Exception:  # watcher bugs must not kill workers
                self.log.exception("bus watcher failed")

    def add_bus_watcher(self, cb: Callable[[BusMessage], None]) -> None:
        self._bus_watchers.append(cb)

    def pop_message(self, timeout: Optional[float] = 0) -> Optional[BusMessage]:
        try:
            return self._bus.get(timeout=timeout) if timeout else self._bus.get_nowait()
        except queue.Empty:
            return None

    # -- schema negotiation (static pass, ≙ initial caps negotiation) -------
    def _negotiate(self) -> None:
        """Propagate output schemas topologically and let each element
        validate via accept_spec.  Dynamic/renegotiation still happens via
        in-band CapsEvents at runtime; this pass fails fast at start()."""
        in_degree: Dict[str, int] = {n: 0 for n in self.elements}
        for el in self.elements.values():
            for pad in el.srcpads:
                for dst, _ in pad.links:
                    in_degree[dst.name] += 1
        ready = [self.elements[n] for n, d in in_degree.items() if d == 0]
        seen = 0
        while ready:
            el = ready.pop()
            seen += 1
            if isinstance(el, SourceElement):
                for pad in el.srcpads:
                    pad.spec = el.output_spec()
            else:
                for i, pad in enumerate(el.srcpads):
                    pad.spec = el.derive_spec(i)
            for pad in el.srcpads:
                for dst, sink_pad in pad.links:
                    if pad.spec is not None:
                        dst.set_sink_spec(sink_pad, pad.spec)
                    in_degree[dst.name] -= 1
                    if in_degree[dst.name] == 0:
                        ready.append(dst)
        if seen != len(self.elements):
            # cycles are legal only through repo src/sink (out-of-band), which
            # do not create graph edges — anything else is a bug.
            raise ElementError("pipeline graph has a cycle through pad links")

    # -- device fusion pass (no reference analog; SURVEY §7 design stance:
    # "compile element graphs down to as few XLA programs as possible") ----
    def _fuse_device_chains(self) -> None:
        """Fold fusable decoder device halves into their upstream jax-xla
        filter's compiled program and switch the pair to device-resident
        batch-through flow.

        Conditions (all checked, else the chain runs unfused):
        the filter owns its backend and has no output-combination/dynamic
        output; its single src pad feeds exactly one tensor_decoder whose
        subplugin exposes a device half (``device_fn``/``decode_fused``)
        and whose only input is this filter.  Runs after element start()
        (subplugins exist) and before negotiation (fused schemas
        propagate).
        """
        incoming: Dict[str, int] = {n: 0 for n in self.elements}
        for el in self.elements.values():
            for pad in el.srcpads:
                for dst, _ in pad.links:
                    incoming[dst.name] += 1
        for el in self.elements.values():
            if not getattr(el, "can_fuse_postprocess", False):
                continue
            if len(el.srcpads) != 1 or len(el.srcpads[0].links) != 1:
                continue
            dst, _ = el.srcpads[0].links[0]
            if not getattr(dst, "can_fuse_device", False):
                continue
            if incoming[dst.name] != 1:
                continue
            el.fuse_device_postprocess(dst._dec.device_fn)
            dst.enable_fused()
            if el.preferred_batch > 1:
                el._auto_batch_through = True
            self.log.info(
                "device-fused %s -> %s (decoder half compiled into the "
                "filter's XLA program)", el.name, dst.name,
            )

    # -- streaming-thread fusion pass (≙ GStreamer: elements share a
    # streaming thread unless an explicit queue boundary is inserted) ------
    def _compute_segments(self) -> List[_Seg]:
        """Partition the element graph into streaming threads: each maximal
        fusable linear chain becomes ONE worker (intermediate mailboxes are
        elided entirely).  An edge up->down fuses iff:

        * fusion is enabled (``fuse=``/``NNS_FUSE``),
        * ``up``'s ONLY outgoing link is to ``down`` and ``down``'s only
          input is ``up`` (branches/tees/muxes keep thread boundaries),
        * ``down`` does not declare ``THREAD_BOUNDARY`` (``queue``, the
          query client — elements whose semantics need a private mailbox;
          they still drive their own fused downstream, GStreamer-style),
        * ``up`` does not declare ``FUSE_DOWNSTREAM = False``
          (``tensor_query_serversrc`` — admission control needs the
          pipeline parallelism below it),
        * ``down`` has no leaky policy (leaky drop decisions need a queue),
        * neither side micro-batches (``preferred_batch > 1`` needs a
          mailbox to drain batches from, and its downstream boundary is
          what overlaps invoke with decode).

        Runs after element start() (``preferred_batch`` needs live
        backends) and after negotiation."""
        incoming: Dict[str, int] = {n: 0 for n in self.elements}
        for el in self.elements.values():
            for pad in el.srcpads:
                for dst, _ in pad.links:
                    incoming[dst.name] += 1

        def total_out(el: Element) -> int:
            return sum(len(p.links) for p in el.srcpads)

        def fusable(up: Element, down: Element) -> bool:
            if not self._fuse or isinstance(down, SourceElement):
                return False
            if total_out(up) != 1 or incoming[down.name] != 1:
                return False
            if getattr(down, "THREAD_BOUNDARY", False):
                return False  # down keeps its own mailbox/thread (queue…)
            if not getattr(up, "FUSE_DOWNSTREAM", True):
                return False  # up's downstream parallelism is load-bearing
            if getattr(down, "leaky_policy", ""):
                return False
            if getattr(up, "preferred_batch", 1) > 1 or getattr(
                    down, "preferred_batch", 1) > 1:
                return False
            return True

        fused_up: Dict[str, Element] = {}  # down name -> its fused upstream
        for el in self.elements.values():
            if total_out(el) == 1:
                for pad in el.srcpads:
                    for dst, _ in pad.links:
                        if fusable(el, dst):
                            fused_up[dst.name] = el
        segs: List[_Seg] = []
        self._seg_of = {}
        for el in self.elements.values():
            if el.name in fused_up:
                continue  # not a head
            chain = [el]
            cur = el
            while True:
                nxt = None
                if total_out(cur) == 1:
                    for pad in cur.srcpads:
                        for dst, _ in pad.links:
                            if fused_up.get(dst.name) is cur:
                                nxt = dst
                if nxt is None:
                    break
                chain.append(nxt)
                cur = nxt
            seg = _Seg(chain)
            for e in chain:
                st = _ElemState(e)
                st.connected = {
                    pad
                    for other in self.elements.values()
                    for sp in other.srcpads
                    for d, pad in sp.links
                    if d is e
                } or {0}
                st.terminal = not isinstance(e, SourceElement) and not any(
                    p.is_linked for p in e.srcpads
                )
                seg.states[e.name] = st
                self._seg_of[e.name] = seg
            # in-segment routing links
            for a, b in zip(chain, chain[1:]):
                sa = seg.states[a.name]
                for i, pad in enumerate(a.srcpads):
                    for dst, sink_pad in pad.links:
                        if dst is b:
                            sa.next_state = seg.states[b.name]
                            sa.out_pad = i
                            sa.next_pad = sink_pad
            segs.append(seg)
        if self._fuse and any(len(s.chain) > 1 for s in segs):
            self.log.info(
                "fused %d elements onto %d streaming thread(s): %s",
                len(self.elements), len(segs),
                " | ".join(
                    "+".join(e.name for e in s.chain) for s in segs
                ),
            )
        return segs

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "Pipeline":
        if self._started:
            return self
        # claim the registry label BEFORE any element start: elements
        # bind instruments to it in their start() (the query client's
        # rtt histogram), so the label must be settled first — and
        # claiming here (not lazily at scrape time) means a scrape can
        # never resurrect a released label
        if self._telemetry_label is None:
            from ..core.telemetry import claim_pipeline_label

            self._telemetry_label = claim_pipeline_label(self.name)
        started: List[Element] = []
        try:
            # start (open models/resources) BEFORE the static negotiation
            # pass so elements can expose model-derived schemas (reference:
            # caps negotiation triggers subplugin open, tensor_filter.c:1157)
            for el in self.elements.values():
                el.start()
                started.append(el)
            self._fuse_device_chains()
            self._negotiate()
        except BaseException:
            for el in started:
                try:
                    el.stop()
                except Exception:
                    self.log.exception("stop() failed for %s", el.name)
            from ..core.telemetry import release_pipeline_label

            release_pipeline_label(self._telemetry_label)
            self._telemetry_label = None
            raise
        # a terminal is any non-source element with no LINKED src pad (a
        # trailing element whose output nobody consumes still ends the
        # stream, e.g. a pipeline ending at tensor_trainer)
        self._pending_sinks = sum(
            1
            for el in self.elements.values()
            if not isinstance(el, SourceElement)
            and not any(p.is_linked for p in el.srcpads)
        )
        if self._pending_sinks == 0:
            self._sinks_done.set()
        # streaming-thread partition (after element start: preferred_batch
        # needs live backends); mailboxes only where thread boundaries
        # remain — fused elements receive their input inline, so the
        # per-frame lock/condvar handoff between them is gone entirely
        self._segments = self._compute_segments()
        fused_tail = {
            e.name for seg in self._segments for e in seg.chain[1:]
        }
        # mailboxes for every segment-head element with sink pads — native
        # C++ condvar queues when the core library is available (immediate
        # wakeups, GIL released while blocked), stdlib queue.Queue otherwise
        for el in self.elements.values():
            if isinstance(el, SourceElement):
                continue
            if el.name in fused_tail:
                el._mailbox = None  # input arrives inline on the segment
                continue
            size = self.default_queue_size
            if "max-buffers" in el.props and el.props["max-buffers"]:
                size = int(el.props["max-buffers"])
            # a micro-batching element needs its full batch to fit in the
            # mailbox or batches can never form at max-batch size
            size = max(size, getattr(el, "preferred_batch", 1))
            el._mailbox = self._make_mailbox(
                size, getattr(el, "leaky_policy", "")
            )
        def _dlq_maxlen(el: Element) -> int:
            v = el.props.get("dead-letter-max")
            # 0 is a VALID setting (count drops, retain no frame payloads
            # — large tensors must not pin memory); only absent means 16
            return 16 if v is None else max(0, int(v))

        self.health_map = {
            el.name: ElementHealth(
                state="running", dlq=deque(maxlen=_dlq_maxlen(el)),
            )
            for el in self.elements.values()
        }
        self._stop_flag.clear()
        self._drain_flag.clear()
        # upstream adjacency for deadline-QoS feedback (a downstream
        # deadline drop throttles every upstream tensor_rate, ≙ the
        # reference's QoS events travelling upstream)
        self._upstream = {n: [] for n in self.elements}
        for el in self.elements.values():
            for pad in el.srcpads:
                for dst, _ in pad.links:
                    self._upstream[dst.name].append(el)
        self._arm_watchdog()
        self._register_telemetry()
        for el in self.elements.values():
            el._interrupted.clear()
        for seg in self._segments:
            t = threading.Thread(
                target=self._run_segment, args=(seg,),
                name=seg.chain[0].name, daemon=True,
            )
            self._threads.append(t)
        for t in self._threads:
            t.start()
        if self._wd_thread is not None:
            self._wd_thread.start()
        self._started = True
        return self

    def register_sweep(self, fn: Callable[[], Any],
                       min_poll_s: float = 1.0) -> None:
        """Register a slow-cadence poller on the watchdog sweeper thread
        (elements call this from ``start()`` — before ``_arm_watchdog``
        runs, so the sweeper picks it up).  ``fn`` must rate-limit
        itself; ``min_poll_s`` only bounds the sweeper's wakeup
        interval.  Hooks are cleared at the next ``start()``."""
        self._sweep_hooks.append((fn, max(0.05, float(min_poll_s))))

    def _arm_watchdog(self) -> None:
        """Build the liveness watchdog for every element that armed a
        stall-timeout / frame-deadline; no-op (zero threads, zero hot-path
        cost) when nothing is armed."""
        self._watchdog = None
        self._watches = {}
        self._wd_thread = None
        armed = [
            el for el in self.elements.values()
            if float(el.props.get("stall-timeout") or 0.0) > 0
            or float(el.props.get("frame-deadline") or 0.0) > 0
        ]
        if not armed:
            extra = [s for _, s in self._sweep_hooks]
            if self._mem_monitor is not None:
                extra.append(self._mem_monitor.min_poll_s)
            if extra:
                # no liveness watches, but the memory monitor / sweep
                # hooks (digest publisher) still need the cadence
                self._wd_thread = threading.Thread(
                    target=self._watchdog_loop,
                    args=(min(extra),),
                    name=f"{self.name}-watchdog", daemon=True,
                )
            return
        self._watchdog = Watchdog()
        for el in armed:
            # a fused element has no mailbox of its own: pending work for
            # the whole segment sits in the head's mailbox (or a source
            # head's internal queue), so stall detection watches that
            box = el._mailbox
            if box is None:
                seg = self._seg_of.get(el.name)
                head = seg.chain[0] if seg else el
                box = head._mailbox or getattr(head, "_q", None)
            qsize = box.qsize if hasattr(box, "qsize") else (lambda: 0)
            self._watches[el.name] = self._watchdog.register(
                el.name,
                stall_timeout=float(el.props.get("stall-timeout") or 0.0),
                frame_deadline=float(el.props.get("frame-deadline") or 0.0),
                policy=el.props.get("stall-policy", "warn"),
                qsize=qsize,
                on_event=lambda w, kind, elapsed, el=el: self._on_liveness(
                    el, kind, elapsed),
            )
        interval = min(
            [self._watchdog.min_interval()]
            + [s for _, s in self._sweep_hooks])
        self._wd_thread = threading.Thread(
            target=self._watchdog_loop,
            args=(interval,),
            name=f"{self.name}-watchdog", daemon=True,
        )

    def _watchdog_loop(self, interval: float) -> None:
        while not self._stop_flag.wait(interval):
            try:
                if self._watchdog is not None:
                    self._watchdog.check()
            except Exception:  # a sweep bug must never kill liveness
                self.log.exception("watchdog sweep failed")
            mon = self._mem_monitor
            if mon is not None:
                try:
                    mon.poll()  # rate-limited internally
                except Exception:
                    self.log.exception("memory-pressure poll failed")
            for fn, _ in self._sweep_hooks:
                try:
                    fn()  # rate-limited internally (register_sweep)
                except Exception:
                    self.log.exception("sweep hook %r failed", fn)

    def _on_liveness(self, el: Element, kind: str, elapsed: float) -> None:
        """Watchdog escalation (runs on the sweeper thread): bus warning
        always; stall-policy restart/fail additionally interrupt the hung
        call cooperatively (the worker's StallError handling does the
        actual restart — only the hung thread itself can retry its
        frame)."""
        policy = el.props.get("stall-policy", "warn")
        h = self.health_map.get(el.name)
        if h is not None:
            h.last_error = f"liveness: {kind} after {elapsed:.3f}s"
        self.post(BusMessage("warning", el.name, {
            "liveness": kind, "elapsed": elapsed, "policy": policy,
        }))
        # first question after a stall is "where did the time go": dump
        # the flight recorder (rate-limited no-op without one) while the
        # stalled frame's open span is still in the ring
        self.incident(f"watchdog_{kind}", el.name,
                      {"elapsed": elapsed, "policy": policy})
        if policy == "warn":
            return
        el._interrupted.set()
        if policy == "fail":
            # the element may be wedged non-cooperatively: surface the
            # failure NOW so wait() raises, instead of hoping the hung
            # thread ever comes back to report it
            err = StallError(
                f"{el.name}: {kind} after {elapsed:.3f}s (stall-policy=fail)"
            )
            if h is not None:
                h.state = "stalled"
            self.errors.append(err)
            self.post(BusMessage("error", el.name, err))
            self._stop_flag.set()
            self._sinks_done.set()

    def _make_mailbox(self, size: int, leaky: str = ""):
        if leaky:
            return _LeakyMailbox(size, leaky)
        try:
            from ..native.runtime import NativeMailbox, available

            if available():
                return NativeMailbox(size)
        except Exception:  # pragma: no cover — toolchain quirks
            self.log.exception("native mailbox unavailable; using queue.Queue")
        return queue.Queue(maxsize=size)

    def _halt_workers(self) -> None:
        """Immediate worker shutdown: stop flag + mailbox sentinels +
        join.  Frames still queued are abandoned (count them with
        ``_count_abandoned`` before element state is torn down)."""
        self._stop_flag.set()
        self._halt_discarded = 0
        for el in self.elements.values():
            if el._mailbox is not None:
                try:
                    el._mailbox.put_nowait((0, _STOP))
                except queue.Full:
                    # drain one slot so the sentinel fits — the evicted
                    # frame is abandoned too, so count it for
                    # _count_abandoned's exact-dropped contract
                    try:
                        _, item = el._mailbox.get_nowait()
                        if isinstance(item, TensorFrame):
                            self._halt_discarded += getattr(
                                item, "batch_size", 1)
                        el._mailbox.put_nowait((0, _STOP))
                    except (queue.Empty, queue.Full):
                        pass
        for t in self._threads:
            t.join(timeout=5.0)

    def stop(self, drain: bool = False,
             drain_timeout: Optional[float] = None) -> None:
        """Tear the pipeline down.  ``drain=True`` first flushes every
        in-flight frame to the sinks via :meth:`drain` (bounded by
        ``drain_timeout``) — planned shutdowns lose nothing; the default
        remains the immediate teardown (queued frames are abandoned)."""
        if drain and self._started and not self._stop_flag.is_set():
            self.drain(drain_timeout)
            if not self._started:
                return  # an expired drain already tore the pipeline down
        self._halt_workers()
        if self._wd_thread is not None:
            if self._wd_thread.is_alive():
                self._wd_thread.join(timeout=2.0)
            self._wd_thread = None
            # _watchdog/_watches survive stop(): a straggler worker whose
            # join timed out may still ping them (harmless — the sweeper
            # is gone), and health() keeps reporting the final counters;
            # the next start() rebuilds both in _arm_watchdog()
        for el in self.elements.values():
            try:
                el.stop()
            except Exception:
                self.log.exception("stop() failed for %s", el.name)
        # telemetry teardown AFTER element stop: a scrape racing the
        # shutdown still sees consistent health; the exposition listener
        # socket is closed synchronously here (leak-check contract)
        self._unregister_telemetry()
        self._threads.clear()
        # sweep hooks die with the run (elements re-register at the
        # next start(); a restart must not accumulate stale pollers)
        self._sweep_hooks = []
        self._started = False

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until EOS reached every sink; re-raise the first element
        error.  ≙ waiting for EOS/ERROR on the GstBus.

        A timed-out wait TEARS THE PIPELINE DOWN (``stop()``) before
        raising ``TimeoutError``: a stuck pipeline must not leak live
        worker threads into the caller (they would poison later tests /
        pipelines in the same process).  A timeout is a terminal
        condition, not a poll — use ``pop_message``/bus watchers to
        observe a pipeline that should keep running."""
        finished = self._sinks_done.wait(timeout)
        if self.errors:
            raise self.errors[0]
        if not finished:
            self.stop()
            if self.errors:
                # an error that raced the timeout is the truer cause
                raise self.errors[0]
            raise TimeoutError(f"pipeline {self.name!r} did not finish in {timeout}s")

    # -- zero-downtime operations (core/lifecycle.py) ------------------------
    @property
    def draining(self) -> bool:
        """True between ``drain()`` and completion/teardown — sources
        (including ones blocking inside ``frames()``, via
        ``lifecycle.pipeline_quiescing``) stop producing and flush EOS."""
        return self._drain_flag.is_set()

    def delivered_frames(self) -> int:
        """Logical frames consumed by terminal elements since start()
        (single-writer per-streaming-thread counters, summed here)."""
        return sum(
            st.delivered
            for seg in self._segments
            for st in seg.states.values()
        )

    def _count_abandoned(self) -> int:
        """Exact count of logical frames abandoned by an immediate halt:
        everything still queued in mailboxes plus whatever elements
        report as parked in-flight (``pending_frames`` hook, e.g. the
        filter's dispatch window).  Call after ``_halt_workers`` and
        before element ``stop()`` clears that state."""
        n = getattr(self, "_halt_discarded", 0)
        for el in self.elements.values():
            box = el._mailbox
            if box is not None:
                try:
                    while True:
                        _, item = box.get_nowait()
                        if isinstance(item, TensorFrame):
                            n += getattr(item, "batch_size", 1)
                except queue.Empty:
                    pass
            pending = getattr(el, "pending_frames", None)
            if pending is not None:
                try:
                    n += int(pending() or 0)
                except Exception:
                    self.log.exception(
                        "pending_frames failed for %s", el.name)
        for seg in self._segments:
            for st in seg.states.values():
                n += st.in_call  # halted mid-call: the frame never left
            for _, item in seg.stash:
                if isinstance(item, TensorFrame):
                    n += getattr(item, "batch_size", 1)
        return n

    def drain(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        """Graceful drain: quiesce every source, flush all in-flight
        frames through to the sinks via the existing EOS machinery, and
        return exact accounting::

            {"drained": <frames delivered to terminal elements since the
                         drain began>,
             "dropped": <frames abandoned because the deadline expired —
                         the pipeline is torn down in that case>,
             "elapsed": <seconds>}

        Semantics are identical fused and unfused (the counters live at
        the terminal dispatch, which both modes share).  A completed
        drain leaves the pipeline stopped-at-EOS but not torn down —
        call ``stop()`` (or use ``stop(drain=True)``) to release
        resources."""
        t0 = time.monotonic()
        if not self._started:
            return {"drained": 0, "dropped": 0, "elapsed": 0.0}
        base = self.delivered_frames()
        self.log.info(
            "draining pipeline%s",
            f" (deadline {timeout}s)" if timeout else "",
        )
        self._drain_flag.set()
        finished = self._sinks_done.wait(timeout)
        dropped = 0
        if not finished:
            # deadline expired: halt NOW and account every frame that
            # did not make it out
            self._halt_workers()
            dropped = self._count_abandoned()
        drained = self.delivered_frames() - base
        elapsed = time.monotonic() - t0
        self.post(BusMessage("element", self.name, {
            "drain": {
                "drained": drained, "dropped": dropped,
                "elapsed": elapsed, "completed": finished,
            },
        }))
        if not finished:
            self.stop()  # finish the teardown (workers already joined)
        return {"drained": drained, "dropped": dropped, "elapsed": elapsed}

    def reload_model(self, element, model: str = ""):
        """Zero-downtime model rollout: stage, validate, and JIT-warm
        ``model`` on a second backend instance off the hot path, then
        hot-swap the named ``tensor_filter`` at a frame boundary (see
        ``core/lifecycle.py``; swap/rollback counters surface in
        :meth:`health`).  Returns the :class:`~..core.lifecycle.SwapTicket`."""
        el = self.elements[element] if isinstance(element, str) else element
        request = getattr(el, "request_reload", None)
        if request is None:
            raise ElementError(
                f"{el.name} does not support hot model reload")
        return request(model)

    # -- supervision ---------------------------------------------------------
    def health(self) -> Dict[str, Dict[str, Any]]:
        """Live supervision snapshot: per-element state, restart count,
        dead-letter depth/total, and any element-specific health (e.g.
        the query client's per-remote circuit-breaker states via
        ``Element.health_info()``).  Cheap enough to poll."""
        out: Dict[str, Dict[str, Any]] = {}
        for name, el in self.elements.items():
            h = self.health_map.get(name) or ElementHealth()
            entry: Dict[str, Any] = {
                "state": h.state,
                "policy": el.props.get("error-policy", "fail-stop"),
                "restarts": h.restarts_total,
                "restarts_window": h.restarts,
                "dead_letters": h.dead_letters,
                "dead_letter_depth": len(h.dlq),
                "deadline_drops": h.deadline_drops,
                "last_error": h.last_error,
            }
            w = self._watches.get(name)
            if w is not None:
                entry["stalls"] = w.stalls
                entry["overruns"] = w.overruns
            info = getattr(el, "health_info", None)
            if info is not None:
                try:
                    entry.update(info() or {})
                except Exception:  # health must never kill the caller
                    self.log.exception("health_info failed for %s", name)
            out[name] = entry
        return out

    def post_health(self) -> None:
        """Publish the current health snapshot on the bus (kind
        ``health``); also posted automatically when an element degrades."""
        self.post(BusMessage("health", self.name, self.health()))

    # -- deadline QoS ---------------------------------------------------------
    def _expire_late(self, el: Element, frames: list) -> list:
        """Deadline QoS: drop frames whose latency budget is exhausted
        before `el` processes them (``late-policy=drop``), with exact
        accounting (``health()[el]["deadline_drops"]``), a rate-limited
        bus warning, and QoS feedback to upstream throttlers
        (``note_qos``, implemented by tensor_rate).  Frames with no
        deadline cost one dict lookup each."""
        keep = None  # lazily forked: the no-drop path must not copy
        now = time.monotonic()
        for i, f in enumerate(frames):
            ts = f.meta.get(DEADLINE_META)
            # boundary contract: delivered strictly BEFORE the deadline,
            # dropped from the instant now >= deadline (liveness.is_expired)
            if ts is None or now < ts:
                if keep is not None:
                    keep.append(f)
                continue
            if keep is None:
                if el.props.get("late-policy", "drop") != "drop":
                    return frames
                keep = list(frames[:i])
            n = getattr(f, "batch_size", 1)
            h = self.health_map.get(el.name)
            if h is not None:
                h.deadline_drops += n
            lateness = now - ts
            last = self._qos_warn_ts.get(el.name, float("-inf"))
            if now - last >= 1.0:  # 1/s per element: drops come in bursts
                self._qos_warn_ts[el.name] = now
                self.log.warning(
                    "%s: dropped %d frame(s) %.3fs past deadline "
                    "(late-policy=drop)", el.name, n, lateness,
                )
                self.post(BusMessage("warning", el.name, {
                    "qos": "deadline", "dropped": n, "lateness": lateness,
                }))
            self._qos_feedback(el, f, lateness)
        return frames if keep is None else keep

    def _qos_feedback(self, el: Element, frame, lateness: float) -> None:
        """Tell every upstream throttler a deadline was missed (≙ the
        reference's QoS events travelling upstream to tensor_rate,
        gsttensor_rate.c): elements exposing ``note_qos(pts, lateness)``
        hear about it and shed earlier, where dropping is cheapest."""
        seen = {el.name}
        stack = [el.name]
        while stack:
            for up in self._upstream.get(stack.pop(), ()):
                if up.name in seen:
                    continue
                seen.add(up.name)
                note = getattr(up, "note_qos", None)
                if note is not None:
                    try:
                        note(frame.pts, lateness)
                    except Exception:
                        self.log.exception("note_qos failed for %s", up.name)
                stack.append(up.name)

    def stream_cancel_feedback(self, el: Element, meta: dict) -> None:
        """A downstream consumer of a generation stream is GONE (the
        serversink's client vanished mid-stream): walk upstream — the
        ``note_qos`` routing — and tell every element exposing
        ``note_stream_cancel(meta)``, so a continuous-batching slot
        engine frees the dead stream's slot instead of decoding tokens
        nobody will read."""
        seen = {el.name}
        stack = [el.name]
        while stack:
            for up in self._upstream.get(stack.pop(), ()):
                if up.name in seen:
                    continue
                seen.add(up.name)
                note = getattr(up, "note_stream_cancel", None)
                if note is not None:
                    try:
                        note(meta)
                    except Exception:
                        self.log.exception(
                            "note_stream_cancel failed for %s", up.name)
                stack.append(up.name)

    def stream_drain_feedback(self) -> None:
        """A query serversrc of THIS pipeline entered its rolling-restart
        drain: tell every element exposing ``note_stream_drain()`` (the
        continuous-batching generator) so live generation streams are
        handed off as resumable GOAWAY chunks — the client migrates them
        to a healthy server — instead of the drain-deadline racing whole
        generations.  Never fired by a plain ``drain()`` on a pipeline
        without a serversrc: local streams flush, they don't migrate."""
        for el in self.elements.values():
            note = getattr(el, "note_stream_drain", None)
            if note is None:
                continue
            try:
                note()
            except Exception:
                self.log.exception(
                    "note_stream_drain failed for %s", el.name)

    def _dead_letter(self, el: Element, frames, err: BaseException) -> None:
        """skip policy: record dropped frame(s) + bus warning."""
        h = self.health_map[el.name]
        frames = frames if isinstance(frames, list) else [frames]
        n = sum(getattr(f, "batch_size", 1) for f in frames)
        for f in frames:
            h.dlq.append((f, repr(err)))
        h.dead_letters += n
        h.last_error = repr(err)
        self.log.warning(
            "%s: dropped %d poisoned frame(s) (error-policy=skip): %s",
            el.name, n, err,
        )
        self.post(BusMessage("warning", el.name, {
            "policy": "skip", "dropped": n, "error": err,
        }))
        self.incident("dead_letter", el.name, err)

    def _restart_element(self, el: Element, err: BaseException) -> str:
        """restart policy: stop+start `el` with exponential backoff.

        Returns ``"retry"`` (restarted — re-run the failed call),
        ``"degraded"`` (max-restarts exhausted or start() itself failed
        — caller falls back to fail-stop), or ``"stopping"`` (pipeline
        shut down mid-backoff — caller exits quietly)."""
        h = self.health_map[el.name]
        el._interrupted.clear()  # a liveness interrupt is consumed here
        h.last_error = repr(err)
        limit = int(el.props.get("max-restarts", 3))
        window = float(el.props.get("restart-window", 60.0) or 0.0)
        now = time.monotonic()
        if window > 0 and h.last_restart_ts and (
                now - h.last_restart_ts) > window:
            # sustained health since the last restart: the budget (and
            # the backoff curve) refills — isolated glitches over days
            # must not accumulate into an inevitable degradation
            h.restarts = 0
        h.last_restart_ts = now
        if h.restarts >= limit:
            h.state = "degraded"
            self.log.error(
                "%s: max-restarts=%d exhausted; degrading to fail-stop",
                el.name, limit,
            )
            self.post(BusMessage("warning", el.name, {
                "policy": "restart", "degraded": True, "error": err,
            }))
            self.post_health()
            return "degraded"
        h.restarts += 1
        h.restarts_total += 1
        h.state = "restarting"
        from ..core.resilience import RetryPolicy

        base = float(el.props.get("restart-backoff", 0.05) or 0.0)
        # RetryPolicy owns the backoff curve (capped exponential + jitter
        # so many elements restarting together don't thundering-herd)
        delay = RetryPolicy(
            base_delay_s=base, max_delay_s=2.0, jitter=0.1,
        ).delay_for(h.restarts) if base > 0 else 0.0
        self.log.warning(
            "%s: restart %d/%d after error (backoff %.3fs): %s",
            el.name, h.restarts, limit, delay, err,
        )
        self.post(BusMessage("warning", el.name, {
            "policy": "restart", "restart": h.restarts, "error": err,
        }))
        try:
            el.stop()
        except Exception:
            self.log.exception("%s: stop() during restart failed", el.name)
        if delay > 0 and self._stop_flag.wait(delay):
            return "stopping"
        if self._stop_flag.is_set():
            return "stopping"
        try:
            el.start()
        except Exception:  # interrupts must propagate, not "degrade"
            self.log.exception("%s: start() during restart failed", el.name)
            h.state = "degraded"
            self.post_health()
            return "degraded"
        h.state = "running"
        return "retry"

    _SUPERVISED_STOPPING = object()  # sentinel: worker must exit quietly

    def _skip_failed(self, el: Element, frames, err: BaseException,
                     per_item) -> list:
        """skip semantics for a failed call: when the call covered
        MULTIPLE frames and a per-item re-call is available, isolate the
        poison — re-run each frame alone so one bad frame in a
        micro-batch doesn't take its batchmates to the dead-letter
        queue; otherwise dead-letter the whole input.  Assumes the batch
        call failed atomically (true for the invoke-style elements that
        batch: one backend call, outputs only on success) — a stateful
        element that partially consumed the batch before raising would
        see the survivors twice."""
        if per_item is not None and isinstance(frames, list) and len(frames) > 1:
            outs: list = []
            for f in frames:
                try:
                    outs.extend(per_item(f) or [])
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as e2:  # noqa: BLE001 — policy boundary
                    self._dead_letter(el, [f], e2)
            return outs
        self._dead_letter(el, frames, err)
        return []

    def _supervised(self, el: Element, call, frames, per_item=None):
        """Run one frame-processing call under `el`'s error-policy.

        fail-stop re-raises (the `_guard` boundary turns it into a bus
        error + pipeline teardown); skip dead-letters the poisoned
        frame(s) — isolating them per-frame via `per_item` when the
        failed call was a micro-batch — and yields the rest; restart
        restarts the element and RETRIES the same call (zero frame loss
        for transient faults), degrading to fail-stop once max-restarts
        is exhausted, and treats fatal (bad-input) errors like skip.

        Elements with ``SUPERVISES_OWN_ERRORS`` (async in-flight
        dispatch, e.g. the query client) always run fail-stop here: an
        error surfacing during frame B's call may belong to in-flight
        frame A, so skip/restart would dead-letter or re-dispatch the
        WRONG frame — such elements degrade via their own mechanism
        (``degrade=`` on the query client) instead."""
        policy = el.props.get("error-policy", "fail-stop")
        if getattr(el, "SUPERVISES_OWN_ERRORS", False):
            policy = "fail-stop"
        # locals: stop() may run concurrently with a straggler worker —
        # the pings must never dereference a half-torn-down pipeline
        wd, watch = self._watchdog, self._watches.get(el.name)
        while True:
            try:
                if el._interrupted.is_set():
                    # a STALE interrupt (the flagged call completed on
                    # its own, or the stall was a transient push-block)
                    # must not leak into this healthy call — it would
                    # raise a spurious StallError and burn the restart
                    # budget on an element that is progressing
                    el._interrupted.clear()
                if watch is not None:
                    # heartbeat: the busy window spans the whole call so
                    # the watchdog can flag a per-frame overrun (pinged
                    # BEFORE the fault site — an injected hang must land
                    # inside the monitored window)
                    wd.begin(watch)
                try:
                    # fault-injection site INSIDE the policy boundary, so
                    # injected faults exercise the same machinery real
                    # ones do; the interrupt predicate lets watchdog
                    # escalation / pipeline stop break hang= faults
                    if FAULTS.is_armed():
                        FAULTS.check(
                            f"element.{el.name}.handle_frame",
                            interrupt=lambda: el.interrupted,
                        )
                    result = call()
                    if policy != "fail-stop" and not isinstance(
                            result, (list, tuple)):
                        # lazy outputs (generators, e.g. the query client's
                        # stream mode) raise during ITERATION, which happens
                        # outside this try under fail-stop; with skip/restart
                        # the errors must land here, so materialize — the
                        # cost of supervision is losing output laziness
                        result = list(result)
                finally:
                    if watch is not None:
                        # any outcome is progress: the item left the queue
                        wd.done(watch)
                return result
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — policy boundary
                if isinstance(e, StallError):
                    # a hung call surfaced via cooperative interruption:
                    # STALL-policy governs (independent of error-policy —
                    # a fail-stop element can still be stall-restarted)
                    el._interrupted.clear()
                    sp = el.props.get("stall-policy", "warn")
                    if sp == "restart":
                        verdict = self._restart_element(el, e)
                        if verdict == "retry":
                            continue
                        if verdict == "stopping":
                            return self._SUPERVISED_STOPPING
                        raise  # degraded: fall back to fail-stop
                    if sp == "fail":
                        raise
                    # warn (element code raised StallError on its own):
                    # fall through to the normal error-policy handling
                if policy == "skip":
                    return self._skip_failed(el, frames, e, per_item)
                if policy == "restart":
                    from ..core.resilience import is_transient

                    if not is_transient(e):
                        # fatal classification (bad input, schema bug):
                        # restarting cannot fix the frame — dead-letter
                        # (isolating within a batch) and keep the restart
                        # budget for faults a restart CAN cure
                        return self._skip_failed(el, frames, e, per_item)
                    verdict = self._restart_element(el, e)
                    if verdict == "retry":
                        continue
                    if verdict == "stopping":
                        return self._SUPERVISED_STOPPING
                raise

    def run(self, timeout: Optional[float] = None) -> None:
        """start + wait + stop."""
        self.start()
        try:
            self.wait(timeout)
        finally:
            self.stop()

    # -- worker runtime ------------------------------------------------------
    # One worker thread per SEGMENT (a maximal fusable linear chain).  The
    # head pulls items (source generator or mailbox); every downstream
    # element in the segment processes inline on the same streaming thread
    # via _dispatch — no intermediate mailbox, no lock/condvar handoff, no
    # per-frame wakeup.  Items leaving the segment go through _push /
    # _push_outs (block handoff: one queue operation per run of outputs).

    def _fail(self, el: Element, e: BaseException) -> bool:
        """Record a fatal element failure (≙ GstBus error posting) and tear
        the pipeline down; returns False so dispatch chains unwind.  Must
        be called from an ``except`` context (log.exception)."""
        self.log.exception("element %s failed", el.name)
        h = self.health_map.get(el.name)
        if h is not None:
            h.state = "failed"
            h.last_error = repr(e)
        self.errors.append(e)
        self.post(BusMessage("error", el.name, e))
        self._stop_flag.set()
        self._sinks_done.set()  # unblock wait()
        return False

    def _guard(self, el: Element, fn, *args):
        try:
            return fn(*args)
        except BaseException as e:  # noqa: BLE001 — worker boundary
            self._fail(el, e)
            return None

    def _push(self, el: Element, src_pad: int, item) -> bool:
        """Push one item downstream with backpressure; False if stopping.

        Frames bound for a leaky queue are dropped instead of blocking
        (``upstream``: the incoming frame; ``downstream``: the oldest
        queued frame).  Events always use the blocking path — caps/EOS
        must never be lost."""
        pad = el.srcpads[src_pad]
        is_frame = isinstance(item, TensorFrame)
        if is_frame and self.tracer is not None:
            # queue-wait origin stamp (host-local, popped at dequeue);
            # tracer-armed only — the disabled path stays one branch
            item.meta[TL_QPUT_META] = time.perf_counter()
        for dst, sink_pad in pad.links:
            box = dst._mailbox
            if is_frame and isinstance(box, _LeakyMailbox):
                box.put_frame((sink_pad, item))
                continue
            if not self._put_blocking(el, box, (sink_pad, item)):
                return False
        return True

    def _put_blocking(self, el: Element, box, entry) -> bool:
        """Bounded-wait put that keeps the stop flag responsive; False if
        stopping.  A put that finds the mailbox full is back-pressure on
        ``el``'s thread: while a profiler session is live its wait goes
        to the span ring (``nns.pipeline.push_wait``; ring only — a
        thread asleep on a queue opens no annotation)."""
        try:
            box.put_nowait(entry)
            return True
        except queue.Full:
            t0 = time.perf_counter() if armed() else None
        try:
            while not self._stop_flag.is_set():
                try:
                    box.put(entry, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False
        finally:
            if t0 is not None:
                record("nns.pipeline.push_wait", t0, time.perf_counter(),
                       element=el.name)

    def _put_many(self, el: Element, dst: Element, items: list) -> int:
        """Deliver an ordered run of ``(pad, item)`` entries into ``dst``'s
        mailbox, amortizing the lock/condvar cost over the run when the
        mailbox supports bulk insertion (block handoff); falls back to the
        per-item blocking path otherwise.  Returns the number of entries
        delivered — short of ``len(items)`` only when stopping (the halt
        accounting needs the exact split: delivered entries are counted
        in the mailbox sweep, the rest stay on the emitter)."""
        box = dst._mailbox
        if self.tracer is not None:
            now = time.perf_counter()
            for _, it in items:
                if isinstance(it, TensorFrame):
                    it.meta[TL_QPUT_META] = now
        put_many = getattr(box, "put_many", None)
        idx, n_items = 0, len(items)
        while idx < n_items:
            if put_many is not None:
                n = put_many(items[idx:] if idx else items, timeout=0.1)
                idx += n
                if idx >= n_items:
                    return idx
                if n > 0:
                    continue  # partial progress: retry the remainder
            # blocked (or no bulk support): bounded-wait single put so the
            # stop flag stays responsive and events are never dropped
            if not self._put_blocking(el, box, items[idx]):
                return idx
            idx += 1
        return idx

    def _push_outs(self, el: Element, outs, st: "_ElemState" = None) -> bool:
        """Deliver a call's outputs through mailboxes.  Consecutive items
        bound for the same destination travel as ONE queue operation, so
        the lock/wakeup cost amortizes over the run (a micro-batching
        filter emitting N per-frame outputs pays ~1 handoff, not N).

        With ``st``, ``st.in_call`` is decremented as frames land in a
        mailbox (where the halt-time sweep takes over counting them) —
        per delivered entry on the common single-destination shape, in
        one step on full success for fan-outs (a frame delivered to one
        of two branches has no exact owner; the all-or-nothing fallback
        at worst overcounts that stop-race edge)."""
        if not outs:
            return True
        if len(outs) == 1:
            sp, out = outs[0]
            if not self._push(el, sp, out):
                return False
            if st is not None and isinstance(out, TensorFrame):
                st.in_call = max(
                    0, st.in_call - getattr(out, "batch_size", 1))
            return True
        runs: list = []  # [(dst, [(pad, item), ...])], order kept per dst
        index: Dict[str, int] = {}
        for sp, out in outs:
            for dst, sink_pad in el.srcpads[sp].links:
                k = index.get(dst.name)
                if k is None:
                    index[dst.name] = len(runs)
                    runs.append((dst, [(sink_pad, out)]))
                else:
                    runs[k][1].append((sink_pad, out))
        track_each = st is not None and len(runs) == 1
        for dst, items in runs:
            n = self._put_many(el, dst, items)
            if track_each:
                for _, item in items[:n]:
                    if isinstance(item, TensorFrame):
                        st.in_call = max(
                            0, st.in_call - getattr(item, "batch_size", 1))
            if n < len(items):
                return False
        if st is not None and not track_each:
            st.in_call = max(0, st.in_call - self._outs_logical(outs))
        return True

    def _route_one(self, seg: _Seg, st: _ElemState, sp: int, item) -> bool:
        """Route one output item: inline into the fused downstream element
        when the link stays inside the segment, else out through its
        mailbox.  False = the worker must exit."""
        nxt = st.next_state
        if nxt is not None:
            if sp == st.out_pad:
                return self._dispatch(seg, nxt, st.next_pad, item)
            return True  # unlinked src pad: dropped (parity with _push)
        return self._push(st.el, sp, item)

    @staticmethod
    def _outs_logical(outs) -> int:
        """Logical frames in a materialized outs list/tuple (0 for lazy
        iterables, which produce frames on demand).  Drain accounting:
        once a handler returns, its INPUT frames are gone (emitted as
        these outs, parked behind a ``pending_frames`` hook, or consumed)
        — ``st.in_call`` transfers to this count so a halt mid-route
        never double-counts parked frames yet still sees unrouted
        outputs."""
        if not isinstance(outs, (list, tuple)):
            return 0
        n = 0
        for _, out in outs:
            if isinstance(out, TensorFrame):
                n += getattr(out, "batch_size", 1)
        return n

    def _route_outs(self, seg: _Seg, st: _ElemState, outs) -> bool:
        """Route a call's outputs (list, tuple, or lazy iterable).  Lists
        are consumed destructively so frame carcasses can return to the
        pool the moment downstream is done with them; lazy iterables (the
        query client's stream mode) are forwarded as they are produced.
        ``st.in_call`` is decremented as each frame is handed off
        (mailbox put or inline dispatch — where the downstream element's
        own accounting takes over), keeping halt-time abandoned counts
        exact."""
        nxt = st.next_state
        if nxt is None:
            if isinstance(outs, (list, tuple)):
                return self._push_outs(st.el, outs, st)
            for sp, out in outs:  # lazy stream: emit answers as they land
                if not self._push(st.el, sp, out):
                    return False
            return True
        out_pad, next_pad = st.out_pad, st.next_pad
        if isinstance(outs, (list, tuple)):
            is_list = isinstance(outs, list)
            for k in range(len(outs)):
                sp, out = outs[k]
                if is_list:
                    outs[k] = None  # drop the ref so recycle can reclaim
                if isinstance(out, TensorFrame):
                    # handed off: the fused downstream call (or its drop
                    # on an unlinked pad) owns the frame from here
                    st.in_call = max(
                        0, st.in_call - getattr(out, "batch_size", 1))
                if sp == out_pad:
                    if not self._dispatch(seg, nxt, next_pad, out):
                        return False
                if isinstance(out, TensorFrame):
                    FRAME_POOL.recycle(out)
            return True
        for sp, out in outs:
            if sp == out_pad:
                if not self._dispatch(seg, nxt, next_pad, out):
                    return False
            if isinstance(out, TensorFrame):
                FRAME_POOL.recycle(out)
        return True

    def _fast_path(self, el: Element, watch) -> bool:
        """True when the full _supervised wrapper would change nothing for
        this call — no watchdog heartbeat to ping, no fault site armed, no
        pending interrupt, fail-stop error policy and warn stall policy —
        so the dispatch loop may call the handler directly (errors still
        reach the worker boundary exactly as _supervised's re-raise
        would).  Saves the per-frame closure allocations and the
        try/finally machinery on the hot path."""
        return (
            watch is None
            and not FAULTS.is_armed()
            and not el._interrupted.is_set()
            and el.props.get("error-policy", "fail-stop") == "fail-stop"
            and el.props.get("stall-policy", "warn") == "warn"
        )

    def _finish_eos(self, seg: _Seg, st: _ElemState) -> bool:
        """`st.el` consumed EOS on every connected pad: propagate it (or
        terminate the stream when this element is a terminal).  Returns
        False: the element — and, via the inline EOS cascade, everything
        downstream of it in this segment — is done, so the worker
        unwinds."""
        el = st.el
        st.finished = True
        h = self.health_map.get(el.name)
        if h is not None and h.state not in ("degraded", "failed"):
            h.state = "finished"
        if any(p.is_linked for p in el.srcpads):
            for i in range(len(el.srcpads)):
                self._route_one(seg, st, i, EOS())
        else:
            with self._sink_lock:
                self._pending_sinks -= 1
                if self._pending_sinks <= 0:
                    self._sinks_done.set()
            self.post(BusMessage("eos", el.name))
        return False

    def _dispatch(self, seg: _Seg, st: _ElemState, pad: int, item) -> bool:
        """Process one in-band item on `st.el`, inline on the segment's
        streaming thread, with full per-ELEMENT supervision (error-policy,
        watchdog heartbeats, deadline expiry, tracing all attribute to the
        element, not the thread).  Returns False when the worker must exit
        (error recorded, stopping, or the stream finished)."""
        el = st.el
        try:
            if isinstance(item, TensorFrame):
                return self._dispatch_frame(seg, st, pad, item)
            if isinstance(item, CapsEvent):
                el.set_sink_spec(pad, item.spec)
                st.caps_pads.add(pad)
                if st.caps_pads >= st.connected:
                    for i in range(len(el.srcpads)):
                        if not self._route_one(
                                seg, st, i, CapsEvent(el.derive_spec(i))):
                            return False
                return True
            if isinstance(item, EOS):
                st.eos_pads.add(pad)
                outs = (
                    el.handle_eos(pad) if hasattr(el, "handle_eos") else None
                )
                if outs and not self._route_outs(seg, st, list(outs)):
                    return False
                if st.eos_pads >= st.connected:
                    return self._finish_eos(seg, st)
                return True
            if isinstance(item, Flush):
                # drop queued FRAMES only (head mailboxes; fused links
                # hold nothing in flight); events behind the flush must
                # survive in order
                box = el._mailbox
                if box is not None:
                    kept = []
                    try:
                        while True:
                            p2, nxt = box.get_nowait()
                            if not isinstance(nxt, TensorFrame):
                                kept.append((p2, nxt))
                    except queue.Empty:
                        pass
                    for entry in kept:
                        while not self._stop_flag.is_set():
                            try:
                                box.put(entry, timeout=0.1)
                                break
                            except queue.Full:
                                continue
                for sp, ev in el.handle_event(pad, item) or []:
                    self._route_one(seg, st, sp, ev)
                return True
            for sp, ev in el.handle_event(pad, item) or []:  # custom events
                if not self._route_one(seg, st, sp, ev):
                    return False
            return True
        except BaseException as e:  # noqa: BLE001 — worker boundary
            return self._fail(el, e)

    def _dispatch_frame(
        self, seg: _Seg, st: _ElemState, pad: int, frame
    ) -> bool:
        """Run one frame (or non-aware block, split per-frame) through
        `st.el` and route the outputs.  Caller owns `frame`'s carcass."""
        el = st.el
        tracer = self.tracer
        if isinstance(frame, BatchFrame) and not el.BATCH_AWARE:
            # block safety net: per-frame elements get logical frames,
            # never a surprise batch axis; each is supervised INDIVIDUALLY
            # (a batch-call-then-replay would re-run the already-processed
            # prefix on a stateful element)
            t_in = time.perf_counter() if tracer is not None else 0.0
            nlog = frame.batch_size
            nbytes = frame_nbytes(frame) if tracer is not None else 0
            src_ts = (
                frame.meta.get(META_SRC_TS) if tracer is not None else None
            )
            if tracer is not None:
                tracer.frame_begin(el.name, frame)
            lfs = self._expire_late(el, frame.split())
            st.in_call = len(lfs)
            for k in range(len(lfs)):
                lf = lfs[k]
                lfs[k] = None  # release the list's ref for the pool
                if self._fast_path(el, st.watch):
                    outs = el.handle_frame(pad, lf) or []
                else:
                    outs = self._supervised(
                        el,
                        lambda lf=lf, pad=pad: el.handle_frame(pad, lf) or [],
                        lf,
                    )
                    if outs is self._SUPERVISED_STOPPING:
                        return False
                if st.terminal:
                    st.delivered += 1
                # this input frame is consumed: what remains at risk is
                # the unprocessed tail plus this call's unrouted outputs
                remaining = len(lfs) - k - 1
                st.in_call = remaining + self._outs_logical(outs)
                if not self._route_outs(seg, st, outs):
                    return False
                st.in_call = remaining
                FRAME_POOL.recycle(lf)
            if tracer is not None:
                tracer.frame_out(
                    el.name, t_in, time.perf_counter(), nlog, nbytes, src_ts,
                    frame=frame,
                )
            return True
        if not self._expire_late(el, (frame,)):
            return True  # deadline passed: accounted drop (caller recycles)
        st.in_call = getattr(frame, "batch_size", 1)
        t_in = time.perf_counter() if tracer is not None else 0.0
        if tracer is not None:
            tracer.frame_begin(el.name, frame)
        if self._fast_path(el, st.watch):
            outs = el.handle_frame(pad, frame) or []
        else:
            outs = self._supervised(
                el,
                lambda frame=frame, pad=pad: el.handle_frame(pad, frame)
                or [],
                frame,
            )
            if outs is self._SUPERVISED_STOPPING:
                return False
        if st.terminal:
            st.delivered += getattr(frame, "batch_size", 1)
        if tracer is not None:
            tracer.frame_out(
                el.name, t_in, time.perf_counter(),
                getattr(frame, "batch_size", 1),
                frame_nbytes(frame),
                frame.meta.get(META_SRC_TS),
                frame=frame,
            )
        # input consumed (emitted / parked behind pending_frames /
        # delivered): transfer in_call to the unrouted outputs, which
        # _route_outs decrements as each is handed off
        st.in_call = self._outs_logical(outs)
        return self._route_outs(seg, st, outs)

    def _run_segment(self, seg: _Seg) -> None:
        name_os_thread()
        for st in seg.states.values():
            st.watch = self._watches.get(st.el.name)
        head = seg.chain[0]
        if isinstance(head, SourceElement):
            self._run_source(seg)
        else:
            self._guard(head, self._run_chain_head, seg)

    def _run_source(self, seg: _Seg) -> None:
        el = seg.chain[0]
        st = seg.states[el.name]

        def body():
            # deadline QoS stamping (deadline-s prop): every emitted frame
            # carries a latency budget downstream elements honor.  The pts
            # anchor (live playback) is the wall instant of the FIRST
            # frame minus its pts, so frame 0 gets its full budget.
            budget = float(el.props.get("deadline-s") or 0.0)
            pts_anchored = el.props.get("deadline-anchor") == "pts"
            anchor = None
            for i in range(len(el.srcpads)):
                spec = (
                    el.output_spec() if len(el.srcpads) == 1
                    else el.derive_spec(i)
                )
                if not self._route_one(seg, st, i, CapsEvent(spec)):
                    return
            # liveness on sources: the busy window wraps each next() on
            # the frames() generator (and the per-frame fault site), so
            # frame-deadline bounds the gap between productions (a
            # stalled camera/publisher) and stall-timeout catches a
            # producer hung mid-pull.  Downstream routing stays OUTSIDE
            # the window — blocking on backpressure (or a fused
            # downstream element's work) is healthy, not a stall.
            wd, watch = self._watchdog, self._watches.get(el.name)
            frames_it = iter(el.frames())
            owns_drain = getattr(el, "OWNS_DRAIN", False)
            src_pending = getattr(el, "pending_frames", None)
            while True:
                if self._drain_flag.is_set() and not owns_drain and (
                        src_pending is None or src_pending() <= 0):
                    # graceful drain: stop pulling and fall through to
                    # the EOS routing below, flushing everything already
                    # in flight through to the sinks.  A source holding
                    # buffered input (appsrc) reports it via
                    # pending_frames and keeps getting pulled until that
                    # is flushed too; sources that wait INSIDE frames()
                    # additionally poll lifecycle.pipeline_quiescing;
                    # sources with their own drain state machine
                    # (serversrc) opt out via OWNS_DRAIN and end their
                    # stream themselves.
                    break
                if el._interrupted.is_set():
                    # stale interrupt from an escalation whose pull
                    # completed anyway: consume it (see _supervised)
                    el._interrupted.clear()
                if watch is not None:
                    wd.begin(watch)
                try:
                    try:
                        frame = next(frames_it)
                    except StopIteration:
                        break
                    if self._stop_flag.is_set():
                        return
                    if not isinstance(frame, Event) and FAULTS.is_armed():
                        FAULTS.check(f"element.{el.name}.frames",
                                     interrupt=lambda: el.interrupted)
                finally:
                    if watch is not None:
                        # always clears the busy window (also on stream
                        # end), or the sweeper would flag a finished
                        # element's stale episode
                        wd.done(watch)
                if isinstance(frame, Event):
                    outs = el.handle_event(0, frame) or []
                    for sp, ev in outs:
                        if not self._route_one(seg, st, sp, ev):
                            return
                    continue
                if budget > 0:
                    if (pts_anchored and anchor is None
                            and frame.pts is not None):
                        anchor = time.monotonic() - frame.pts
                    stamp_deadline(frame, budget,
                                   anchor=anchor if pts_anchored else None)
                if self.tracer is not None:
                    self.tracer.stamp_source(frame)
                if not self._route_one(seg, st, 0, frame):
                    return
                FRAME_POOL.recycle(frame)
            for i in range(len(el.srcpads)):
                # EOS routing result intentionally unchecked: a fused
                # downstream finishing returns False (normal unwind), and
                # an external push fails only when already stopping
                self._route_one(seg, st, i, EOS())
            h = self.health_map.get(el.name)
            if h is not None and h.state == "running":
                h.state = "finished"

        def supervised_body():
            # source supervision: `restart` re-opens the element (the
            # flaky-camera case) and re-enters frames() from its current
            # state — fresh CapsEvents re-negotiate downstream; frames
            # emitted before the crash are NOT replayed.  `skip` cannot
            # resume a broken generator mid-frame, so sources treat it
            # as fail-stop.  Errors raised by FUSED DOWNSTREAM elements
            # never reach here: _dispatch handles them against their own
            # element and unwinds via a False return.
            while True:
                try:
                    return body()
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as e:  # noqa: BLE001 — policy boundary
                    # a watchdog-interrupted hang (StallError) restarts
                    # under stall-policy=restart even when error-policy
                    # is the fail-stop default — same contract as the
                    # non-source path in _supervised
                    stall_restart = (
                        isinstance(e, StallError)
                        and el.props.get("stall-policy") == "restart")
                    if isinstance(e, StallError):
                        el._interrupted.clear()
                    if (el.props.get("error-policy") != "restart"
                            and not stall_restart):
                        raise
                    from ..core.resilience import is_transient

                    if not is_transient(e):
                        # fatal classification: a deterministic bug a
                        # restart cannot cure — fail fast instead of
                        # crash-looping through the budget (a source has
                        # no input frame to dead-letter)
                        raise
                    verdict = self._restart_element(el, e)
                    if verdict == "retry":
                        continue
                    if verdict == "stopping":
                        return
                    raise

        self._guard(el, supervised_body)

    def _run_chain_head(self, seg: _Seg) -> None:
        el = seg.chain[0]
        st = seg.states[el.name]
        box = el._mailbox
        # hot-loop constants, latched at start() like the mailbox itself
        # (part of the allocation diet: no per-frame getattr/hasattr)
        get_many = getattr(box, "get_many", None)
        has_qsize = hasattr(box, "qsize")
        idle = getattr(el, "handle_idle", None)
        # fused tails with deferred output: today unreachable in practice
        # (parking needs preferred_batch>1, which blocks fusion), but any
        # future element deferring output inside a fused chain must still
        # get its idle flush when the head's input goes quiet
        tail_idles = [
            (seg.states[e.name], e.handle_idle)
            for e in seg.chain[1:]
            if hasattr(e, "handle_idle")
        ]
        want = getattr(el, "preferred_batch", 1)
        batching = want > 1 and hasattr(el, "handle_frame_batch")
        wait_s = getattr(el, "batch_wait_s", 0.0)
        # async device feed: an element holding parked in-flight work
        # (the filter's completion window / staged ingest batch) gets a
        # short mailbox poll so completed batches emit promptly instead
        # of aging up to the full idle period at a live stream's tail
        pending = getattr(el, "pending_frames", None)
        stop_flag = self._stop_flag
        # items popped from the mailbox but not yet processed (bulk pops
        # can pull events/other-pad items past a batch boundary); lives
        # on the segment so halt-time accounting can count it
        stash = seg.stash
        stash.clear()

        def flush_idle() -> bool:
            """Run the idle hooks and route what they release; False when
            the worker must return."""
            if idle is not None:
                outs = idle() or []
                if outs and not self._route_outs(seg, st, outs):
                    return False
            for t_st, t_idle in tail_idles:
                try:
                    t_outs = t_idle() or []
                    if t_outs and not self._route_outs(seg, t_st, t_outs):
                        return False
                except BaseException as e:  # noqa: BLE001
                    self._fail(t_st.el, e)
                    return False
            return True

        while not stop_flag.is_set():
            if stash:
                pad, item = stash.popleft()
            else:
                try:
                    try:
                        # hot path: items queued — no pending_frames()
                        # probe, no lock, no timeout bookkeeping
                        pad, item = box.get_nowait()
                    except queue.Empty:
                        poll = 0.1
                        if pending is not None:
                            try:
                                if pending() > 0:
                                    poll = 0.02
                            except Exception:
                                self.log.exception(
                                    "pending_frames failed for %s", el.name)
                                pending = None
                        pad, item = box.get(timeout=poll)
                except queue.Empty:
                    # idle hook: elements holding deferred output (the
                    # filter's dispatch window) release it when the
                    # input goes quiet — a live stream's tail must not
                    # wait for the next frame or EOS
                    if not flush_idle():
                        return
                    continue
            if item is _STOP:
                return
            if item is WAKE:
                # a thread of the element's own (the slot pump) has output
                # ready: release it now, not at the next poll (a poll's
                # phase against the pump's turn was 0-20 ms of jitter in
                # every token frame's delivery: PERF.md section 5)
                if not flush_idle():
                    return
                continue
            tracer = self.tracer
            if tracer is not None:
                if has_qsize:
                    try:
                        tracer.queue_level(
                            el.name, box.qsize(), getattr(box, "maxsize", 0),
                        )
                    except Exception:
                        self.log.debug(
                            "tracer queue_level failed", exc_info=True)
                if isinstance(item, TensorFrame):
                    # queue-wait histogram: enqueue stamp -> this dequeue
                    # (stash dwell counts too — the frame was waiting)
                    t_q = item.meta.pop(TL_QPUT_META, None)
                    if t_q is not None:
                        tracer.queue_wait(
                            el.name, time.perf_counter() - t_q)
            if batching and isinstance(item, TensorFrame):
                # micro-batching: batch-capable elements drain extra
                # queued frames and process them in one call (the TPU
                # dispatch-amortization lever; no reference analog).
                # batch-timeout > 0 waits to FILL the batch; 0 keeps the
                # lossless drain-what's-queued behavior
                deadline = time.monotonic() + wait_s
                frames = [item]
                # LOGICAL frame count: a block-ingest BatchFrame counts as
                # its batch_size, so max-batch bounds the invoke's batch
                # axis, not the queue-item count
                nlog = getattr(item, "batch_size", 1)
                while nlog < want:
                    # consume stashed items first (a previous bulk pop may
                    # have pulled qualifying frames); an event at the
                    # stash head ends the batch IN PLACE — never rotate
                    # it behind later items
                    if stash:
                        p2, nxt = stash[0]
                        if isinstance(nxt, TensorFrame) and p2 == pad:
                            frames.append(stash.popleft()[1])
                            nlog += getattr(nxt, "batch_size", 1)
                            continue
                        break
                    try:
                        wait = deadline - time.monotonic()
                        if get_many is not None:
                            chunk = get_many(
                                want - nlog, timeout=max(0.0, wait),
                            )
                        elif wait > 0:
                            chunk = [box.get(timeout=wait)]
                        else:
                            chunk = [box.get_nowait()]
                    except queue.Empty:
                        break
                    boundary = False
                    now_q = (
                        time.perf_counter() if tracer is not None else 0.0
                    )
                    for p2, nxt in chunk:
                        if tracer is not None and isinstance(
                                nxt, TensorFrame):
                            t_q = nxt.meta.pop(TL_QPUT_META, None)
                            if t_q is not None:
                                tracer.queue_wait(el.name, now_q - t_q)
                        if (not boundary
                                and isinstance(nxt, TensorFrame)
                                and p2 == pad
                                and nlog < want):
                            # nlog<want re-checked per item: blocks count
                            # as batch_size, so a bulk pop (item-granular)
                            # can overshoot the LOGICAL bound mid-chunk —
                            # the excess stashes for the next micro-batch
                            frames.append(nxt)
                            nlog += getattr(nxt, "batch_size", 1)
                        else:
                            # event/other-pad item ends the batch; it and
                            # everything popped after it run after, in order
                            boundary = True
                            stash.append((p2, nxt))
                    if boundary:
                        break
                if not el.BATCH_AWARE:
                    # same safety net as the per-frame branch: the block
                    # opt-in is BATCH_AWARE, not the mere presence of
                    # handle_frame_batch
                    frames = [
                        lf for f in frames for lf in (
                            f.split() if isinstance(f, BatchFrame)
                            else (f,)
                        )
                    ]
                frames = self._expire_late(el, frames)
                if not frames:
                    continue  # whole micro-batch expired
                st.in_call = sum(
                    getattr(f, "batch_size", 1) for f in frames)
                t_in = time.perf_counter() if tracer is not None else 0.0
                if tracer is not None:
                    tracer.frame_begin(el.name, frames[0])
                outs = self._supervised(
                    el,
                    lambda frames=frames, pad=pad:
                    el.handle_frame_batch(pad, frames) or [],
                    frames,
                    per_item=lambda f, pad=pad: (
                        el.handle_frame_batch(pad, [f]) or []),
                )
                if outs is self._SUPERVISED_STOPPING:
                    return
                if st.terminal:
                    st.delivered += sum(
                        getattr(f, "batch_size", 1) for f in frames)
                if tracer is not None:
                    tracer.frame_out(
                        el.name, t_in, time.perf_counter(),
                        sum(getattr(f, "batch_size", 1) for f in frames),
                        sum(frame_nbytes(f) for f in frames),
                        frames[0].meta.get(META_SRC_TS),
                        frame=frames[0],
                    )
                # inputs consumed (emitted / parked behind the element's
                # pending_frames hook / delivered): in_call transfers to
                # the unrouted outputs so a halt mid-route never
                # double-counts the filter's parked dispatch window
                st.in_call = self._outs_logical(outs)
                if not self._route_outs(seg, st, outs):
                    return
                st.in_call = 0
            else:
                if not self._dispatch(seg, st, pad, item):
                    return
                if isinstance(item, TensorFrame):
                    # the head owns the popped item's carcass once the
                    # dispatch chain is done with it
                    FRAME_POOL.recycle(item)
                if st.finished:
                    return
