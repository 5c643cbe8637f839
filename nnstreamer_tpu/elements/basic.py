"""Basic source/sink/utility elements.

Reference analogs: appsrc/videotestsrc (GStreamer core sources used by every
nnstreamer example pipeline), ``tensor_sink`` (appsink-like terminal with
``new-data`` signals — ``gst/nnstreamer/elements/gsttensor_sink.c``),
``queue`` (thread boundary; here every element already has a thread so it
only sets mailbox depth), ``tee`` (fan-out), capsfilter (schema constraint),
``join`` (N:1 first-come forwarding — ``gst/join/gstjoin.c``).
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from fractions import Fraction
from typing import Any, Callable, Iterator, List, Optional, Sequence

import numpy as np

from ..core.buffer import FRAME_POOL, BatchFrame, TensorFrame
from ..core.telemetry import TRACE_ID_META, new_trace_id
from ..core.tracer import record, span
from ..core.types import ANY, FORMAT_STATIC, StreamSpec, TensorSpec
from ..pipeline.element import (
    Element,
    ElementError,
    Property,
    SinkElement,
    SourceElement,
    TransformElement,
    element,
)



def _frame_interval(framerate: str) -> float:
    """Seconds per frame from an "n/d" framerate string ("30" == "30/1")."""
    n, _, d = framerate.partition("/")
    return float(Fraction(int(d or 1), int(n)))

@element("appsrc")
class AppSrc(SourceElement):
    """Push-model source: the application feeds frames via ``push()``.

    ≙ GStreamer appsrc, the standard way tests/apps inject data.
    """

    PROPERTIES = {
        "max-buffers": Property(int, 64, "internal queue depth"),
        "framerate": Property(str, "", "n/d framerate stamped on frames without pts"),
    }

    @staticmethod
    def _make_queue(depth: int):
        # native condvar mailbox when built (GIL-released blocking puts,
        # bulk drain in frames()); stdlib queue otherwise
        from ..native.runtime import NativeMailbox, available

        if available():
            return NativeMailbox(depth)
        return _queue.Queue(maxsize=depth)

    def __init__(self, name=None):
        super().__init__(name)
        self._q = self._make_queue(self.PROPERTIES["max-buffers"].default)
        self._spec: StreamSpec = ANY
        self._count = 0
        # logical frames pushed/popped — two single-writer counters (app
        # thread / streaming thread), no lock: pending_frames() drives
        # graceful-drain flushing and exact dropped accounting
        self._pushed_logical = 0
        self._popped_logical = 0

    def pending_frames(self) -> int:
        """Logical frames pushed but not yet pulled into the stream
        (drain flushes these; an immediate stop abandons them)."""
        return max(0, self._pushed_logical - self._popped_logical)

    def health_info(self) -> dict:
        """Ingest-buffer depth merged into ``Pipeline.health()`` (and the
        telemetry registry as ``nns.source.pending``)."""
        return {"pending_frames": self.pending_frames()}

    def start(self):
        # honor max-buffers: a full queue blocks push() — backpressure
        # reaches the producer (≙ appsrc max-buffers/block)
        depth = int(self.props["max-buffers"])
        if self._q.maxsize != depth and self._q.empty():
            self._q = self._make_queue(depth)

    def set_spec(self, spec: StreamSpec) -> None:
        self._spec = spec

    def output_spec(self) -> StreamSpec:
        return self._spec

    def _offer(self, frame: TensorFrame, sp):
        """Hand ``frame`` to the stream from inside its push span.  No
        profiler session: the plain blocking put.  Live: the frame gets
        its request id here (the source mints it), and a FULL queue
        returns the id instead of sleeping inside the span — the caller
        finishes with :meth:`_put_blocked` once the span has closed, so
        the span is the push's work and the sleep is recorded apart."""
        if not sp.live:
            self._q.put(frame)
            return None
        rid = frame.meta.setdefault(TRACE_ID_META, new_trace_id())
        sp.set(request=rid)
        try:
            self._q.put_nowait(frame)
            return None
        except _queue.Full:
            return rid

    def _put_blocked(self, frame: TensorFrame, rid) -> None:
        t0 = time.perf_counter()
        self._q.put(frame)
        record("nns.appsrc.blocked", t0, time.perf_counter(), request=rid)

    def push(self, frame_or_arrays: Any, pts: Optional[float] = None) -> None:
        with span("nns.appsrc.push") as sp:
            if isinstance(frame_or_arrays, TensorFrame):
                frame = frame_or_arrays
            else:
                arrays = (
                    list(frame_or_arrays)
                    if isinstance(frame_or_arrays, (list, tuple))
                    else [frame_or_arrays]
                )
                # keep device arrays (jax.Array) as-is — zero-copy into
                # the stream
                frame = TensorFrame(
                    [a if hasattr(a, "shape") else np.asarray(a)
                     for a in arrays],
                    pts=pts,
                )
            if frame.pts is None:
                fr = self.props["framerate"]
                if fr:
                    frame.pts = self._count * _frame_interval(fr)
            self._count += 1
            # a pushed frame may itself be a BatchFrame (N logical
            # frames): count what the pop side will count or
            # pending_frames() skews
            self._pushed_logical += getattr(frame, "batch_size", 1)
            rid = self._offer(frame, sp)
        if rid is not None:
            self._put_blocked(frame, rid)

    def push_block(
        self, arrays: Any, pts: Optional[Sequence[Optional[float]]] = None
    ) -> None:
        """Push N logical frames as ONE pre-batched stream item.

        ``arrays`` is a tensor (or list of tensors) whose LEADING axis is
        the frame axis — the block travels the pipeline as a single
        :class:`BatchFrame`, so per-frame mailbox/stacking costs are paid
        once per block instead of once per frame (≙ the reference
        converter's ``frames-per-tensor`` batching,
        gsttensor_converter.c frames-per-tensor).  Batch-capable elements
        (tensor_filter micro-batching, fused decoders) consume the batch
        axis directly; sinks and decoders split it back out.  Other
        per-frame elements (transform/if/...) are NOT batch-aware — feed
        blocks straight into a tensor_filter, or keep per-frame pushes
        when such an element sits upstream of it."""
        tensors = (
            list(arrays) if isinstance(arrays, (list, tuple)) else [arrays]
        )
        tensors = [t if hasattr(t, "shape") else np.asarray(t) for t in tensors]
        n = int(tensors[0].shape[0])
        for t in tensors[1:]:
            if int(t.shape[0]) != n:
                raise ValueError(
                    f"push_block: tensors disagree on the frame axis "
                    f"({n} vs {int(t.shape[0])})"
                )
        if pts is not None and len(pts) != n:
            raise ValueError(
                f"push_block: {len(pts)} pts for {n} frames — a mismatched "
                "frames_info silently misaligns rows downstream"
            )
        if n == 0:
            return  # a VALID empty block carries no frames: explicit no-op
        if pts is None:
            fr = self.props["framerate"]
            if fr:
                dt = _frame_interval(fr)
                pts = [(self._count + i) * dt for i in range(n)]
            else:
                pts = [None] * n
        frame = BatchFrame(
            tensors=tensors,
            pts=pts[0],
            frames_info=[(p, None, {}) for p in pts],
        )
        self._count += n
        self._pushed_logical += n
        with span("nns.appsrc.push") as sp:
            rid = self._offer(frame, sp)
        if rid is not None:
            self._put_blocked(frame, rid)

    def push_event(self, event) -> None:
        """Queue an out-of-band event into the stream in arrival order
        (e.g. ``CustomEvent("reload-model", {...})`` ≙ RELOAD_MODEL)."""
        self._q.put(event)

    def end_of_stream(self) -> None:
        self._q.put(None)

    def frames(self) -> Iterator[TensorFrame]:
        get_many = getattr(self._q, "get_many", None)
        while True:
            try:
                if get_many is not None:
                    # bulk drain: one native call per burst, not per frame
                    items = get_many(32, timeout=0.1)
                else:
                    items = [self._q.get(timeout=0.1)]
            except _queue.Empty:
                # stay responsive to pipeline stop/drain while idle
                from ..core.lifecycle import pipeline_quiescing

                p = self._pipeline
                if p is not None and p._stop_flag.is_set():
                    return
                # graceful drain must flush frames already pushed: a
                # push can land between the Empty above and the flag
                # check, so only end the stream once pending_frames()
                # confirms nothing is held (push() bumps the counter
                # BEFORE enqueuing, making this re-check sufficient)
                if pipeline_quiescing(self) and self.pending_frames() <= 0:
                    return
                continue
            for item in items:
                if item is None:
                    return
                if isinstance(item, TensorFrame):
                    self._popped_logical += getattr(item, "batch_size", 1)
                yield item


@element("videotestsrc")
class VideoTestSrc(SourceElement):
    """Synthetic video source (≙ gst videotestsrc as used in reference SSAT
    tests): deterministic RGB pattern frames."""

    PROPERTIES = {
        "num-buffers": Property(int, 10, "number of frames to emit (-1 = unlimited)"),
        "width": Property(int, 224),
        "height": Property(int, 224),
        "framerate": Property(str, "30/1"),
        "pattern": Property(str, "gradient", "gradient|solid|random"),
        "seed": Property(int, 0),
    }

    def output_spec(self) -> StreamSpec:
        h, w = self.props["height"], self.props["width"]
        n, _, d = self.props["framerate"].partition("/")
        return StreamSpec(
            (TensorSpec((h, w, 3), np.uint8, "video"),),
            FORMAT_STATIC,
            Fraction(int(n), int(d or 1)),
        )

    def frames(self) -> Iterator[TensorFrame]:
        h, w = self.props["height"], self.props["width"]
        dt = _frame_interval(self.props["framerate"])
        rng = np.random.default_rng(self.props["seed"])
        count = self.props["num-buffers"]
        i = 0
        while count < 0 or i < count:
            if self.props["pattern"] == "random":
                img = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
            elif self.props["pattern"] == "solid":
                img = np.full((h, w, 3), (i * 8) % 256, np.uint8)
            else:  # gradient, phase-shifted per frame
                row = (np.arange(w, dtype=np.uint32) * 255 // max(w - 1, 1) + i * 3) % 256
                img = np.broadcast_to(row[None, :, None], (h, w, 3)).astype(np.uint8)
            yield TensorFrame([img], pts=i * dt, duration=dt)
            i += 1


@element("tensor_sink", "appsink")
class TensorSink(SinkElement):
    """Terminal sink emitting new-data callbacks and retaining frames.

    ≙ ``tensor_sink`` (gsttensor_sink.c): signals new-data/eos, property to
    cap retained frames.
    """

    BATCH_AWARE = True  # splits blocks itself (split-batches prop)

    PROPERTIES = {
        "max-stored": Property(int, 0, "retain at most N frames (0 = all)"),
        "to-host": Property(bool, True, "materialize device arrays on render"),
        "max-buffers": Property(int, 0, "mailbox depth override"),
        "split-batches": Property(
            bool, True,
            "fan incoming BatchFrames back out to per-frame callbacks "
            "(false = deliver the block whole; callbacks check batch_size)",
        ),
        # ≙ gsttensor_sink.c props: gate/throttle the new-data signal
        # (frames are still stored either way)
        "emit-signal": Property(bool, True, "emit new-data callbacks"),
        "signal-rate": Property(
            int, 0, "max new-data callbacks per second (0 = every frame)"
        ),
    }

    def __init__(self, name=None):
        super().__init__(name)
        self.frames: List[TensorFrame] = []
        self._callbacks: List[Callable[[TensorFrame], None]] = []
        self.eos_received = threading.Event()
        self._last_signal_ts = 0.0
        # logical frames rendered (single-writer: the sink's streaming
        # thread) — the terminal-delivery counter telemetry exports
        self._rendered = 0

    def connect_new_data(self, cb: Callable[[TensorFrame], None]) -> None:
        self._callbacks.append(cb)

    def health_info(self) -> dict:
        """Delivery counter merged into ``Pipeline.health()`` (and the
        telemetry registry as ``nns.sink.rendered``)."""
        return {"rendered_frames": self._rendered}

    def render(self, frame: TensorFrame) -> None:
        if isinstance(frame, BatchFrame) and self.props["split-batches"]:
            # batch-through chains end here: fan the micro-batch back out
            # so callbacks/stored frames see per-frame granularity
            # (split-batches=false delivers the block whole — at chip-rate
            # streams the per-frame fan-out is itself the bottleneck)
            for f in frame.split():
                self.render(f)
            return
        with span("nns.sink.render") as sp:
            if sp.live:
                sp.set(request=frame.meta.get(TRACE_ID_META))
            self._deliver(frame)

    def _deliver(self, frame: TensorFrame) -> None:
        if self.props["to-host"]:
            frame = frame.to_host()
        self._rendered += getattr(frame, "batch_size", 1)
        limit = self.props["max-stored"]
        self.frames.append(frame)
        if limit and len(self.frames) > limit:
            evicted = self.frames.pop(0)
            # frame-pool recycling: the sink is the end of most frames'
            # lives; the refcount guard refuses frames a callback retained
            FRAME_POOL.recycle(evicted)
        if not self.props["emit-signal"]:
            return
        rate = self.props["signal-rate"]
        if rate > 0:
            now = time.monotonic()
            if now - self._last_signal_ts < 1.0 / rate:
                return
            self._last_signal_ts = now
        for cb in self._callbacks:
            cb(frame)

    def handle_eos(self, pad):
        # the scheduler routes EOS here (not handle_event)
        self.eos_received.set()
        return []


@element("queue")
class Queue(TransformElement):
    """Thread-boundary element (≙ GstQueue): the explicit way to break a
    fused streaming thread.  A linear chain shares ONE worker thread under
    the scheduler's fusion pass; inserting `queue` ends the segment, giving
    the downstream half its own thread and a bounded mailbox — use it where
    pipeline parallelism pays (a slow stage that should overlap its
    neighbors).  Also sets the buffering depth (`max-buffers` maps to the
    mailbox size) and provides the live-pipeline ``leaky`` modes (≙
    GstQueue leaky): a full queue then DROPS frames instead of blocking the
    producer — ``leaky=upstream`` drops the incoming frame,
    ``leaky=downstream`` drops the oldest queued frame.  Events are never
    dropped."""

    BATCH_AWARE = True  # batch-transparent pass-through
    THREAD_BOUNDARY = True  # the explicit fusion boundary

    PROPERTIES = {
        "max-buffers": Property(int, 16, "bounded queue depth (backpressure)"),
        "leaky": Property(
            str, "",
            "''|no|upstream|downstream — full queue drops frames instead "
            "of blocking (upstream: incoming; downstream: oldest)",
        ),
    }

    def start(self):
        mode = (self.props["leaky"] or "no").lower()
        if mode not in ("", "no", "upstream", "downstream"):
            from ..pipeline.element import ElementError

            raise ElementError(
                f"{self.name}: leaky must be ''|no|upstream|downstream, "
                f"got {self.props['leaky']!r}"
            )

    @property
    def leaky_policy(self) -> str:
        mode = (self.props["leaky"] or "no").lower()
        return "" if mode in ("", "no") else mode

    def transform(self, frame):
        return frame


@element("identity")
class Identity(TransformElement):
    BATCH_AWARE = True  # batch-transparent; sleep scales per logical frame

    PROPERTIES = {
        "sleep": Property(float, 0.0, "artificial per-frame delay, seconds (tests)"),
    }

    def transform(self, frame):
        if self.props["sleep"]:
            time.sleep(
                self.props["sleep"] * getattr(frame, "batch_size", 1)
            )
        return frame


@element("tee")
class Tee(Element):
    """1:N fan-out; frames are pushed to every linked src pad (payloads are
    shared, not copied — downstream must not mutate in place)."""

    BATCH_AWARE = True  # batch-transparent fan-out

    NUM_SRC_PADS = None  # request pads

    def derive_spec(self, pad=0):
        return self.sink_specs.get(0, ANY)

    def handle_frame(self, pad, frame):
        return [(i, frame) for i in range(len(self.srcpads))]


@element("capsfilter")
class CapsFilter(TransformElement):
    """Constrain the stream schema (≙ capsfilter with other/tensors caps).

    The parser creates one for bare schema strings between ``!`` links.
    """

    BATCH_AWARE = True  # batch-transparent

    PROPERTIES = {"caps": Property(str, "", "tensors schema string")}

    def _target(self) -> StreamSpec:
        text = self.props["caps"]
        return StreamSpec.from_string(text) if text else ANY

    def accept_spec(self, pad, spec):
        merged = self._target().intersect(spec)
        if merged is None:
            raise ElementError(
                f"{self.name}: schema {spec.to_string()} does not satisfy {self.props['caps']}"
            )
        return merged

    def derive_spec(self, pad=0):
        return self.sink_specs.get(0, self._target())

    def transform(self, frame):
        return frame


@element("join")
class Join(Element):
    """N:1 first-come forwarding without synchronization.

    ≙ ``gst/join/gstjoin.c``: whichever sink pad receives data first pushes
    through; no collation.
    """

    BATCH_AWARE = True  # batch-transparent forwarding

    NUM_SINK_PADS = None

    def derive_spec(self, pad=0):
        for spec in self.sink_specs.values():
            return spec
        return ANY

    def handle_frame(self, pad, frame):
        return [(0, frame)]

    def handle_eos(self, pad):
        return []  # scheduler emits EOS when all pads end
