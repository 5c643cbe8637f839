"""Element model: the composable unit of a pipeline.

Reference analog: GStreamer GstElement/GstPad conventions as used by the
nnstreamer elements (``gst/nnstreamer/elements/``, registered in
``gst/nnstreamer/registerer/nnstreamer.c:91-122``):

* properties — the reference's entire user API is stringly-typed GObject
  properties embedded in pipeline text; here each Element declares a
  ``PROPERTIES`` table (name -> Property) and values are set/parsed the same
  way from pipeline descriptions.
* pads & negotiation — elements declare how many sink/src pads they expose
  and negotiate schemas by intersection (``accept_spec`` / ``derive_spec``),
  the analog of caps negotiation (fixed at PLAYING transition, reference
  ``tensor_filter.c:1157-1314``).
* processing — 1:1/1:N elements implement ``handle_frame``; N:1 elements get
  a time-sync :class:`~nnstreamer_tpu.core.sync.Collator`; sources implement
  ``frames()``; sinks ``render()``.

TPU-first: elements never copy payloads; they pass numpy/jax arrays through
and are encouraged to express compute as jit-able functions so chains fuse.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple, Union

from ..core.buffer import EOS, CapsEvent, CustomEvent, Event, Flush, TensorFrame
from ..core.liveness import _check_stall_policy
from ..core.log import get_logger
from ..core.types import ANY, StreamSpec


# ---------------------------------------------------------------------------
# Property system (≙ GObject properties)
# ---------------------------------------------------------------------------
# properties every element answers, merged under each class's declared
# PROPERTIES (a class declaring its own wins) — ≙ the reference's
# near-universal GObject props (silent on ~every element)
COMMON_PROPERTIES: Dict[str, "Property"] = {}  # filled after Property def


#: out-of-band mailbox item (``(0, WAKE)``): a thread of the element's own
#: has output ready for ``handle_idle``; the dispatch loop runs the hook at
#: once (:meth:`Element.wake_dispatch`)
WAKE = object()


@dataclass
class Property:
    """Declared element property: type-checked, string-parsable."""

    type: type = str
    default: Any = None
    doc: str = ""
    # optional validator/transformer applied after type conversion
    convert: Optional[Callable[[Any], Any]] = None

    def parse(self, value: Any) -> Any:
        if isinstance(value, str) and self.type is not str:
            if self.type is bool:
                value = value.strip().lower() in ("1", "true", "yes", "on")
            elif self.type in (int, float):
                value = self.type(value)
            elif self.type in (list, tuple):
                value = self.type(
                    s.strip() for s in value.split(",") if s.strip() != ""
                )
        if self.type is not None and value is not None and not isinstance(value, self.type):
            try:
                value = self.type(value)
            except Exception:
                raise ValueError(f"cannot convert {value!r} to {self.type.__name__}")
        return self.convert(value) if self.convert else value


def enum_prop_check(prop: str, *choices: str):
    """Converter factory for enum-valued properties: eager validation so
    a typo fails at set time with a uniform message, not at first use."""
    def convert(v: str) -> str:
        if v not in choices:
            raise ValueError(f"{prop} {v!r} (want {' | '.join(choices)})")
        return v
    return convert


COMMON_PROPERTIES.update({
    # ≙ the reference's universal `silent` prop (e.g. gsttensor_rate.c
    # PROP_SILENT: "Don't produce verbose output"): false lowers this
    # element's logger to DEBUG so per-frame diagnostics stream out
    "silent": Property(bool, True, "false = verbose (debug-level) logging"),
    # supervision (core/resilience.py + the pipeline worker loop): what
    # the scheduler does when THIS element raises while processing a
    # frame.  Events (caps/EOS/flush) always fail-stop — losing one
    # desynchronizes the stream.  See Documentation/resilience.md.
    "error-policy": Property(
        str, "fail-stop",
        "on frame error: fail-stop (kill the pipeline, default) | skip "
        "(drop the poisoned frame to the dead-letter queue, warn on the "
        "bus) | restart (supervisor restarts the element with backoff, "
        "then retries the frame; degrades to fail-stop after "
        "max-restarts)",
        convert=enum_prop_check("error-policy", "fail-stop", "skip", "restart"),
    ),
    "max-restarts": Property(
        int, 3, "restart policy: restarts allowed (within restart-window) "
        "before degrading to fail-stop"),
    "restart-backoff": Property(
        float, 0.05, "restart policy: base backoff seconds (doubles per "
        "restart, capped at 2s)"),
    # always-on contract: a budget that never refills would guarantee
    # eventual degradation — N isolated glitches spread over weeks must
    # not kill the pipeline the way N back-to-back crash-loops should
    "restart-window": Property(
        float, 60.0, "restart policy: seconds of sustained health after "
        "which the restart budget (and backoff) fully refills; 0 = "
        "lifetime budget, never refills"),
    "dead-letter-max": Property(
        int, 16, "skip policy: poisoned frames retained for inspection "
        "(older ones roll off; 0 = count drops but retain nothing; the "
        "drop COUNTER is unbounded)"),
    # liveness (core/liveness.py + the pipeline watchdog): catches the
    # failures that never raise — a silent hang, a frame too late to
    # matter.  See Documentation/resilience.md "Liveness & overload".
    "frame-deadline": Property(
        float, 0.0, "watchdog: max seconds ONE frame call may run before "
        "an overrun is flagged (0 = disabled)"),
    "stall-timeout": Property(
        float, 0.0, "watchdog: seconds with input queued but no frame "
        "completed before a stall is flagged (0 = disabled)"),
    "stall-policy": Property(
        str, "warn",
        "on watchdog stall/overrun: warn (bus warning + health counter) "
        "| restart (interrupt the hung call cooperatively, then the "
        "restart machinery retries the frame) | fail (interrupt + tear "
        "the pipeline down)",
        convert=_check_stall_policy,
    ),
    "late-policy": Property(
        str, "drop",
        "frames carrying an expired deadline (core/liveness.py deadline "
        "QoS): drop (default — dropped before processing, with exact "
        "accounting in health()) | deliver (process regardless)",
        convert=enum_prop_check("late-policy", "drop", "deliver"),
    ),
    # deadline stamping (sources only; ignored elsewhere): every emitted
    # frame gets a latency budget that downstream elements honor
    "deadline-s": Property(
        float, 0.0, "sources: stamp each emitted frame with this latency "
        "budget, seconds (0 = no deadline)"),
    "deadline-anchor": Property(
        str, "arrival",
        "deadline-s anchoring: arrival (wall clock at emission — the "
        "serving contract) | pts (stream epoch + pts — live playback)",
        convert=enum_prop_check("deadline-anchor", "arrival", "pts"),
    ),
})


class ElementError(RuntimeError):
    pass


def parse_host_list(raw: str, owner: str, prop: str) -> List[Tuple[str, int]]:
    """Parse a 'h1:p1,h2:p2' property value into [(host, port), ...].

    Shared by every element exposing a multi-remote list (query client
    ``hosts``, edgesrc ``dest-hosts``) so the syntax and its errors
    cannot drift apart."""
    targets: List[Tuple[str, int]] = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        h, sep, p = part.rpartition(":")
        if not sep or not h or not p.isdigit():
            raise ElementError(
                f"{owner}: bad {prop} entry {part!r} (want host:port)")
        targets.append((h, int(p)))
    if not targets:
        raise ElementError(f"{owner}: {prop} parsed to nothing")
    return targets


# ---------------------------------------------------------------------------
# Element registry (≙ gst element factory names)
# ---------------------------------------------------------------------------
ELEMENT_TYPES: Dict[str, type] = {}


def element(name: str, *aliases: str):
    """Class decorator registering an element factory name."""

    def wrap(cls):
        cls.FACTORY_NAME = name
        for n in (name, *aliases):
            ELEMENT_TYPES[n] = cls
        return cls

    return wrap


def make_element(factory: str, name: Optional[str] = None, **props) -> "Element":
    if factory not in ELEMENT_TYPES:
        raise ElementError(f"no such element factory {factory!r}")
    el = ELEMENT_TYPES[factory](name=name)
    for k, v in props.items():
        el.set_property(k, v)
    return el


# ---------------------------------------------------------------------------
# Pads & links
# ---------------------------------------------------------------------------
class SrcPad:
    """An output pad; delivers items to linked sink pads (fan-out copies ≙ tee)."""

    def __init__(self, owner: "Element", index: int):
        self.owner = owner
        self.index = index
        self.links: List[Tuple["Element", int]] = []
        self.spec: Optional[StreamSpec] = None

    def link(self, sink_element: "Element", sink_pad: int = 0) -> None:
        self.links.append((sink_element, sink_pad))

    def push(self, item: Union[TensorFrame, Event]) -> None:
        for el, pad in self.links:
            el.deliver(pad, item)

    @property
    def is_linked(self) -> bool:
        return bool(self.links)


# ---------------------------------------------------------------------------
# Base element
# ---------------------------------------------------------------------------
class Element:
    """Base pipeline element.

    Subclass contract:
      * class attrs ``NUM_SINK_PADS`` / ``NUM_SRC_PADS`` (``None`` = dynamic,
        request pads created on link).
      * ``PROPERTIES``: dict of declared properties.
      * override ``accept_spec`` (validate/intersect incoming schema per pad),
        ``derive_spec`` (compute output schema), ``handle_frame``,
        ``handle_event``, ``start``/``stop`` as needed.
    """

    #: a BatchFrame (N logical frames, one stream item) reaches this
    #: element whole ONLY when True; otherwise the scheduler splits it
    #: into per-frame calls first.  Opt in when the element either
    #: consumes the batch axis (tensor_filter) or is batch-transparent
    #: (queue/tee/capsfilter) or splits blocks itself (tensor_sink).
    BATCH_AWARE = False

    #: streaming-thread fusion opt-OUT (upstream side): True means this
    #: element never fuses INTO its upstream's thread — it keeps its own
    #: worker and mailbox (and, GStreamer-style, drives its fused
    #: downstream from there).  Set it when the element's semantics NEED
    #: the mailbox: `queue` (the explicit boundary element) and the query
    #: client (which wakes its own worker through it).
    THREAD_BOUNDARY = False

    #: streaming-thread fusion opt-OUT (downstream side): False means
    #: downstream elements never run inline on THIS element's thread.
    #: Set False when the pipeline parallelism below this element is
    #: load-bearing (`tensor_query_serversrc`: admission control's
    #: in-flight window only fills when pull and processing overlap).
    FUSE_DOWNSTREAM = True

    FACTORY_NAME = "element"
    NUM_SINK_PADS: Optional[int] = 1
    NUM_SRC_PADS: Optional[int] = 1
    PROPERTIES: Dict[str, Property] = {}

    def __init__(self, name: Optional[str] = None):
        self.name = name or f"{self.FACTORY_NAME}{id(self) & 0xFFFF}"
        self.log = get_logger(self.name)
        self.props: Dict[str, Any] = {
            **{k: p.default for k, p in COMMON_PROPERTIES.items()},
            **{k: p.default for k, p in self.PROPERTIES.items()},
        }
        # keys set explicitly (pipeline text / API) — lets config-file
        # style bulk application defer to explicit settings
        self._explicit_props: set = set()
        nsrc = self.NUM_SRC_PADS if self.NUM_SRC_PADS is not None else 0
        self.srcpads: List[SrcPad] = [SrcPad(self, i) for i in range(nsrc)]
        self.sink_specs: Dict[int, StreamSpec] = {}
        self._pipeline = None  # set by Pipeline.add
        self._mailbox = None  # set by Pipeline at start for elements w/ sinks
        # liveness: set by the watchdog to cooperatively interrupt a hung
        # call (see `interrupted`); cleared when the stall is handled
        self._interrupted = threading.Event()

    # -- properties ---------------------------------------------------------
    def set_property(self, key: str, value: Any) -> None:
        key = key.replace("_", "-")
        decl = self.PROPERTIES.get(key) or COMMON_PROPERTIES.get(key)
        if decl is None:
            raise ElementError(f"{self.name}: unknown property {key!r}")
        self.props[key] = decl.parse(value)
        self._explicit_props.add(key)
        if key == "silent":
            import logging

            self.log.setLevel(
                logging.NOTSET if self.props[key] else logging.DEBUG
            )

    def get_property(self, key: str) -> Any:
        key = key.replace("_", "-")
        if key not in self.props:
            raise ElementError(f"{self.name}: unknown property {key!r}")
        return self.props[key]

    def _apply_config_file(self) -> None:
        """≙ the reference's filter/decoder `config-file` prop: key=value
        lines become properties; properties set explicitly in the
        pipeline text win.  Elements that declare the prop call this at
        the top of start()."""
        path = self.props.get("config-file", "")
        if not path:
            return
        try:
            with open(path) as f:
                lines = f.read().splitlines()
        except OSError as e:
            raise ElementError(f"{self.name}: config-file: {e}") from None
        for ln, raw in enumerate(lines, 1):
            line = raw.strip()
            # comment lines only — an inline '#' may be part of a value
            # (custom=color:#ff0000, paths), so never truncate mid-line
            if not line or line.startswith("#"):
                continue
            key, sep, value = line.partition("=")
            if not sep:
                raise ElementError(
                    f"{self.name}: config-file {path}:{ln}: expected "
                    f"key=value, got {raw!r}"
                )
            key = key.strip().replace("_", "-")
            if key == "config-file":
                raise ElementError(
                    f"{self.name}: config-file {path}:{ln}: nested "
                    "config-file not allowed"
                )
            if key in self._explicit_props:
                continue
            try:
                self.set_property(key, value.strip())
            except (ElementError, ValueError) as e:
                raise ElementError(
                    f"{self.name}: config-file {path}:{ln}: {e}"
                ) from None
            self._explicit_props.discard(key)  # config values stay soft

    # -- pads ---------------------------------------------------------------
    def request_src_pad(self) -> SrcPad:
        """Create a new src pad (dynamic-src elements: demux/split/tee)."""
        pad = SrcPad(self, len(self.srcpads))
        self.srcpads.append(pad)
        return pad

    def srcpad(self, i: int = 0) -> SrcPad:
        if self.NUM_SRC_PADS is None:
            while len(self.srcpads) <= i:
                self.request_src_pad()
        return self.srcpads[i]

    def link(self, downstream: "Element", src_pad: int = 0, sink_pad: Optional[int] = None) -> "Element":
        """Link this element's src pad to downstream's sink pad; returns
        downstream for chaining: ``a.link(b).link(c)``."""
        if sink_pad is None:
            sink_pad = downstream.next_sink_pad()
        elif downstream.NUM_SINK_PADS is None:
            # explicit pad index on a request-pad element (pbtxt links):
            # keep the allocation counter consistent so num_sink_pads is right
            downstream._next_sink = max(downstream._next_sink, sink_pad + 1)
        self.srcpad(src_pad).link(downstream, sink_pad)
        return downstream

    _next_sink = 0

    def next_sink_pad(self) -> int:
        """Allocate the next sink pad index (N:1 request pads)."""
        if self.NUM_SINK_PADS == 1:
            return 0
        i = self._next_sink
        self._next_sink += 1
        return i

    @property
    def num_sink_pads(self) -> int:
        if self.NUM_SINK_PADS is not None:
            return self.NUM_SINK_PADS
        return max(self._next_sink, 1)

    def wake_dispatch(self) -> None:
        """Any thread: have this element's dispatch thread run its
        ``handle_idle`` hook NOW instead of at its next mailbox poll (a
        thread of the element's own, such as the slot pump, has output
        ready).  A full mailbox holds frames whose handling drains the
        same output, and the poll stays as the fallback."""
        box = self._mailbox
        if box is not None:
            try:
                box.put_nowait((0, WAKE))
            except queue.Full:
                pass

    # -- delivery (called from upstream worker threads) ---------------------
    def deliver(self, pad: int, item: Union[TensorFrame, Event]) -> None:
        assert self._mailbox is not None, f"{self.name} not scheduled"
        put_frame = getattr(self._mailbox, "put_frame", None)
        if put_frame is not None and isinstance(item, TensorFrame):
            put_frame((pad, item))  # leaky mailbox: drop, never block
            return
        # blocking backpressure semantics, expressed as a bounded-wait
        # retry loop so a leaky mailbox (which forbids timeout=None)
        # behaves the same as queue.Queue here; never raises queue.Full
        import queue as _queue

        while True:
            try:
                self._mailbox.put((pad, item), timeout=0.5)
                return
            except _queue.Full:
                continue

    # -- negotiation --------------------------------------------------------
    def accept_spec(self, pad: int, spec: StreamSpec) -> StreamSpec:
        """Validate/refine the incoming schema on `pad`.

        Raise ElementError to reject (negotiation failure)."""
        return spec

    def derive_spec(self, pad: int = 0) -> StreamSpec:
        """Output schema for src pad `pad`, given ``self.sink_specs``."""
        return self.sink_specs.get(0, ANY)

    def set_sink_spec(self, pad: int, spec: StreamSpec) -> None:
        self.sink_specs[pad] = self.accept_spec(pad, spec)

    # -- liveness -----------------------------------------------------------
    @property
    def interrupted(self) -> bool:
        """True when the watchdog (stall-policy escalation) or pipeline
        stop wants this element's current call to give up NOW.

        The cooperative-interruption contract: element code doing long
        waits or chunked work should poll this between steps and raise
        :class:`~nnstreamer_tpu.core.liveness.StallError` (or simply
        return) when set — a hung Python call cannot be killed from
        outside, so liveness restart/fail escalation only works for
        calls that cooperate.  Injected ``hang=`` faults poll it."""
        if self._interrupted.is_set():
            return True
        p = self._pipeline
        return p is not None and p._stop_flag.is_set()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        """Transition to running (open models, allocate state)."""

    def stop(self) -> None:
        """Release resources."""

    # -- processing ---------------------------------------------------------
    def handle_frame(
        self, pad: int, frame: TensorFrame
    ) -> Iterable[Tuple[int, TensorFrame]]:
        """Process one frame from sink pad `pad`; yield (src_pad, frame)."""
        return [(0, frame)]

    def handle_event(self, pad: int, event: Event) -> Iterable[Tuple[int, Event]]:
        """Process an in-band event; default: forward to all src pads once
        (EOS aggregation across sink pads is handled by the scheduler)."""
        return [(i, event) for i in range(len(self.srcpads))]

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


class SourceElement(Element):
    """Element with no sink pads; produces frames from ``frames()``."""

    NUM_SINK_PADS = 0

    def frames(self) -> Iterator[TensorFrame]:
        raise NotImplementedError

    def output_spec(self) -> StreamSpec:
        """Schema this source produces (sent as CapsEvent before data)."""
        return ANY


class SinkElement(Element):
    """Element with no src pads; consumes frames via ``render()``."""

    NUM_SRC_PADS = 0
    # non-aware sinks receive logical frames (the scheduler splits blocks)

    def render(self, frame: TensorFrame) -> None:
        raise NotImplementedError

    def handle_frame(self, pad, frame):
        self.render(frame)
        return []


class TransformElement(Element):
    """1:1 element transforming each frame (≙ GstBaseTransform)."""

    def transform(self, frame: TensorFrame) -> Optional[TensorFrame]:
        raise NotImplementedError

    def handle_frame(self, pad, frame):
        out = self.transform(frame)
        return [] if out is None else [(0, out)]
