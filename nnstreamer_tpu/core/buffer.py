"""Stream payload and event objects.

Reference analogs:

- ``TensorFrame`` ≙ a GstBuffer holding up to 256 GstMemory tensor chunks plus
  pts/dts/duration timestamps (reference
  ``gst/nnstreamer/nnstreamer_plugin_api_impl.c:1541`` nth-memory access).
- ``meta`` dict ≙ GstMeta attachments; key ``"client_id"`` mirrors the query
  meta that routes answers back to the right client
  (reference ``gst/nnstreamer/tensor_meta.c``).
- Event classes ≙ GstEvent EOS / FLUSH / SEGMENT / CAPS.

TPU-first notes: tensor payloads may be numpy arrays *or* ``jax.Array``s —
elements that chain JAX computation keep data on device between elements
(the zero-copy analog of mapped GstMemory), and only sinks/serializers pull
to host.
"""

from __future__ import annotations

import itertools
import os
import sys
import time
from collections import deque as _deque
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .tracer import span
from .types import StreamSpec, TensorSpec, FORMAT_STATIC

# monotonic frame sequence for debugging/tracing
_seq = itertools.count()


@dataclass
class TensorFrame:
    """One frame of a tensor stream: N tensors + timestamps + metadata."""

    tensors: List[Any]  # np.ndarray | jax.Array, len <= TENSOR_COUNT_LIMIT
    pts: Optional[float] = None  # presentation timestamp, seconds
    duration: Optional[float] = None
    meta: Dict[str, Any] = field(default_factory=dict)
    seq: int = field(default_factory=lambda: next(_seq))

    def __len__(self) -> int:
        return len(self.tensors)

    def nth(self, i: int):
        """Reference: gst_tensor_buffer_get_nth_memory."""
        return self.tensors[i]

    def pick(self, indices: Sequence[int]) -> "TensorFrame":
        """input-combination / tensorpick subset-reorder."""
        return replace(
            self,
            tensors=[self.tensors[i] for i in indices],
            meta=dict(self.meta),
        )

    def with_tensors(self, tensors: Sequence[Any]) -> "TensorFrame":
        """New frame with same timestamps, COPIED meta, different payload.

        Meta is copied, not aliased: derived frames get stamped with new
        keys by decoders/elements, and a tee sibling sharing the source
        frame must never see those (the payload-sharing contract covers
        tensors only)."""
        return replace(self, tensors=list(tensors), meta=dict(self.meta))

    def spec(self) -> StreamSpec:
        """Derive the concrete schema of this frame."""
        return StreamSpec(
            tuple(TensorSpec(tuple(t.shape), np.dtype(t.dtype)) for t in self.tensors),
            FORMAT_STATIC,
        )

    def nbytes(self) -> int:
        return sum(int(np.prod(t.shape)) * np.dtype(t.dtype).itemsize for t in self.tensors)

    def to_host(self) -> "TensorFrame":
        """Materialize all payloads as numpy arrays (device -> host),
        overlapping the per-tensor transfers (see :func:`materialize`).
        Already-host frames return self — the common sink-side case must
        not pay a per-frame dataclass copy."""
        if all(type(t) is np.ndarray for t in self.tensors):
            return self
        return self.with_tensors(materialize(self.tensors))


@dataclass
class BatchFrame(TensorFrame):
    """A micro-batch travelling as ONE stream item: every tensor has a
    leading batch axis; ``frames_info`` keeps the per-logical-frame
    (pts, duration, meta) so the batch can be split back losslessly.

    TPU-first rationale (no reference analog): per-frame Python dispatch
    caps throughput long before the MXU does, so batch-capable element
    chains (filter -> fused decoder -> sink) move whole micro-batches —
    usually still device-resident — and split only at a host boundary.
    Produced by tensor_filter in batch-through mode and by block ingest
    (``AppSrc.push_block`` / converter ``emit-blocks``).  ``with_tensors``/
    ``pick`` preserve the subclass (dataclasses.replace), but delivery of
    a WHOLE block to an element additionally requires that element to set
    ``Element.BATCH_AWARE = True`` — the scheduler splits blocks into
    logical frames before anything else (per-frame semantics are the
    default; the batch fast path is an opt-in).  Sinks/decoders split via
    :meth:`split`.
    """

    frames_info: List[Tuple[Optional[float], Optional[float], Dict[str, Any]]] = field(
        default_factory=list
    )

    @property
    def batch_size(self) -> int:
        return len(self.frames_info)

    @classmethod
    def from_frames(
        cls, tensors: Sequence[Any], frames: Sequence[TensorFrame]
    ) -> "BatchFrame":
        first = frames[0]
        return cls(
            tensors=list(tensors),
            pts=first.pts,
            duration=first.duration,
            meta=dict(first.meta),
            frames_info=[(f.pts, f.duration, f.meta) for f in frames],
        )

    def split(self) -> List[TensorFrame]:
        """Materialize on host and fan back out into per-frame views.
        Per-frame wrappers come from the frame pool (the split fan-out is
        the hottest frame allocator at chip-rate streams)."""
        # the host boundary of a device-resident batch: ready-wait and
        # device-to-host, apart from the fan-out that follows
        with span("nns.batch.materialize"):
            mats = materialize(self.tensors)
        acquire = FRAME_POOL.acquire
        return [
            acquire([m[b] for m in mats], pts=p, duration=d, meta=dict(fm))
            for b, (p, d, fm) in enumerate(self.frames_info)
        ]


def start_host_copies(tensors: Sequence[Any]) -> None:
    """Kick off async device->host copies for every device tensor (no-op
    for host arrays).  Callers that park outputs (the filter's dispatch
    window) call this at park time so the transfer overlaps later
    compute; :func:`materialize` calls it so N outputs cost ~one round
    trip instead of N serialized ones."""
    for t in tensors:
        start = getattr(t, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # allow-silent: prefetch hint only
                pass  # stale/donated buffer: np.asarray later decides


def materialize(tensors: Sequence[Any]) -> List[np.ndarray]:
    """Bring a tensor list to host, overlapping the transfers.

    All device tensors start their device->host copies ASYNC before any
    is awaited: on a latency-bound link (a PCIe queue) N
    outputs cost ~one round trip instead of N serialized ones — a hidden
    per-batch cost on every host boundary (BatchFrame.split, the unfused
    micro-batch path, sinks)."""
    start_host_copies(tensors)
    return [np.asarray(t) for t in tensors]


# ---------------------------------------------------------------------------
# Frame pool (hot-path allocation diet)
# ---------------------------------------------------------------------------
class FramePool:
    """Free-list of TensorFrame/BatchFrame carcasses.

    At chip-rate streams the per-frame wrapper objects (dataclass
    instance, meta dict, seq counter) are real scheduler overhead: every
    split/emit allocates one and every sink/drop frees one, thousands of
    times per second.  The pool recycles the *wrapper only* — payload
    tensors and meta dicts are dropped at recycle time so nothing large is
    ever pinned by the free list.

    Safety contract: :meth:`recycle` accepts a frame ONLY when the caller
    provably holds the last reference (``sys.getrefcount`` guard), so a
    frame retained by an element (``tensor_if`` previous-frame cache, a
    sink's stored frames, an application callback) can never be reused
    under its holder.  Call it with at most one local binding:
    ``pool.recycle(f)``.  Both sides are GIL-atomic (deque append/pop), so
    any worker thread may acquire/recycle concurrently.

    ``NNS_FRAME_POOL`` sizes the default pool (frames retained per class;
    0 disables recycling entirely)."""

    __slots__ = (
        "_free", "_free_batch", "_max_refs", "enabled", "reused", "recycled",
    )

    def _probe_refs(self, x) -> int:
        """Observed refcount of an object held by exactly one caller local,
        seen from inside a method call — the method-call machinery's
        contribution varies across CPython versions (3.10 keeps an extra
        stack reference), so the recycle threshold is calibrated, not
        assumed."""
        return sys.getrefcount(x)

    def __init__(self, maxsize: int = 1024):
        self._free: _deque = _deque(maxlen=max(0, maxsize))
        self._free_batch: _deque = _deque(maxlen=max(0, maxsize // 8))
        self.enabled = maxsize > 0
        probe = object()
        self._max_refs = self._probe_refs(probe)
        # stats (racy best-effort counters; tests/monitoring only)
        self.reused = 0
        self.recycled = 0

    def acquire(
        self,
        tensors: List[Any],
        pts: Optional[float] = None,
        duration: Optional[float] = None,
        meta: Optional[Dict[str, Any]] = None,
    ) -> "TensorFrame":
        """A TensorFrame with the given payload: recycled when a carcass
        is free, freshly constructed otherwise.  Same signature/cost
        either way; ``seq`` is always fresh."""
        try:
            f = self._free.pop()
        except IndexError:
            return TensorFrame(
                tensors, pts=pts, duration=duration,
                meta={} if meta is None else meta,
            )
        f.tensors = tensors
        f.pts = pts
        f.duration = duration
        f.meta = {} if meta is None else meta
        f.seq = next(_seq)
        self.reused += 1
        return f

    def acquire_batch(
        self,
        tensors: List[Any],
        pts: Optional[float] = None,
        duration: Optional[float] = None,
        meta: Optional[Dict[str, Any]] = None,
        frames_info: Optional[List] = None,
    ) -> "BatchFrame":
        try:
            f = self._free_batch.pop()
        except IndexError:
            return BatchFrame(
                tensors, pts=pts, duration=duration,
                meta={} if meta is None else meta,
                frames_info=frames_info or [],
            )
        f.tensors = tensors
        f.pts = pts
        f.duration = duration
        f.meta = {} if meta is None else meta
        f.frames_info = frames_info or []
        f.seq = next(_seq)
        self.reused += 1
        return f

    def recycle(self, frame: Any) -> bool:
        """Return ``frame``'s carcass to the free list iff the caller holds
        the only remaining reference; payload/meta references are dropped
        immediately either way the frame is accepted.  Safe to call
        speculatively — a still-referenced or foreign object is refused."""
        if not self.enabled:
            return False
        t = type(frame)  # exact types only: subclasses own extra state
        if t is TensorFrame:
            if sys.getrefcount(frame) > self._max_refs:
                return False
            frame.tensors = None  # type: ignore[assignment] — re-set on acquire
            frame.meta = None  # type: ignore[assignment]
            frame.pts = frame.duration = None
            self._free.append(frame)
        elif t is BatchFrame:
            if sys.getrefcount(frame) > self._max_refs:
                return False
            frame.tensors = None  # type: ignore[assignment]
            frame.meta = None  # type: ignore[assignment]
            frame.frames_info = None  # type: ignore[assignment]
            frame.pts = frame.duration = None
            self._free_batch.append(frame)
        else:
            return False
        self.recycled += 1
        return True

    def trim(self) -> int:
        """Drop every retained carcass (memory-pressure relief valve —
        the watermark monitor calls this at the high watermark).  The
        pool keeps recycling afterwards; returns the carcasses freed."""
        n = len(self._free) + len(self._free_batch)
        self._free.clear()
        self._free_batch.clear()
        return n


#: process-wide default pool used by the scheduler dispatch loop,
#: BatchFrame.split, and tensor_filter's batch emitter
FRAME_POOL = FramePool(int(os.environ.get("NNS_FRAME_POOL", "1024")))


# ---------------------------------------------------------------------------
# Device/staging buffer pool (async device feed — zero-alloc steady state)
# ---------------------------------------------------------------------------
class DeviceBufferPool:
    """Free-list of STAGING buffers keyed by ``(shape, dtype, placement)``.

    The host->device ingest lane stacks every micro-batch into a host
    staging array before the transfer; allocating that array per batch is
    a steady hidden cost (a 128x224x224x3 uint8 batch is ~19 MB of fresh
    pages per invoke) and, on platforms with pinned-host staging, defeats
    transfer pinning entirely.  This pool keeps a small ring per
    (shape, dtype) so steady-state serving reuses the same buffers —
    together with XLA buffer donation on the jax-xla invoke path
    (``invoke_batch_donated``) the hot loop performs zero per-batch
    allocations once warm.

    Ownership contract: a buffer acquired here is exclusively the
    caller's until ``release()``.  Callers must release only when nothing
    can still read the memory — the filter releases a staging buffer when
    the batch it carried has been *emitted* (outputs materialized), which
    is strictly after any async transfer/compute consuming it finished.
    ``release()`` on a foreign array is accepted (it just joins the pool
    under its own key) but the double-release of a buffer still in use is
    the caller's bug — never release early.

    Placement domains: ``acquire``/``release`` take an optional hashable
    ``placement`` token (``FilterBackend.staging_placement()`` — a device
    ordinal, a mesh spec) that joins the ring key, so a buffer staged for
    one placement is never recycled into a caller staging for another.
    Shape+dtype alone is NOT an identity once meshes exist: a replicated
    carcass handed to a dp-sharded caller would be re-placed with the
    wrong scatter (and, on platforms with pinned-host staging, carry the
    wrong pinning).  Callers must pass the SAME token to release that
    they acquired under — the ring key is derived per call, not stored
    on the buffer.

    Key-space bound: the ring DICT itself is LRU-bounded at
    ``MAX_KEYS`` distinct ``(shape, dtype, placement)`` keys — a
    flexible-shape or mesh-config sweep mints a fresh key per
    configuration and each ring pins full-size staging buffers, the
    same slow-leak class the jit-cache LRU bounds (an evicted ring just
    re-allocates on next use).  ``rings_evicted`` counts dropped rings
    so truncation is never silent.

    Thread-safe; counters (``allocated``/``reused``) are exact under the
    lock and drive the perf smoke's reuse-rate floor.
    """

    __slots__ = ("_free", "_lock", "_max_per_key", "enabled",
                 "allocated", "reused", "rings_evicted", "trims")

    #: max distinct (shape, dtype, placement) rings kept live (LRU)
    MAX_KEYS = 32

    def __init__(self, max_per_key: int = 8):
        import threading
        from collections import OrderedDict

        self._free: "OrderedDict[Tuple, List[np.ndarray]]" = OrderedDict()
        self._lock = threading.Lock()
        self._max_per_key = max(0, max_per_key)
        self.enabled = self._max_per_key > 0
        self.allocated = 0
        self.reused = 0
        self.rings_evicted = 0  # whole rings dropped by the key LRU
        self.trims = 0          # memory-pressure trim() calls

    @staticmethod
    def _key(shape, dtype, placement=None) -> Tuple:
        return (tuple(int(d) for d in shape), np.dtype(dtype).str, placement)

    def acquire(self, shape, dtype, placement=None) -> np.ndarray:
        """A writable host buffer of exactly (shape, dtype) for the given
        placement domain: recycled when one is free, freshly allocated
        otherwise (contents undefined)."""
        key = self._key(shape, dtype, placement)
        if self.enabled:
            with self._lock:
                lst = self._free.get(key)
                if lst is not None:
                    self._free.move_to_end(key)  # ring touched = ring live
                    if lst:
                        self.reused += 1
                        return lst.pop()
                self.allocated += 1
        return np.empty(shape, np.dtype(dtype))

    def release(self, buf: np.ndarray, placement=None) -> bool:
        """Return ``buf`` to its placement domain's free list (True) or
        drop it when the per-key ring is full / pooling is disabled
        (False).  ``placement`` must match the acquire-side token."""
        if not self.enabled or not isinstance(buf, np.ndarray):
            return False
        key = self._key(buf.shape, buf.dtype, placement)
        with self._lock:
            lst = self._free.get(key)
            if lst is None:
                lst = self._free[key] = []
                while len(self._free) > self.MAX_KEYS:
                    # evict the least-recently-touched ring wholesale
                    # (its buffers are plain host arrays; dropping the
                    # references IS the free)
                    self._free.popitem(last=False)
                    self.rings_evicted += 1
            else:
                self._free.move_to_end(key)
            if len(lst) >= self._max_per_key:
                return False
            lst.append(buf)
        return True

    def trim(self) -> int:
        """Drop every pooled staging buffer (memory-pressure relief
        valve: the watermark monitor and the filter's OOM recovery both
        call this).  Outstanding (acquired) buffers are untouched —
        ownership is the caller's until release.  Returns buffers
        freed."""
        with self._lock:
            n = sum(len(lst) for lst in self._free.values())
            self._free.clear()
            self.trims += 1
        return n

    @property
    def reuse_rate(self) -> float:
        """reused / (reused + allocated) — 1.0 means zero-alloc steady
        state."""
        total = self.reused + self.allocated
        return self.reused / total if total else 0.0


#: process-wide default staging-buffer pool (``NNS_DEVICE_POOL`` sizes the
#: per-(shape,dtype) ring; 0 disables reuse)
DEVICE_POOL = DeviceBufferPool(int(os.environ.get("NNS_DEVICE_POOL", "8")))


# ---------------------------------------------------------------------------
# In-band events (flow through the same queues as frames, in order)
# ---------------------------------------------------------------------------
class Event:
    """Base class for in-band stream events (≙ GstEvent)."""

    __slots__ = ()

    def __repr__(self):
        return f"<{type(self).__name__}>"


class EOS(Event):
    """End of stream: no more frames will follow (≙ GST_EVENT_EOS)."""


class Flush(Event):
    """Drop queued data, reset element state (≙ FLUSH_START/STOP)."""


@dataclass(repr=True)
class SegmentEvent(Event):
    """New time segment (≙ GST_EVENT_SEGMENT)."""

    start: float = 0.0
    rate: float = 1.0


@dataclass(repr=True)
class CapsEvent(Event):
    """Announce the downstream schema (≙ GST_EVENT_CAPS).

    Sent before the first frame and whenever the schema changes; elements
    negotiate by intersecting with what they accept.
    """

    spec: StreamSpec = field(default_factory=StreamSpec)


@dataclass(repr=True)
class CustomEvent(Event):
    """Application/element-defined event (e.g. model RELOAD, epoch stats)."""

    name: str = ""
    data: Dict[str, Any] = field(default_factory=dict)


StreamItem = Any  # TensorFrame | Event


def now() -> float:
    return time.monotonic()
