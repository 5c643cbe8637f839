"""tensor_filter: run a model as a stream element — the heart of the
framework.

Reference: ``gst/nnstreamer/tensor_filter/tensor_filter.c`` (transform :642,
set_caps :1314, configure :960) + ``tensor_filter_common.c`` (24+ properties,
framework auto-detect :1171-1196, shared-model table :2879-3084, accelerator
parse :2719-2878, latency/throughput statistics :363-430).

TPU-native deltas:

* **micro-batching**: with ``max-batch > 1`` the scheduler drains up to N
  queued frames and the element runs ONE backend ``invoke_batch`` call — the
  single biggest throughput lever on TPU (per-frame Python dispatch cannot
  reach 1000 fps; one XLA call on a batch can).  Timestamps/metadata of each
  frame are preserved; outputs are split back per-frame.
* accelerator wish lists resolve to a concrete device (``true:tpu.1,cpu``
  pins the second chip) — see ``backends.jax_xla.pick_device``.
* backends may return device-resident jax.Arrays; the filter passes them
  through untouched (zero-copy chaining).
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..backends.base import FilterBackend, find_backend, parse_accelerator
from ..core import config as nns_config
from ..core import registry
from ..core.buffer import FRAME_POOL, BatchFrame, CustomEvent, Flush, TensorFrame
from ..core.feed import CompletionWindow, HostStagingLane, StagedBatch
from ..core.lifecycle import HotSwapCoordinator, SwapTicket
from ..core.liveness import StallError
from ..core.model_uri import resolve_model_uri
from ..core.resilience import FAULTS, DeviceLostError, DeviceOomError
from ..core.telemetry import TL_INVOKE_META, TL_RX_META
from ..core.tracer import BATCH_SEQ_META, armed, span
from ..core.types import ANY, FORMAT_FLEXIBLE, StreamSpec
from ..pipeline.element import ElementError, Property, TransformElement, element

# ---------------------------------------------------------------------------
# Shared model table (reference tensor_filter_common.c:2879-3084):
# filter instances with the same shared-tensor-filter-key share one backend.
# ---------------------------------------------------------------------------
_shared_lock = threading.Lock()
_shared_table: Dict[str, Tuple[FilterBackend, int]] = {}


def _shared_acquire(key: str, factory) -> FilterBackend:
    with _shared_lock:
        if key in _shared_table:
            be, refs = _shared_table[key]
            _shared_table[key] = (be, refs + 1)
            return be
        be = factory()
        _shared_table[key] = (be, 1)
        return be


def _shared_release(key: str) -> bool:
    """Returns True if the caller should close the backend."""
    with _shared_lock:
        if key not in _shared_table:
            return True
        be, refs = _shared_table[key]
        if refs <= 1:
            del _shared_table[key]
            return True
        _shared_table[key] = (be, refs - 1)
        return False


def detect_framework(model_path: str, custom: str = "") -> str:
    """framework=auto resolution from the model extension.

    Reference: ``_detect_framework_from_config`` tensor_filter_common.c:1171.
    jax-xla wins a foreign extension (e.g. .tflite) only when the pipeline
    supplies ``custom=arch:<zoo-family>`` — without it jax-xla cannot load
    the file, so auto falls through to the native runtime for that format.
    """
    ext = os.path.splitext(model_path)[1]
    # parse the "k1:v1,k2:v2" custom dialect properly — a substring test
    # would false-positive on keys/values merely containing "arch:"
    has_arch = any(
        part.partition(":")[0].strip() == "arch"
        for part in str(custom or "").split(",")
        if ":" in part
    )
    for cand in nns_config.framework_priority(ext):
        if not registry.exists(registry.KIND_FILTER, cand):
            continue
        if (
            cand == "jax-xla"
            and ext not in ("", ".py", ".msgpack")
            and ext not in nns_config.EXPORTED_MODEL_EXTS
            and not has_arch
        ):
            continue
        return cand
    raise ElementError(
        f"cannot auto-detect a backend for model {model_path!r} (ext {ext!r})"
    )


def _parse_combination(text: str) -> Optional[List[Tuple[str, int]]]:
    """Parse "0,2" / "i0,o1" combination strings into (src, idx) pairs.

    Reference: input/output-combination props (tensor_filter.c:723-765,
    856-898); bare indices mean input for input-combination and output for
    output-combination — callers pass the default source tag.
    """
    if not text:
        return None
    out = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if part[0] in ("i", "o"):
            out.append((part[0], int(part[1:])))
        else:
            out.append(("", int(part)))
    return out or None


# bounded LRU: flexible-shape streams mint a new (bucket, shape, dtype)
# key per distinct frame shape, and each entry pins a compiled XLA
# program — unbounded growth is a slow leak on long-lived servers.  64
# entries cover every steady-state pipeline observed (buckets are powers
# of two, shapes are per-model); eviction just retraces on next use.
# The lock guards the get/move_to_end/evict compound ops — the cache is
# module-global and filter workers on different pipelines share it (its
# cost is noise next to the jitted stack call it fronts).
_STACK_JIT_MAX = 64
_stack_jit_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
_stack_jit_lock = threading.Lock()


def _never() -> bool:
    """``is_deleted`` stand-in for host arrays (numpy has no donation)."""
    return False


def _stack_tensors(arrs: List[Any]):
    """Stack per-frame tensors into a batch WITHOUT pulling device-resident
    arrays to host.

    Device arrays stack through a jitted program cached per
    (count, shape, dtype): eager ``jnp.stack`` is N expand_dims + concat =
    N+1 separate dispatches per micro-batch — measured at ~85% of the
    filter worker's time at batch 128.  One compiled call replaces them.
    Numpy stacks on host (the single host->device transfer then happens
    inside the backend).
    """
    a0 = arrs[0]
    if type(a0).__module__.split(".")[0] == "jaxlib" or hasattr(a0, "sharding"):
        import jax
        import jax.numpy as jnp

        # bucket the count to the next power of two (padding with repeated
        # references — free) so fluctuating queue-drain sizes share a
        # handful of compiles per shape instead of one per distinct count
        n = len(arrs)
        bucket = 1
        while bucket < n:
            bucket <<= 1
        key = (bucket, tuple(a0.shape), str(a0.dtype))
        with _stack_jit_lock:
            fn = _stack_jit_cache.get(key)
            if fn is not None:
                _stack_jit_cache.move_to_end(key)
        if fn is None:
            fn = jax.jit(lambda *xs: jnp.stack(xs))
            with _stack_jit_lock:
                _stack_jit_cache[key] = fn
                while len(_stack_jit_cache) > _STACK_JIT_MAX:
                    _stack_jit_cache.popitem(last=False)  # evict LRU
        stacked = fn(*(list(arrs) + [a0] * (bucket - n)))
        # lazy device slice (one op) back to the true count
        return stacked[:n] if bucket != n else stacked
    return np.stack([np.asarray(a) for a in arrs])


def _concat_tensors(arrs: List[Any]):
    """Concatenate along the existing batch axis (block-ingest merge).

    Unlike :func:`_stack_tensors` the operand count here is the number of
    QUEUE ITEMS (a handful), not the number of logical frames, so an eager
    concat is one cheap dispatch and needs no jit cache."""
    if len(arrs) == 1:
        return arrs[0]
    if any(
        type(a).__module__.split(".")[0] == "jaxlib" or hasattr(a, "sharding")
        for a in arrs
    ):
        # ANY device-resident piece keeps the concat on device — a host
        # np.concatenate would drag every device block through a sync
        # transfer only for invoke_batch to re-upload the result
        import jax.numpy as jnp

        return jnp.concatenate(arrs, axis=0)
    return np.concatenate([np.asarray(a) for a in arrs], axis=0)


def _batched_tensors(
    frames: Sequence[TensorFrame], select: Optional[List[int]]
) -> List[Any]:
    """Expand a mixed plain/BatchFrame list into ONE batched tensor list:
    plain frames gain a length-1 batch axis, blocks pass through, pieces
    concatenate per tensor index.  ``select`` optionally narrows to the
    given tensor indices (input-combination)."""
    pieces: List[List[Any]] = []
    for f in frames:
        tens = (
            [f.tensors[i] for i in select] if select is not None
            else list(f.tensors)
        )
        if not isinstance(f, BatchFrame):
            tens = [
                t[None] if hasattr(t, "shape") else np.asarray(t)[None]
                for t in tens
            ]
        pieces.append(tens)
    if len(pieces) == 1:
        return pieces[0]
    return [
        _concat_tensors([p[t] for p in pieces])
        for t in range(len(pieces[0]))
    ]


def _logical_infos(
    frames: Sequence[TensorFrame],
) -> List[Tuple[Optional[float], Optional[float], Dict[str, Any]]]:
    """Flatten (pts, duration, meta) per LOGICAL frame across a mixed list
    of plain frames and BatchFrames, in stream order."""
    infos: List[Tuple[Optional[float], Optional[float], Dict[str, Any]]] = []
    for f in frames:
        if isinstance(f, BatchFrame):
            infos.extend(f.frames_info)
        else:
            infos.append((f.pts, f.duration, f.meta))
    return infos


@element("tensor_filter")
class TensorFilter(TransformElement):
    BATCH_AWARE = True  # consumes the batch axis (micro-batching)

    PROPERTIES = {
        "framework": Property(str, "auto", "backend name or 'auto'"),
        "model": Property(str, "", "model path / registry key"),
        "custom": Property(
            str, "",
            "backend-specific options 'k1:v1,k2:v2' (jax-xla zoo: "
            "arch:<family> and its sizes, e.g. arch:vit,size,patch,d_model,"
            "heads,layers,d_ff,classes,dtype,quantize,seed; no key selects "
            "a kernel: Documentation/performance.md 'Kernels on the default "
            "path')"),
        "accelerator": Property(str, "", "'true:tpu.N,cpu' ordered wish list -> real device pinning"),
        # mesh-sharded serving (parallel/mesh.py grammar): one logical
        # filter across a device mesh — params sharded by the parallel
        # layer's rules, micro-batches scattered over dp, replicated on
        # tp; XLA SPMD inserts the collectives (jax-xla only)
        "mesh": Property(
            str, "",
            "serve this model sharded across a device mesh: 'tp:4' / "
            "'dp:2,tp:2' / 'dp:-1' (-1 = remaining devices; empty = "
            "unsharded).  Params shard per parallel/sharding.py rules, "
            "micro-batches scatter on dp; backend must support meshes "
            "(jax-xla)"),
        "input-combination": Property(str, "", "subset/reorder input tensors, e.g. '0,2'"),
        "output-combination": Property(str, "", "compose output from 'iN'/'oN' tensors"),
        "latency": Property(int, 0, "1 = enable per-invoke latency measurement"),
        "throughput": Property(int, 0, "1 = enable throughput measurement"),
        "latency-report": Property(int, 0, "1 = post latency bus messages"),
        "is-updatable": Property(bool, False, "allow hot model reload"),
        # zero-downtime model rollout (core/lifecycle.py): reloads stage
        # the new model on a SECOND backend instance off the hot path
        # (open + schema validation + JIT warmup), swap at a frame
        # boundary, and roll back on a post-swap error burst
        "staged-reload": Property(
            bool, True,
            "hot reloads stage+validate+warm the new model on a second "
            "backend instance and swap at a frame boundary (false = "
            "legacy inline backend.reload(), still guarded: a failed "
            "reload keeps the old model serving)"),
        "observation-window": Property(
            float, 5.0,
            "seconds after a hot swap during which invoke errors are "
            "served by the retained old model and an error burst rolls "
            "the swap back"),
        "rollback-error-burst": Property(
            int, 3,
            "invoke errors within observation-window that auto-roll-back "
            "a hot swap to the previous model"),
        "shared-tensor-filter-key": Property(str, "", "share one backend instance"),
        "invoke-dynamic": Property(bool, False, "output schema varies per buffer"),
        "max-batch": Property(int, 1, "micro-batch up to N queued frames into one invoke"),
        "batch-timeout": Property(
            int, 0, "ms to wait filling a micro-batch (0 = only drain queued)"
        ),
        "dispatch-depth": Property(
            int, 4,
            "micro-batches kept in flight in the completion-driven "
            "dispatch window (a reaper thread materializes finished "
            "batches; the dispatch thread keeps stacking/dispatching and "
            "never blocks in device_get; 1 = synchronous)",
        ),
        "ingest-lane": Property(
            str, "auto",
            "auto|on|off — double-buffered host->device staging: host "
            "frames are stacked into pooled staging buffers and placed "
            "on device from a lane thread, one batch ahead, so the "
            "transfer overlaps the previous batch's compute (auto = on "
            "when the backend supports staged placement and max-batch>1)",
        ),
        # manual model-info override (≙ tensor_filter_common.c props
        # input/inputtype/inputname/inputranks + output side): declare or
        # force I/O schemas for backends that cannot infer them (custom
        # functions, raw .so) or to reshape shape-polymorphic models
        "input": Property(str, "", "manual input dims 'd:d:d[,d:d]' (reference dialect)"),
        "input-type": Property(str, "", "manual input element types 't[,t]'"),
        "inputname": Property(str, "", "manual input tensor names"),
        "inputranks": Property(str, "", "true ranks of manual input dims"),
        "output": Property(str, "", "manual output dims (validated/declared)"),
        "output-type": Property(str, "", "manual output element types"),
        "outputname": Property(str, "", "manual output tensor names"),
        "outputranks": Property(str, "", "true ranks of manual output dims"),
        "inputlayout": Property(
            str, "", "NCHW|NHWC|ANY per input (recorded; XLA owns layout)"
        ),
        "outputlayout": Property(
            str, "", "NCHW|NHWC|ANY per output (recorded; XLA owns layout)"
        ),
        "config-file": Property(
            str, "", "key=value file applied as properties (explicit "
            "pipeline-text properties win)"
        ),
        # ≙ GstShark/NNShark tracing (SURVEY §5.1) done the XLA-native way
        "trace": Property(int, 0, "1 = capture a jax.profiler trace while running"),
        "trace-dir": Property(str, "/tmp/nns_tpu_trace", "profiler output dir"),
        "batch-through": Property(
            bool, False,
            "emit micro-batches as ONE BatchFrame (device-resident) instead "
            "of per-frame outputs; downstream must be batch-aware (set "
            "automatically by the pipeline's device-fusion pass)",
        ),
    }

    def __init__(self, name=None):
        super().__init__(name)
        self.backend: Optional[FilterBackend] = None
        self._owns_backend = True
        self._model_in: Optional[StreamSpec] = None
        self._model_out: Optional[StreamSpec] = None
        self._latency_ring: deque = deque(maxlen=10)  # µs, reference keeps last 10
        self._nframes = 0
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        # telemetry (core/telemetry.py): always-on invoke counters (two
        # int adds per invoke) + the handler-entry stamp the trace-span
        # dispatch segment is derived from
        self._invokes = 0
        self._invoked_frames = 0
        self._t_handler = 0.0
        # combination props parsed once at start (hot path stays parse-free)
        self._in_comb: Optional[List[Tuple[str, int]]] = None
        self._out_comb: Optional[List[Tuple[str, int]]] = None
        # set by the pipeline's device-fusion pass (NOT the user prop, so a
        # restart without the pass re-fusing leaves the chain unfused)
        self._auto_batch_through = False
        # the depth-N dispatch window, completion-driven: parked batches
        # are materialized by the window's reaper thread in FIFO order;
        # the dispatch thread only pops completed entries (never sits in
        # device_get) and waits on a completion EVENT when the window is
        # full (core/feed.py)
        self._inflight = CompletionWindow(self.name)
        # host-ingest staging lane + the one-batch staged deferral that
        # double-buffers it (dispatch of batch k happens while k+1 stages)
        self._lane: Optional[HostStagingLane] = None
        self._staged: Optional[
            Tuple[StagedBatch, List[TensorFrame], int, int]] = None
        # micro-batch sequence number (dispatch-thread-private): what the
        # spans of one batch share from staging to emission
        self._seq = 0
        self._seq_alloc = 0
        # async-output capability, latched ONCE per backend instance
        # (reset at start()/swap/rollback) — the hot path never re-probes
        self._win_async: Optional[bool] = None
        # hot-swap coordinator (core/lifecycle.py), created on the first
        # reload request; None keeps the per-call check to one attr read
        self._swapper: Optional[HotSwapCoordinator] = None
        # device-resource resilience (core/resilience.py taxonomy):
        # lifetime accounting + the degraded-mesh override a re-shard
        # leaves behind (a restart keeps serving the shrunk mesh — the
        # dead chip is still dead)
        self._oom_retries = 0     # invokes retried after a device OOM
        self._oom_shrinks = 0     # micro-batches split to a smaller bucket
        self._oom_evictions = 0   # cache/pool entries trimmed on OOM
        self._device_lost = 0     # lost-device events seen
        self._remeshes = 0        # backends rebuilt on surviving devices
        self._degraded = False    # serving in a reduced configuration
        self._mesh_override: Optional[str] = None
        self._mesh_exclude: Tuple[int, ...] = ()

    @property
    def batch_through_active(self) -> bool:
        """Effective batch-through: the user prop, or the device-fusion
        pass's per-run flag (reset on every start)."""
        return bool(self.props["batch-through"]) or self._auto_batch_through

    # -- device fusion (pipeline pass) --------------------------------------
    @property
    def can_fuse_postprocess(self) -> bool:
        """True when a downstream device half can be folded into this
        filter's compiled program (no combination/dynamic-shape features
        that would change what the postprocess sees, and a private,
        postprocess-capable backend)."""
        return (
            self.backend is not None
            and hasattr(self.backend, "append_postprocess")
            and self._owns_backend
            and not self.props["invoke-dynamic"]
            and not self._out_comb
        )

    def fuse_device_postprocess(self, fn) -> None:
        """Fold ``fn`` (jit-traceable, operates on the model's output list)
        into the backend program and invalidate cached output schemas so
        negotiation re-derives the fused shape."""
        assert self.can_fuse_postprocess
        self.backend.append_postprocess(fn)
        self._model_out = None

    # -- batching hook for the scheduler ------------------------------------
    @property
    def preferred_batch(self) -> int:
        be = self.backend
        if be is not None and be.supports_batch:
            return max(1, int(self.props["max-batch"]))
        return 1

    @property
    def batch_wait_s(self) -> float:
        return max(0, int(self.props["batch-timeout"])) / 1000.0

    # -- lifecycle ----------------------------------------------------------
    @staticmethod
    def _apply_rank(shape: tuple, rank: int) -> tuple:
        """Trim/pad OUTERMOST (numpy-leading) unit dims so the shape has
        the declared true rank (≙ inputranks/outputranks, which exist in
        the reference to disambiguate trailing-1 dims of the padded dim
        string)."""
        shape = tuple(shape)
        while len(shape) > rank:
            if shape[0] not in (1, None):
                raise ElementError(
                    f"cannot reduce shape {shape} to rank {rank}: leading "
                    f"dim {shape[0]} != 1"
                )
            shape = shape[1:]
        while len(shape) < rank:
            shape = (1,) + shape
        return shape

    def _manual_spec(self, side: str) -> Optional[StreamSpec]:
        """Build the manual model-info override for 'input'/'output' from
        the reference-dialect props, or None when not configured."""
        from ..core.types import (
            FORMAT_STATIC,
            TensorSpec,
            dtype_from_name,
            parse_dims_string,
        )

        dims_text = self.props[side]
        types_text = self.props[f"{side}-type"]
        if not dims_text and not types_text:
            return None
        if not dims_text or not types_text:
            raise ElementError(
                f"{self.name}: {side} and {side}-type must be given together"
            )
        dims = [d for d in dims_text.split(",") if d.strip()]
        types = [t.strip() for t in types_text.split(",") if t.strip()]
        if len(dims) != len(types):
            raise ElementError(
                f"{self.name}: {side} declares {len(dims)} tensors but "
                f"{side}-type declares {len(types)}"
            )
        names_key = "inputname" if side == "input" else "outputname"
        ranks_key = "inputranks" if side == "input" else "outputranks"
        names = self.props[names_key].split(",") if self.props[names_key] else []
        ranks = [
            int(r) for r in self.props[ranks_key].split(",") if r.strip()
        ] if self.props[ranks_key] else []
        specs = []
        for i, (d, t) in enumerate(zip(dims, types)):
            try:
                shape = parse_dims_string(d)
                if i < len(ranks):
                    shape = self._apply_rank(shape, ranks[i])
                spec = TensorSpec(
                    shape, dtype_from_name(t),
                    names[i].strip() if i < len(names) else "",
                )
            except (ValueError, ElementError) as e:
                raise ElementError(f"{self.name}: {side}[{i}]: {e}") from None
            specs.append(spec)
        return StreamSpec(tuple(specs), FORMAT_STATIC, None)

    @staticmethod
    def _as_stream_spec(s) -> Optional[StreamSpec]:
        """Normalize a backend model-info value — None | StreamSpec |
        sequence of TensorSpec | sequence of (shape, dtype) — into a
        StreamSpec, or None when empty/unknown."""
        if s is None:
            return None
        if isinstance(s, StreamSpec):
            return s if s.tensors else None
        from ..core.types import FORMAT_STATIC, TensorSpec

        tensors = []
        for t in s:
            if isinstance(t, TensorSpec):
                tensors.append(t)
            else:
                shape, dt = t
                tensors.append(TensorSpec(tuple(shape), np.dtype(dt)))
        return (
            StreamSpec(tuple(tensors), FORMAT_STATIC, None)
            if tensors else None
        )

    _LAYOUTS = ("", "none", "any", "nchw", "nhwc")

    def _check_layouts(self) -> None:
        for key in ("inputlayout", "outputlayout"):
            for i, lay in enumerate(
                x.strip().lower()
                for x in self.props[key].split(",") if x.strip()
            ):
                if lay not in self._LAYOUTS:
                    raise ElementError(
                        f"{self.name}: {key}[{i}]: unknown layout {lay!r} "
                        f"(want NCHW|NHWC|ANY|NONE); note XLA owns physical "
                        "layout on TPU — this prop is declarative"
                    )

    def _make_backend(self, model: Optional[str]) -> FilterBackend:
        """Open ONE backend instance for ``model`` with this element's
        props.  Used at start() and by the hot-swap staging thread (which
        builds a second instance without touching the serving one)."""
        be = self._backend_cls()
        info = be.framework_info()
        if model is None and not info.run_without_model:
            raise ElementError(
                f"{self.name}: framework {self._framework!r} requires a model")
        if model and info.verify_model_path and not os.path.exists(model):
            raise ElementError(f"{self.name}: model file not found: {model}")
        props = dict(self.props)
        enabled, wishes = parse_accelerator(self.props["accelerator"])
        props["accelerators"] = wishes if enabled else ["cpu"]
        if self._mesh_override is not None:
            # degraded re-shard: every backend built from here on (the
            # re-mesh itself, later hot swaps, restarts) claims only the
            # surviving devices at the shrunk mesh config — which
            # REPLACES any legacy mesh_* custom props outright
            props["mesh"] = self._mesh_override
            props["mesh_remesh_override"] = True
        if self._mesh_exclude:
            props["mesh_exclude_ids"] = list(self._mesh_exclude)
        be.open(model, props)
        return be

    def start(self) -> None:
        self._apply_config_file()
        self._check_layouts()
        self._tracing = False
        self._auto_batch_through = False  # re-set by the fusion pass, or not
        self._in_comb = _parse_combination(self.props["input-combination"])
        self._out_comb = _parse_combination(self.props["output-combination"])
        # constant per run: does output-combination read any INPUT tensor?
        # (an outputs-only combination must not drag input blocks to host)
        self._out_needs_inputs = self._out_comb is not None and any(
            src == "i" for src, _ in self._out_comb
        )
        if self.props["batch-through"] and self._out_comb:
            # the BatchFrame fast path bypasses _compose_outputs; refusing
            # beats emitting a layout that depends on queue depth
            raise ElementError(
                f"{self.name}: batch-through=true is incompatible with "
                "output-combination"
            )
        if self.props["invoke-dynamic"] and int(self.props["max-batch"]) > 1:
            # per-buffer-varying output shapes cannot be stacked into one
            # batched XLA call (reference invoke_dynamic is per-frame too,
            # tensor_filter.c:856-930)
            raise ElementError(
                f"{self.name}: invoke-dynamic is per-frame "
                "(incompatible with max-batch>1)"
            )
        if self.props["mesh"]:
            # parse NOW so a typo'd mesh spec fails at start, not after
            # the backend loaded a model (grammar owned by parallel/mesh)
            from ..parallel.mesh import parse_mesh_spec

            try:
                parse_mesh_spec(self.props["mesh"])
            except ValueError as e:
                raise ElementError(f"{self.name}: {e}") from None
        fw = self.props["framework"]
        model = self.props["model"] or None
        if model:
            # mlagent-URI analog: model://name[/version] + file:// schemes
            # (plain paths pass through unchanged)
            model = resolve_model_uri(model)
        if fw == "auto":
            if not model:
                raise ElementError(f"{self.name}: framework=auto requires a model")
            fw = detect_framework(model, self.props["custom"])
        try:
            backend_cls = find_backend(fw)
        except KeyError:
            raise ElementError(f"{self.name}: unknown framework {fw!r}") from None
        # latched for hot model swaps: a reload keeps the framework
        # resolved at start (≙ the reference RELOAD_MODEL contract)
        self._backend_cls, self._framework = backend_cls, fw
        if self.props["mesh"] and not getattr(
                backend_cls, "SUPPORTS_MESH", False):
            # refusing beats silently serving unsharded: the operator
            # asked for a placement this backend cannot honor
            raise ElementError(
                f"{self.name}: mesh={self.props['mesh']!r} but backend "
                f"{fw!r} does not support mesh-sharded serving")

        key = self.props["shared-tensor-filter-key"]
        if key:
            self.backend = _shared_acquire(
                key, lambda: self._make_backend(model))
            self._owns_backend = False
        else:
            self.backend = self._make_backend(model)
            self._owns_backend = True
        self._model_in, self._model_out = self.backend.get_model_info()
        in_override = self._manual_spec("input")
        out_override = self._manual_spec("output")
        if in_override is not None:
            if not self._owns_backend:
                # a shared backend's model info is visible to every filter
                # on the key: mutating it (set_input_info) mid-run would
                # desynchronize siblings' negotiated schemas
                raise ElementError(
                    f"{self.name}: manual input override is incompatible "
                    "with shared-tensor-filter-key (set it on a non-shared "
                    "filter)"
                )
            model_in = self._as_stream_spec(self._model_in)
            if model_in is None:
                # backend cannot infer (custom fn / raw .so): declare
                self._model_in = in_override
                try:
                    derived = self.backend.set_input_info(in_override)
                    if self._as_stream_spec(derived) is not None:
                        self._model_out = derived
                except NotImplementedError:
                    pass
            elif not in_override.is_compatible(model_in):
                # flexible ('?'/0) override dims are wildcards — only a
                # genuinely conflicting declaration forces a reshape
                # force-reshape a shape-polymorphic model (≙ SET_INPUT_INFO)
                try:
                    self._model_out = self.backend.set_input_info(in_override)
                except NotImplementedError:
                    raise ElementError(
                        f"{self.name}: input={self.props['input']} conflicts "
                        f"with the model's declared input and backend "
                        f"{fw!r} cannot reshape"
                    ) from None
                self._model_in = in_override
        if out_override is not None:
            model_out = self._as_stream_spec(self._model_out)
            if model_out is None:
                self._model_out = out_override
            elif not out_override.is_compatible(model_out):
                raise ElementError(
                    f"{self.name}: output={self.props['output']}/"
                    f"{self.props['output-type']} does not match the "
                    f"model's output "
                    f"{tuple((t.shape, str(t.dtype)) for t in model_out.tensors)}"
                )
        # async device feed state: capability re-latched for the fresh
        # backend; host-ingest staging lane armed when the backend really
        # copies off the staging buffers (SUPPORTS_STAGING) and the hot
        # path micro-batches (invoke-dynamic already excludes max-batch>1)
        self._win_async = None
        self._staged = None
        self._lane = None
        lane_mode = str(self.props["ingest-lane"] or "auto").lower()
        if lane_mode not in ("auto", "on", "off"):
            raise ElementError(
                f"{self.name}: ingest-lane={lane_mode!r} (want auto|on|off)")
        # the one-batch dispatch deferral means an invoke error surfaces
        # during the NEXT batch's call — fine under fail-stop (the
        # pipeline tears down), but skip/restart would dead-letter or
        # replay the WRONG frames, so those policies exclude the lane
        replay_policy = (
            self.props.get("error-policy", "fail-stop") != "fail-stop"
            or self.props.get("stall-policy", "warn") == "restart"
        )
        if lane_mode != "off" and self.preferred_batch > 1:
            if replay_policy:
                if lane_mode == "on":
                    raise ElementError(
                        f"{self.name}: ingest-lane=on is incompatible "
                        "with error-policy=skip|restart / "
                        "stall-policy=restart (deferred dispatch would "
                        "misattribute the failed frames)")
            elif getattr(self.backend, "SUPPORTS_STAGING", False):
                self._lane = HostStagingLane(
                    lambda arrs: self.backend.to_device(arrs),
                    name=self.name,
                    # placement-domain key for the staging-buffer pool: a
                    # mesh/device identity, so this lane's rings never mix
                    # with a differently-placed filter's (core/buffer.py)
                    placement=self.backend.staging_placement(),
                )
            elif lane_mode == "on":
                raise ElementError(
                    f"{self.name}: ingest-lane=on but backend "
                    f"{self._framework!r} does not support staged "
                    "host->device placement")
        elif lane_mode == "on":
            raise ElementError(
                f"{self.name}: ingest-lane=on requires max-batch>1 "
                "(staging overlaps per-micro-batch transfers)")
        # trace only after the backend opened: a start() failure must not
        # leak a profiler reference (pipeline won't call stop() on us then)
        if self.props["trace"]:
            from ..core.profiler import trace_start

            self._tracing = trace_start(self.props["trace-dir"])

    def stop(self) -> None:
        if self._staged is not None:
            self._staged[0].discard()
            self._staged = None
        self._inflight.clear()  # drop parked batches (refs released now)
        if self._lane is not None:
            self._lane.close()
            self._lane = None
        if self._swapper is not None:
            # staged / retired / rolled-back backends; the coordinator
            # (and its lifetime swap counters) survives restarts
            self._swapper.close()
        if getattr(self, "_tracing", False):
            from ..core.profiler import trace_stop

            trace_stop()
            self._tracing = False
        if self.backend is not None:
            key = self.props["shared-tensor-filter-key"]
            should_close = _shared_release(key) if key else True
            if should_close and (self._owns_backend or key):
                self.backend.close()
            self.backend = None
        # stop the reaper LAST: a reaper mid-materialization may only be
        # unblocked by the backend teardown above (close() joins it)
        self._inflight.close()

    # -- zero-downtime model rollout (core/lifecycle.py) ---------------------
    def _ensure_swapper(self) -> HotSwapCoordinator:
        if self._swapper is None:
            self._swapper = HotSwapCoordinator(
                self.name,
                # "" = modelless backend (custom fns): open(None, ...)
                build=lambda m: self._make_backend(m or None),
                validate=self._validate_staged,
                warmup=self._warmup_staged,
            )
        return self._swapper

    def request_reload(self, model: str = "") -> SwapTicket:
        """Validated hot model swap (``Pipeline.reload_model`` and the
        RELOAD_MODEL event land here): stage the new model on a second
        backend instance in a background thread — open, schema check
        against the negotiated specs, JIT warmup on a zero probe frame —
        then swap atomically at the next frame boundary.  Any staging
        failure keeps the old model serving and counts ``swap_failures``
        (never the supervisor's restart budget)."""
        if not self.props["is-updatable"]:
            raise ElementError(
                f"{self.name}: model reload requires is-updatable=true")
        if self.backend is None:
            raise ElementError(f"{self.name}: not started")
        model = model or self.props["model"]
        model = resolve_model_uri(model) if model else ""
        sw = self._ensure_swapper()
        if not self._owns_backend or not self.props["staged-reload"]:
            # a shared backend is visible to every filter on the key, so a
            # per-element pointer swap cannot replace it — guarded legacy
            # inline reload (double-buffered inside backends that support
            # it, e.g. jax-xla)
            return self._inline_reload(model)
        return sw.request(
            model,
            observation_window=float(self.props["observation-window"]),
            error_burst=int(self.props["rollback-error-burst"]),
        )

    def _inline_reload(self, model: str) -> SwapTicket:
        """Legacy in-place ``backend.reload()`` with the keep-serving
        guarantee: a failed reload logs, counts ``swap_failures``, and
        leaves the old model serving — it must never escape into the
        supervision machinery and kill/restart the element."""
        sw = self._ensure_swapper()
        try:
            FAULTS.check("filter.reload.load")
            self.backend.reload(model)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — reload boundary
            self.log.error(
                "model reload from %r failed (old model keeps serving): %s",
                model, e,
            )
            return sw.note_inline_failure(e)
        self.props["model"] = model
        self.log.info("model reloaded from %s", model)
        return sw.note_inline_swap(model)

    def _validate_staged(self, be: FilterBackend):
        """Staging-thread schema validation: the new model must accept
        the pipeline's negotiated input stream and keep producing the
        negotiated output schema (downstream never renegotiates during a
        hot swap).  Returns the raw model info the element adopts at
        swap time."""
        raw_in, raw_out = be.get_model_info()
        new_in = self._as_stream_spec(raw_in)
        new_out = self._as_stream_spec(raw_out)
        negotiated = self.sink_specs.get(0)
        if (negotiated is not None and negotiated.tensors
                and new_in is not None):
            got = self._input_for_backend(negotiated)
            if not new_in.is_compatible(got):
                raise ElementError(
                    f"{self.name}: staged model input "
                    f"{new_in.to_string()} does not accept the negotiated "
                    f"stream {got.to_string()}"
                )
        if (new_out is None and negotiated is not None
                and negotiated.tensors):
            try:
                new_out = self._as_stream_spec(
                    be.set_input_info(self._input_for_backend(negotiated)))
            except NotImplementedError:
                new_out = None
        cur_out = self.srcpads[0].spec if self.srcpads else None
        if (new_out is not None and cur_out is not None
                and getattr(cur_out, "tensors", None)
                and not self.props["invoke-dynamic"]
                and not self._out_comb
                and not cur_out.is_compatible(new_out)):
            raise ElementError(
                f"{self.name}: staged model output {new_out.to_string()} "
                f"does not match the negotiated downstream schema "
                f"{cur_out.to_string()}"
            )
        return raw_in, raw_out

    def _probe_inputs(self, model_in=None) -> Optional[List[Any]]:
        """A zero frame matching the model's (or negotiated) input
        schema, flexible dims resolved to 1; None when no static schema
        exists to probe.  ``model_in`` overrides the serving model's raw
        input info (the staging path probes the NEW model's schema)."""
        spec = self._as_stream_spec(
            self._model_in if model_in is None else model_in)
        if spec is None and model_in is None:
            negotiated = self.sink_specs.get(0)
            if negotiated is not None and negotiated.tensors:
                spec = self._input_for_backend(negotiated)
        if spec is None or not spec.tensors:
            return None
        probes = []
        for t in spec.tensors:
            shape = tuple(1 if d in (None, 0) else int(d) for d in t.shape)
            probes.append(np.zeros(shape, dtype=t.dtype))
        return probes

    def _warmup_staged(self, be: FilterBackend) -> None:
        """Staging-thread JIT warmup: one probe invoke (and a batched one
        when the hot path micro-batches) so a swap never forces a fresh
        XLA trace on the serving thread — on TPU that compile is
        multi-second, which would stall the stream."""
        probes = self._probe_inputs()
        if probes is None:
            probes = self._probe_inputs(model_in=be.get_model_info()[0])
            if probes is None:
                return  # nothing static to probe (dynamic/custom schema)
        be.invoke(list(probes))
        if be.supports_batch and self.preferred_batch > 1:
            be.invoke_batch([p[None] for p in probes])

    def _swap_tick(self) -> List[Tuple[int, TensorFrame]]:
        """Frame-boundary lifecycle work: apply a staged swap, commit an
        expired observation window, and reap retired backends — all
        strictly AFTER draining the in-flight dispatch window, so a
        retiring backend outlives its last in-flight frame.  Returns the
        drained results (the caller emits them ahead of new output)."""
        sw = self._swapper
        if sw is None or not sw.has_boundary_work:
            return []
        drained = self._flush_staged()
        drained.extend(self._drain_inflight())
        staged = sw.take_staged()
        if staged is not None:
            be, model, raw_in, raw_out, ticket = staged
            old_blob = (
                self.backend, self._model_in, self._model_out,
                self.props["model"],
            )
            self.backend = be
            self._win_async = None  # re-latch for the fresh backend
            if raw_in is not None:
                self._model_in = raw_in
            if raw_out is not None:
                self._model_out = raw_out
            self.props["model"] = model
            sw.activated(old_blob, ticket)
            self.log.info(
                "hot-swapped to model %r (version %d); observing for "
                "%.1fs", model, sw.model_version,
                float(self.props["observation-window"]),
            )
        if sw.observing:
            sw.note_ok()  # commits once the observation window elapsed
        sw.reap()
        return drained

    def _backend_invoke(self, inputs: List[Any]) -> List[Any]:
        sw = self._swapper
        # per-frame call site: no keywords (span()'s off branch must
        # allocate nothing), attributes only while a session is live
        with span("nns.filter.invoke") as sp:
            if sp.live:
                sp.set(frames=1, bucket=1)
            if sw is None or not sw.observing:
                return self.backend.timed_invoke(inputs)
            return self._observed_invoke(False, inputs)

    def _backend_invoke_batch(
        self, inputs: List[Any], private: bool = False
    ) -> List[Any]:
        """``private=True`` marks inputs the filter freshly stacked or
        staged itself — the backend may DONATE them (XLA reuses their
        device memory for outputs: zero per-batch allocations).  Never
        donated inside a post-swap observation window: a failed invoke is
        replayed on the retained old backend with the SAME inputs, which
        donation would have destroyed."""
        sw = self._swapper
        # ``bucket`` (what the batch was padded to) is the backend's to
        # say: it notes it on this span (tracer.note)
        with span("nns.filter.invoke", seq=self._seq,
                  frames=int(inputs[0].shape[0])):
            if sw is None or not sw.observing:
                if private:
                    return self.backend.timed_invoke_batch_donated(inputs)
                return self.backend.timed_invoke_batch(inputs)
            return self._observed_invoke(True, inputs)

    # -- device-resource resilience (degrade, don't die) ---------------------
    def _resilient_invoke(self, inputs: List[Any]) -> List[Any]:
        """Per-frame invoke with the OOM/device-loss recovery ladder."""
        try:
            return self._backend_invoke(inputs)
        except DeviceOomError:
            # a single frame has no batch to split: trim recreatable
            # memory and retry the frame once
            self._oom_retries += 1
            self._trim_for_oom()
            return self._backend_invoke(inputs)
        except DeviceLostError as e:
            self._remesh_after_loss(e)
            return self._backend_invoke(inputs)

    def _resilient_invoke_batch(
        self, inputs: List[Any], private: bool = False
    ) -> List[Any]:
        """Micro-batch invoke with the recovery ladder: on device OOM,
        trim recreatable memory and retry ONCE at the next-smaller
        batch bucket (the halves re-bucket through the backend's own
        ``_pad_rows`` machinery — a strictly smaller compile bucket,
        hence a strictly smaller peak working set); on device loss,
        re-mesh onto the survivors and retry.  Retries never donate:
        both halves slice the same underlying arrays."""
        try:
            return self._backend_invoke_batch(inputs, private=private)
        except DeviceOomError:
            if any(getattr(t, "is_deleted", _never)() for t in inputs):
                # the donated first attempt consumed its inputs before
                # the OOM landed (donation invalidates at dispatch, not
                # at success): nothing left to slice — surface the
                # typed transient error to supervision instead of
                # crashing on a deleted array
                raise
            self._oom_retries += 1
            self._trim_for_oom()
            n = int(inputs[0].shape[0])
            if n <= 1:
                return self._backend_invoke_batch(inputs)
            self._oom_shrinks += 1
            self.log.warning(
                "device OOM on a %d-row micro-batch: trimmed caches, "
                "retrying as two half-bucket invokes", n)
            h = (n + 1) // 2
            out1 = self._backend_invoke_batch([t[:h] for t in inputs])
            out2 = self._backend_invoke_batch([t[h:] for t in inputs])
            return [
                _concat_tensors([a, b]) for a, b in zip(out1, out2)
            ]
        except DeviceLostError as e:
            self._remesh_after_loss(e)
            if any(getattr(t, "is_deleted", _never)() for t in inputs):
                # donated inputs died with the device: the re-mesh cures
                # the NEXT frames; this one surfaces typed to supervision
                raise
            return self._backend_invoke_batch(inputs)

    def _trim_for_oom(self) -> None:
        """Release every recreatable byte before the retry: the
        backend's compiled-program cache and the process staging-buffer
        pool (exact ``oom_evictions`` accounting)."""
        from ..core.buffer import DEVICE_POOL

        freed = 0
        be = self.backend
        if be is not None:
            freed += int(be.trim_caches() or 0)
        freed += DEVICE_POOL.trim()
        self._oom_evictions += freed

    def _remesh_after_loss(self, err: DeviceLostError) -> None:
        """Degraded-mesh re-shard: build a replacement backend on the
        surviving devices (``parallel/mesh.shrink_axes`` ladder via the
        backend's ``remesh_spec_after_loss``), swap the serving pointer
        atomically once the replacement is FULLY staged, retire the
        wounded backend through the hot-swap graveyard (closed only
        after the in-flight window drains), and mark this element —
        and, via the pipeline, the serving plane — degraded.  Backends
        with no re-mesh story (or shared backends, whose pointer this
        element does not own) re-raise into supervision: an element
        restart re-picks devices."""
        self._device_lost += 1
        be = self.backend
        if be is None or not self._owns_backend:
            # shared backends (pointer not ours) re-raise untouched —
            # checked BEFORE remesh_spec_after_loss, whose per-device
            # liveness probe may block against a wedged runtime only to
            # have its result discarded here
            raise err
        reported = getattr(err, "device_ids", ()) or ()
        res = be.remesh_spec_after_loss(reported)
        if res is None:
            # no re-mesh story (unsharded, or the probe found every
            # mesh member alive): record any ordinals PROVABLY dead so
            # the supervision restart cannot re-pick the dead chip —
            # open()'s survivor placement honors the exclusion even
            # unsharded — then escalate
            dead = be.dead_ordinals_after_loss(reported)
            if dead:
                self._mesh_exclude = tuple(
                    set(self._mesh_exclude) | set(dead))
            raise err
        spec, lost = res
        self._mesh_override = spec
        # always exclude the identified dead members (reported, probed,
        # or conservatively guessed): ordinal-first claiming would
        # otherwise hand the rebuilt backend the dead chip back
        self._mesh_exclude = tuple(set(self._mesh_exclude) | set(lost))
        model = self.props["model"] or None
        if model:
            model = resolve_model_uri(model)
        self.log.error(
            "device lost (%s): re-sharding onto survivors as mesh=%r",
            err, spec or "unsharded")
        new_be = self._make_backend(model)  # fully staged before return
        new_be.degraded = True
        old_be, self.backend = self.backend, new_be
        self._win_async = None  # re-latch for the fresh backend
        self._ensure_swapper().discard(old_be)  # reaped at a drained boundary
        self._remeshes += 1
        self._degraded = True
        p = self._pipeline
        if p is not None:
            p.incident("device_lost", self.name, {
                "lost_devices": list(lost), "remesh": spec or "unsharded",
            })
            p.degraded_feedback(
                self.name, f"device lost; serving on mesh={spec or 'none'}")

    def _observed_invoke(self, batched: bool, inputs: List[Any]) -> List[Any]:
        """Invoke inside the post-swap observation window: an error is
        served by the RETAINED old model (zero frame loss) and counted;
        a burst rolls the swap back entirely.  Neither path ever reaches
        the supervisor's error-policy/restart machinery."""
        sw = self._swapper
        try:
            if FAULTS.is_armed():
                FAULTS.check("filter.reload.post",
                             interrupt=lambda: self.interrupted)
            out = (
                self.backend.timed_invoke_batch(inputs) if batched
                else self.backend.timed_invoke(inputs)
            )
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — observation boundary
            verdict = sw.note_error(e)
            if verdict is None:
                raise
            (old_be, old_in, old_out, old_model), rolled_back = verdict
            if rolled_back:
                failed = self.backend
                self.backend = old_be
                self._win_async = None  # re-latch for the restored backend
                self._model_in, self._model_out = old_in, old_out
                self.props["model"] = old_model
                sw.discard(failed)
                p = self._pipeline
                if p is not None:
                    # incident: a rollout that rolled back is exactly
                    # when "where did the time go" gets asked
                    p.incident("swap_rollback", self.name, e)
            # the frame is retried on the old backend either way — a
            # bad rollout must not cost a single frame
            return (
                old_be.timed_invoke_batch(inputs) if batched
                else old_be.timed_invoke(inputs)
            )
        sw.note_ok()
        return out

    def pending_frames(self) -> int:
        """Logical frames parked in the in-flight dispatch window plus
        the staged (not yet dispatched) ingest batch (drain/stop
        accounting, Pipeline.drain)."""
        n = sum(
            sum(getattr(f, "batch_size", 1) for f in frames)
            for frames, _ in self._inflight.payloads()
        )
        staged = self._staged
        if staged is not None:
            n += staged[2]
        return n

    def health_info(self) -> Dict[str, Any]:
        """Model-rollout counters merged into ``Pipeline.health()``."""
        info: Dict[str, Any] = {
            "model": self.props["model"],
            "model_version": 0,
            "swaps": 0,
            "swap_failures": 0,
            "rollbacks": 0,
            # jax-profiler session held by this element (trace=1) —
            # exported as nns.profiler.active via the health collector
            "profiler_active": 1 if getattr(self, "_tracing", False) else 0,
            # device-resource resilience (nns.device.*): exact OOM
            # shrink-retry / trim / re-mesh accounting, plus the
            # degraded flag the discovery plane mirrors
            "oom_retries": self._oom_retries,
            "oom_shrinks": self._oom_shrinks,
            "oom_evictions": self._oom_evictions,
            "device_lost": self._device_lost,
            "remeshes": self._remeshes,
            "degraded": 1 if (
                self._degraded
                or (self.backend is not None and self.backend.degraded)
            ) else 0,
        }
        if self._swapper is not None:
            info.update(self._swapper.snapshot())
        # mesh-sharded serving facts (jax-xla mesh= prop): devices/axis
        # sizes + host-batch scatters — exported as nns.mesh.* via the
        # ONE health-collector path (metrics_info here would double-emit)
        be = self.backend
        if be is not None and hasattr(be, "mesh_info"):
            info.update(be.mesh_info())
        # named-thread census (core/liveness.py ThreadBeat): the async
        # feed's reaper + staging-lane workers are part of the health
        # story — a wedged one shows alive=True with a growing age
        from ..core.liveness import thread_census

        win = self._inflight
        lane = self._lane
        info["threads"] = thread_census(
            win.heartbeat if win is not None else None,
            lane.heartbeat if lane is not None else None,
        )
        return info

    def metrics_info(self):
        """Registry samples (core/telemetry.py, scrape time only): invoke
        counters plus the async-feed gauges — the CompletionWindow
        occupancy/reap counts and the HostStagingLane stats."""
        win = self._inflight
        lane = self._lane
        return [
            ("nns.filter.invokes", self._invokes),
            ("nns.filter.invoked_frames", self._invoked_frames),
            ("nns.filter.invoke_latency", self.latency_us * 1e-6),
            ("nns.feed.window_occupancy", len(win)),
            ("nns.feed.window_reaped", win.reaped),
            ("nns.feed.dispatch_waits", win.dispatch_waits),
            ("nns.feed.lane_pending",
             lane.pending() if lane is not None else 0),
            ("nns.feed.lane_staged",
             lane.staged if lane is not None else 0),
        ]

    def histograms_info(self):
        """Always-on log2 latency histograms exported by the telemetry
        collector (buckets + derived p50/p99 gauges at scrape time):
        completion-window dwell, park -> pop."""
        return [("nns.feed.window_dwell_seconds", self._inflight.dwell)]

    @staticmethod
    def _stamp_invoke_spans(frames: Sequence[TensorFrame],
                            dispatch_s: float, compute_s: float) -> None:
        """Trace spans over the query wire: frames that carry the server
        receive stamp (``TL_RX_META``, set by ``QueryServerCore.process``)
        get this invoke's (dispatch, compute) durations attached, so the
        answer's server-side decomposition can split device time out of
        queue time.  One dict-containment probe per invoke when the
        stream never crossed the wire."""
        probe = frames[0]
        m0 = (
            probe.frames_info[0][2]
            if isinstance(probe, BatchFrame) and probe.frames_info
            else probe.meta
        )
        if TL_RX_META not in m0:
            return
        span = (max(0.0, dispatch_s), max(0.0, compute_s))
        for f in frames:
            if isinstance(f, BatchFrame):
                for _, _, m in f.frames_info:
                    if TL_RX_META in m:
                        m[TL_INVOKE_META] = span
                if TL_RX_META in f.meta:
                    f.meta[TL_INVOKE_META] = span
            elif TL_RX_META in f.meta:
                f.meta[TL_INVOKE_META] = span

    # -- negotiation --------------------------------------------------------
    def _input_for_backend(self, spec: StreamSpec) -> StreamSpec:
        comb = self._in_comb if self.backend is not None else _parse_combination(
            self.props["input-combination"]
        )
        if comb:
            return spec.pick([i for _, i in comb])
        return spec

    def accept_spec(self, pad, spec):
        if self._model_in is not None and spec.tensors:
            want = self._model_in
            got = self._input_for_backend(spec)
            if not want.is_compatible(got):
                raise ElementError(
                    f"{self.name}: stream schema {got.to_string()} does not match "
                    f"model input {want.to_string()}"
                )
        return spec

    def derive_spec(self, pad=0):
        in_spec = self.sink_specs.get(0, ANY)
        if self.props["invoke-dynamic"]:
            # per-buffer output schemas: advertise format=flexible so
            # downstream negotiates late, per frame (reference wraps
            # invoke_dynamic outputs as flexible, tensor_filter.c:856-930)
            return StreamSpec((), FORMAT_FLEXIBLE, in_spec.framerate)
        if self._model_out is not None:
            out = self._model_out
        elif self.backend is not None and in_spec.tensors:
            out = self.backend.set_input_info(self._input_for_backend(in_spec))
        else:
            return ANY
        comb = self._out_comb
        if comb:
            # 'iN' indexes the element's ORIGINAL input tensors (pre
            # input-combination), matching reference tensor_filter.c:856-898
            tensors = []
            for src, i in comb:
                tensors.append(in_spec.tensors[i] if src == "i" else out.tensors[i])
            out = StreamSpec(tuple(tensors), out.fmt, in_spec.framerate or out.framerate)
        return out

    # -- processing ---------------------------------------------------------
    def _compose_outputs(self, orig_inputs: List[Any], outputs: List[Any]) -> List[Any]:
        comb = self._out_comb
        if not comb:
            return outputs
        return [orig_inputs[i] if src == "i" else outputs[i] for src, i in comb]

    def _record_stats(self, dt_s: float, nframes: int) -> None:
        import time

        self._invokes += 1
        self._invoked_frames += nframes
        if self.props["latency"]:
            self._latency_ring.append(dt_s * 1e6 / max(nframes, 1))
            if self.props["latency-report"] and self._pipeline is not None:
                from ..pipeline.pipeline import BusMessage

                self._pipeline.post(
                    BusMessage(
                        "element",
                        self.name,
                        {"latency-us": self.latency_us, "batch": nframes},
                    )
                )
        if self.props["throughput"]:
            t = time.monotonic()
            if self._t_first is None:
                self._t_first = t
            self._t_last = t
            self._nframes += nframes

    @property
    def latency_us(self) -> float:
        """Average per-frame invoke latency of the last 10 invokes, µs
        (reference: prop `latency`, nnstreamer_plugin_api_filter.h:162)."""
        return float(np.mean(self._latency_ring)) if self._latency_ring else 0.0

    @property
    def throughput_fps(self) -> float:
        """Outputs/sec since start (reference: prop `throughput`)."""
        if not self._nframes or self._t_first is None or self._t_last == self._t_first:
            return 0.0
        return self._nframes / (self._t_last - self._t_first)

    def transform(self, frame: TensorFrame) -> TensorFrame:
        assert self.backend is not None, f"{self.name} not started"
        sw = self._swapper
        if sw is not None and sw.has_boundary_work and not self._inflight:
            # per-frame path never parks batches, so the tick's drained
            # results are always empty here
            self._swap_tick()
        comb = self._in_comb
        inputs = [frame.tensors[i] for _, i in comb] if comb else list(frame.tensors)
        import time

        FAULTS.check("filter.invoke", interrupt=lambda: self.interrupted)
        t0 = time.perf_counter()
        if isinstance(frame, BatchFrame):
            # a pre-batched block on a single-invoke path (max-batch=1,
            # invoke-dynamic, backend without native batching): the batch
            # axis must still mean "batch" — invoke() would treat it as
            # part of one frame's shape (and a mesh backend would
            # REPLICATE instead of shard).  invoke_batch's per-frame
            # fallback covers batchless backends.
            outputs = self._resilient_invoke_batch(inputs)
            dt = time.perf_counter() - t0
            self._record_stats(dt, frame.batch_size)
        else:
            outputs = self._resilient_invoke(inputs)
            dt = time.perf_counter() - t0
            self._record_stats(dt, 1)
        self._stamp_invoke_spans((frame,), 0.0, dt)
        return frame.with_tensors(self._compose_outputs(frame.tensors, outputs))

    def handle_frame_batch(
        self, pad: int, frames: List[TensorFrame]
    ) -> List[Tuple[int, TensorFrame]]:
        """Micro-batched path: scheduler hands N frames; one invoke_batch.
        A pending hot swap applies here first — a frame boundary with the
        in-flight window drained (the drained results are emitted ahead
        of this batch's, preserving stream order)."""
        sw = self._swapper
        if sw is not None and sw.has_boundary_work:
            pre = self._swap_tick()
            if pre:
                return pre + list(self._handle_batch(pad, frames) or [])
        return self._handle_batch(pad, frames)

    def _handle_batch(
        self, pad: int, frames: List[TensorFrame]
    ) -> List[Tuple[int, TensorFrame]]:
        assert self.backend is not None
        import time

        # handler-entry stamp: the trace-span "device-dispatch" segment
        # (stack/stage time before the backend call) is measured from here
        self._t_handler = time.perf_counter()
        if any(isinstance(f, BatchFrame) for f in frames):
            # block ingest (≙ converter frames-per-tensor batching,
            # gsttensor_converter.c frames-per-tensor): the batch axis
            # already exists — skip per-frame stacking entirely.  A
            # staged lane batch is older: dispatch it first (FIFO).
            return self._flush_staged() + self._handle_prebatched(frames)
        if len(frames) == 1:
            # queue-starved moment: release the staged batch and drain
            # the in-flight window first so this frame cannot overtake
            # older parked batches
            results = self._flush_staged()
            results.extend(self._drain_inflight())
            results.append((0, self.transform(frames[0])))
            return results
        comb = self._in_comb
        per_frame = [
            [f.tensors[i] for _, i in comb] if comb else list(f.tensors) for f in frames
        ]
        if self._lane is not None and type(per_frame[0][0]) is np.ndarray:
            # host ingest: stack + host->device placement move to the lane
            # thread, and dispatch is DEFERRED BY ONE BATCH — by the time
            # batch k's device arrays are needed, its transfer has been
            # overlapping batch k-1's compute (double-buffered staging)
            seq = self._next_seq()
            job = self._lane.submit(per_frame, seq=seq)
            prev, self._staged = self._staged, (
                job, frames, len(frames), seq)
            if prev is None:
                return []
            pjob, pframes, pn, pseq = prev
            batched = self._staged_result(pjob, pseq)
            return self._run_batch(batched, pframes, pn, private=True,
                                   seq=pseq)
        results = self._flush_staged()  # mixed stream: keep FIFO
        ntensors = len(per_frame[0])
        batched = [
            _stack_tensors([pf[t] for pf in per_frame]) for t in range(ntensors)
        ]
        results.extend(self._run_batch(batched, frames, len(frames),
                                       private=True))
        return results

    def _next_seq(self) -> int:
        self._seq_alloc += 1
        return self._seq_alloc

    def _run_batch(
        self, batched: List[Any], frames: List[TensorFrame], nlogical: int,
        private: bool = False, seq: Optional[int] = None,
    ) -> List[Tuple[int, TensorFrame]]:
        """Shared micro-batch tail: one invoke_batch + stats, then either
        batch-through (device residency: the whole micro-batch leaves as
        ONE frame, outputs still on device — no host sync here, so the
        next batch's stack/dispatch overlaps this one's compute; downstream
        fused decoder / chained filter / sink splits or materializes at the
        real host boundary) or the depth-N dispatch window.  ``private``
        marks caller-created batches the backend may donate; ``seq`` is
        the batch's sequence number where staging already gave it one."""
        import time

        self._seq = self._next_seq() if seq is None else seq
        with span("nns.filter.batch", seq=self._seq, frames=nlogical):
            FAULTS.check("filter.invoke", interrupt=lambda: self.interrupted)
            t0 = time.perf_counter()
            out_b = self._resilient_invoke_batch(batched, private=private)
            dt = time.perf_counter() - t0
            self._record_stats(dt, nlogical)
            self._stamp_invoke_spans(
                frames, t0 - self._t_handler if self._t_handler else 0.0, dt)
            if self.batch_through_active:
                infos = _logical_infos(frames)
                p, d, m = infos[0]
                meta = dict(m)
                if armed():
                    # the batch leaves device-resident: whoever brings it to
                    # the host (a fused decoder, a sink) names its span by it
                    meta[BATCH_SEQ_META] = self._seq
                return [(0, FRAME_POOL.acquire_batch(
                    list(out_b), pts=p, duration=d, meta=meta,
                    frames_info=infos,
                ))]
            return self._dispatch_or_park(out_b, frames)

    def _dispatch_or_park(
        self, out_b: List[Any], frames: List[TensorFrame]
    ) -> List[Tuple[int, TensorFrame]]:
        """Completion-driven depth-N dispatch: park this batch's (async)
        device outputs in the window — its reaper thread materializes
        parked batches FIFO off the dispatch thread — then emit whatever
        has COMPLETED at the front.  The dispatch thread never sits in
        ``device_get``: when the window is full it waits on the oldest
        batch's completion event (bounded, cooperatively interruptible)
        as pure backpressure, and by the time an entry is popped its
        device->host sync has already happened on the reaper.  A bare
        jitted model driven with several calls in flight has exactly
        this structure; the reference's steady state is synchronous
        map->invoke->append (tensor_filter.c:642-930)."""
        depth = max(1, int(self.props["dispatch-depth"]))
        if self._win_async is None:
            # capability latched once per backend instance (reset at
            # start()/swap/rollback): the hot path never re-probes
            self._win_async = any(
                hasattr(o, "copy_to_host_async") for o in out_b
            )
            if not self._win_async and depth > 1:
                self.log.info(
                    "dispatch-depth=%d requested but %r outputs are "
                    "host-resident: the dispatch window degrades to the "
                    "synchronous path", depth, self._framework,
                )
        if depth > 1 and self._win_async:
            from ..core.buffer import start_host_copies

            start_host_copies(out_b)
            self._inflight.park(out_b, (frames, self._seq), seq=self._seq)
            results = self._pop_ready()
            while len(self._inflight) > depth - 1:
                self._wait_window_oldest()
                results.extend(self._pop_ready())
            return results
        # synchronous path: drain any batches parked while the window was
        # active (depth lowered mid-stream / backend change) first, so the
        # current batch cannot overtake them
        return self._drain_inflight() + self._emit_batch(
            out_b, frames, seq=self._seq)

    def _handle_prebatched(
        self, frames: List[TensorFrame]
    ) -> List[Tuple[int, TensorFrame]]:
        """Frames that already carry a batch axis (BatchFrame block ingest,
        possibly mixed with plain frames): concatenate on axis 0 — usually a
        no-op because the scheduler hands exactly one full block — and run
        invoke_batch.  input-combination selects tensor INDICES, which
        applies to batched tensors unchanged; output-combination's
        per-logical input rows are sliced in _emit_batch.  A block larger
        than max-batch is chunked here (lazy device slices) so max-batch
        keeps bounding the invoke's batch axis — the compiled-bucket /
        HBM-budget contract — even though the scheduler never splits a
        queue item."""
        comb = self._in_comb
        batched = _batched_tensors(
            frames, [i for _, i in comb] if comb else None
        )
        nlogical = sum(getattr(f, "batch_size", 1) for f in frames)
        mb = max(1, int(self.props["max-batch"]))
        if nlogical <= mb:
            return self._run_batch(batched, frames, nlogical)
        # out-combination 'iN' entries index ORIGINAL input tensors; when
        # in-combination narrowed `batched`, the chunks' synthetic frames
        # must carry the originals for _emit_batch to slice
        if self._out_needs_inputs and comb:
            carry = _batched_tensors(frames, None)
        else:
            carry = batched
        infos = _logical_infos(frames)
        results: List[Tuple[int, TensorFrame]] = []
        for k in range(0, nlogical, mb):
            chunk = [t[k:k + mb] for t in batched]
            cinfos = infos[k:k + mb]
            syn = BatchFrame(
                tensors=[t[k:k + mb] for t in carry],
                pts=cinfos[0][0], duration=cinfos[0][1],
                meta=dict(cinfos[0][2]), frames_info=list(cinfos),
            )
            results.extend(self._run_batch(chunk, [syn], len(cinfos)))
        return results

    def _emit_batch(
        self, out_b: Optional[List[Any]], frames: List[TensorFrame],
        out_np: Optional[List[Any]] = None, seq: Optional[int] = None,
    ) -> List[Tuple[int, TensorFrame]]:
        """Materialize one micro-batch's outputs (one overlapped
        device->host pass for all tensors, then zero-copy views per
        frame).  ``frames`` may mix plain frames (one output row each)
        and BatchFrames (``batch_size`` consecutive rows).  ``out_np``
        carries outputs the window's reaper already materialized."""
        with span("nns.filter.emit", seq=seq, frames=len(frames)):
            from ..core.buffer import materialize

            if out_np is None:
                out_np = materialize(out_b)
            # only the tensor indices an 'iN' entry actually reads get pulled
            # to host; "o0"-style output subsetting (and unreferenced input
            # tensors) must not drag input blocks over the link
            need_idx = sorted({
                i for src, i in (self._out_comb or []) if src == "i"
            }) if self._out_needs_inputs else []
            results = []
            b = 0
            for f in frames:
                if isinstance(f, BatchFrame):
                    ins_np: List[Any] = [None] * len(f.tensors)
                    if need_idx:
                        mats = materialize([f.tensors[i] for i in need_idx])
                        for k, i in enumerate(need_idx):
                            ins_np[i] = mats[k]
                    for j, (p, d, m) in enumerate(f.frames_info):
                        outs = [o[b + j] for o in out_np]
                        if self._out_comb:
                            ins = [
                                (t[j] if t is not None else None) for t in ins_np
                            ]
                            outs = self._compose_outputs(ins, outs)
                        results.append((0, FRAME_POOL.acquire(
                            outs, pts=p, duration=d, meta=dict(m),
                        )))
                    b += f.batch_size
                else:
                    outs = [o[b] for o in out_np]
                    results.append(
                        (0, f.with_tensors(self._compose_outputs(f.tensors, outs)))
                    )
                    b += 1
            return results

    def _pop_ready(self) -> List[Tuple[int, TensorFrame]]:
        """Emit every batch the reaper has COMPLETED at the front of the
        window (FIFO), without blocking."""
        results: List[Tuple[int, TensorFrame]] = []
        for mats, (frames, seq) in self._inflight.pop_ready():
            results.extend(
                self._emit_batch(None, frames, out_np=mats, seq=seq))
        return results

    def _wait_window_oldest(self) -> None:
        """Bounded, cooperatively interruptible wait for the oldest
        parked batch's completion (full-window backpressure)."""
        if self._inflight.oldest_ready():
            return
        # the dispatch thread blocked because the window is full: the
        # thread that feeds the device, so the wait is named
        with span("nns.feed.window_wait", element=self.name):
            while not self._inflight.wait_oldest(timeout=0.05):
                if self.interrupted:
                    raise StallError(
                        f"{self.name}: interrupted waiting on the dispatch "
                        "window")

    def _drain_inflight(self) -> List[Tuple[int, TensorFrame]]:
        results = self._pop_ready()
        while len(self._inflight):
            self._wait_window_oldest()
            results.extend(self._pop_ready())
        return results

    def _staged_result(self, job: StagedBatch, seq: int) -> List[Any]:
        """Collect a staging job's device arrays (bounded waits so a
        wedged transfer stays interruptible)."""
        if not job.wait(timeout=0.0):
            # the dispatch thread blocked on the lane: host-to-device
            # time the double buffer did NOT hide
            with span("nns.filter.stage_wait", seq=seq):
                while not job.wait(timeout=0.05):
                    if self.interrupted:
                        raise StallError(
                            f"{self.name}: interrupted waiting on the "
                            "ingest lane")
        # allow-blocking: the wait() loop above already saw _done set —
        # result() returns (or raises the staging error) immediately
        return job.result()

    def _flush_staged(self) -> List[Tuple[int, TensorFrame]]:
        """Dispatch the deferred (staged) ingest batch, if any.  Always
        called BEFORE draining the window at a boundary: the dispatch
        parks into the window, so a subsequent drain emits everything in
        FIFO order."""
        if self._staged is None:
            return []
        job, frames, nlogical, seq = self._staged
        self._staged = None
        batched = self._staged_result(job, seq)
        return self._run_batch(batched, frames, nlogical, private=True,
                               seq=seq)

    def handle_eos(self, pad: int) -> List[Tuple[int, TensorFrame]]:
        """Release the staged batch and drain the in-flight window before
        EOS propagates."""
        outs = self._flush_staged()
        outs.extend(self._drain_inflight())
        outs.extend(self._swap_tick())
        return outs

    def handle_idle(self) -> List[Tuple[int, TensorFrame]]:
        """Scheduler idle hook: the input went quiet, so overlap has
        nothing left to win — release the staged batch and the parked
        window instead of withholding a live stream's tail until the next
        frame/EOS.  Also a natural frame boundary: a staged swap on an
        idle stream lands here instead of waiting for the next frame."""
        outs = self._flush_staged()
        outs.extend(self._drain_inflight())
        outs.extend(self._swap_tick())
        return outs

    # -- events -------------------------------------------------------------
    def handle_event(self, pad, ev):
        if isinstance(ev, Flush):
            # a flush drops queued frames; the staged batch and in-flight
            # results are frames too
            if self._staged is not None:
                self._staged[0].discard()
                self._staged = None
            self._inflight.clear()
            return super().handle_event(pad, ev)
        # any other in-band event must not overtake parked frames (events
        # and frames share one ordered queue, core/buffer.py) — emit the
        # staged batch and the window first, then the event
        drained = self._flush_staged()
        drained.extend(self._drain_inflight())
        if isinstance(ev, CustomEvent) and ev.name == "reload-model":
            # ≙ RELOAD_MODEL framework event (tested by
            # tests/nnstreamer_filter_reload in the reference), routed
            # through the staged swap path (core/lifecycle.py).  A failed
            # reload must NEVER escape into the supervision machinery —
            # it logs, counts swap_failures, and the old model keeps
            # serving.
            if not self.props["is-updatable"]:
                self.log.warning("reload requested but is-updatable=false")
            elif self.backend is not None:
                try:
                    ticket = self.request_reload(ev.data.get("model") or "")
                    if ticket.state == "refused":
                        # not a swap_failure (nothing was tried), but the
                        # operator's update was NOT applied — say so
                        self.log.warning(
                            "reload-model event refused (old model keeps "
                            "serving): %s", ticket.error,
                        )
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as e:  # noqa: BLE001 — reload boundary
                    self._ensure_swapper().note_inline_failure(e)
                    self.log.error(
                        "reload-model event failed (old model keeps "
                        "serving): %s", e,
                    )
            return drained  # event swallowed; parked frames still flow
        return drained + list(super().handle_event(pad, ev) or [])


class SingleShot:
    """Pipeline-less single-invoke API.

    Reference: ``GTensorFilterSingle``
    (``tensor_filter_single.c:30-35``, "basis of single shot api") — wraps
    the same backends without any pipeline.
    """

    def __init__(self, framework: str = "auto", model: str = "", **props):
        if model:
            model = resolve_model_uri(model)
        merged = {"custom": "", **props}
        fw = (
            detect_framework(model, merged["custom"])
            if framework == "auto" else framework
        )
        self.backend: FilterBackend = find_backend(fw)()
        self.backend.open(model or None, merged)
        self.in_spec, self.out_spec = self.backend.get_model_info()

    def invoke(self, arrays: Sequence[Any]) -> List[Any]:
        return self.backend.invoke(list(arrays))

    def invoke_batch(self, arrays: Sequence[Any]) -> List[Any]:
        return self.backend.invoke_batch(list(arrays))

    def set_input_info(self, spec: StreamSpec) -> StreamSpec:
        return self.backend.set_input_info(spec)

    def close(self) -> None:
        self.backend.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
