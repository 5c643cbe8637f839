"""Deterministic fake backends for tests.

Reference analog: the custom-filter scaffolding subplugins used as fake
backends throughout the reference test suite
(``tests/nnstreamer_example/``: passthrough, scaler, average, framecounter)
so element behavior is testable without any NN framework.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core.resilience import FAULTS, DeviceLostError, DeviceOomError
from ..core.types import StreamSpec, TensorSpec
from .base import FilterBackend, register_backend


class Passthrough(FilterBackend):
    """Identity model (≙ nnstreamer_customfilter_example_passthrough)."""

    NAME = "passthrough"

    def framework_info(self):
        info = super().framework_info()
        info.run_without_model = True
        return info

    def set_input_info(self, in_spec: StreamSpec) -> StreamSpec:
        return in_spec

    def invoke(self, inputs: List[Any]) -> List[Any]:
        return list(inputs)

    def invoke_batch(self, inputs: List[Any]) -> List[Any]:
        return list(inputs)


class Scaler(FilterBackend):
    """Multiply by a constant from custom props ("factor:2") — the analog of
    the reference scaler example used to check option plumbing."""

    NAME = "scaler"

    def framework_info(self):
        info = super().framework_info()
        info.run_without_model = True
        return info

    @property
    def factor(self) -> float:
        return float(self.custom_props.get("factor", "2"))

    def set_input_info(self, in_spec: StreamSpec) -> StreamSpec:
        return in_spec

    def invoke(self, inputs: List[Any]) -> List[Any]:
        return [np.asarray(a) * np.asarray(a).dtype.type(self.factor) for a in inputs]

    def invoke_batch(self, inputs: List[Any]) -> List[Any]:
        return self.invoke(inputs)


class Average(FilterBackend):
    """Reduce each tensor to its scalar mean (float32, shape (1,))
    (≙ nnstreamer_customfilter_example_average)."""

    NAME = "average"

    def framework_info(self):
        info = super().framework_info()
        info.run_without_model = True
        return info

    def set_input_info(self, in_spec: StreamSpec) -> StreamSpec:
        return StreamSpec(
            tuple(TensorSpec((1,), np.float32, t.name) for t in in_spec.tensors),
            in_spec.fmt,
            in_spec.framerate,
        )

    def invoke(self, inputs: List[Any]) -> List[Any]:
        return [np.asarray([np.asarray(a).mean()], np.float32) for a in inputs]


class FrameCounter(FilterBackend):
    """Emit a running frame counter (tests ordering/liveness)."""

    NAME = "framecounter"

    def __init__(self):
        super().__init__()
        self._n = 0

    def framework_info(self):
        info = super().framework_info()
        info.run_without_model = True
        return info

    def set_input_info(self, in_spec: StreamSpec) -> StreamSpec:
        return StreamSpec(
            (TensorSpec((1,), np.int64, "count"),), in_spec.fmt, in_spec.framerate
        )

    def invoke(self, inputs: List[Any]) -> List[Any]:
        self._n += 1
        return [np.asarray([self._n], np.int64)]


class FakeDeviceArray:
    """A numpy value masquerading as an ASYNC device buffer.

    Models the accelerator contract the async feed is built against:
    ``is_ready()`` reflects device-side completion, ``copy_to_host_async``
    is a prefetch *hint* (it buys nothing here — the worst case), and ``__array__`` (materialization) blocks until
    completion and then pays the transfer cost ON THE CALLING THREAD.
    Every pre-completion blocking sync is recorded with the calling
    thread's name, so tests can pin "the dispatch thread never sat inside
    device_get" structurally instead of by timing.

    ``done`` may be a tuple of events — a MESH-sharded value whose shards
    complete independently: the buffer is ready only when EVERY shard is
    (the contract the sharded CompletionWindow rides — readiness means
    all shards, never just shard 0).
    """

    __slots__ = ("_value", "_done", "_transfer_s", "_sim", "_host")

    def __init__(self, value: np.ndarray, done,
                 transfer_s: float, sim: "AsyncSim"):
        self._value = value
        self._done = done if isinstance(done, tuple) else (done,)
        self._transfer_s = transfer_s
        self._sim = sim
        self._host: Optional[np.ndarray] = None  # transfer paid once

    @property
    def shape(self):
        return self._value.shape

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def ndim(self):
        return self._value.ndim

    def is_ready(self) -> bool:
        return all(ev.is_set() for ev in self._done)

    def copy_to_host_async(self) -> None:
        self._sim.copy_hints += 1  # hint only; no overlap (worst case)

    def _materialize(self) -> np.ndarray:
        if self._host is None:
            if not self.is_ready():
                self._sim.note_blocking_sync()
                for ev in self._done:
                    ev.wait()
            if self._transfer_s > 0:
                time.sleep(self._transfer_s)  # transfer occupies the caller
            self._host = self._value
        return self._host

    def __array__(self, dtype=None, copy=None):
        host = self._materialize()
        return host if dtype is None else host.astype(dtype, copy=False)

    def __getitem__(self, idx):
        return self._materialize()[idx]

    def __len__(self) -> int:
        return len(self._value)


class AsyncSim(FilterBackend):
    """Deterministic async-device simulator: affine ``y = 2x + 1`` with a
    single-server device worker (one batch in service at a time) and
    tunable costs, for CPU-proxy evidence of the async feed's structure.

    Custom props (milliseconds unless noted):

    * ``compute_ms``  — device service time per batch (single server).
    * ``transfer_ms`` — device->host materialization cost paid on the
      SYNCING thread (the ``device_get`` analog).
    * ``dispatch_ms`` — invoke-dispatch cost paid on the dispatch thread
      (the stack-jit + XLA-dispatch analog).
    * ``h2d_ms``      — ``to_device`` cost paid on the staging-lane thread.
    * ``manual``      — "1": batches complete only via :meth:`release_one`
      / :meth:`release_all` (deterministic window unit tests).
    * ``mesh_dp``     — N > 1: a SIMULATED dp mesh — N independent device
      servers, each serving its 1/N batch shard concurrently (per-shard
      service = compute_ms / N, the compute-bound split), outputs ready
      only when ALL shards are.  This is the deterministic twin the
      sharded-dataplane perf floor drives: on a single-core box the real
      XLA CPU proxy mesh cannot exhibit dp parallelism (both virtual
      devices share the one core), so the ≥1.5x dp:2 aggregate floor
      measures the FEED/dispatch structure over sleeping shard servers —
      the PR-9 SimSlotModel discipline.  Distinct from the jax-xla
      ``mesh=`` prop (a real jax.sharding.Mesh).

    Device-resource chaos (the typed taxonomy, core/resilience.py —
    deterministic twins of the chip failing, so the OOM/lost recovery
    ladders are testable chip-free):

    * ``oom_at``   — invoke_batch index K (0-based) raises
      :class:`~..core.resilience.DeviceOomError` ONCE (the injected OOM
      burst: the shrink-retry ladder must redeliver every frame).
    * ``oom_every``— every Nth invoke_batch raises DeviceOomError
      (sustained pressure; N >= 2 or the retry itself would OOM forever).
    * ``lost_at``  — invoke_batch index K raises
      :class:`~..core.resilience.DeviceLostError` ONCE (mesh-member
      death) and marks the backend degraded.

    The process-wide ``device.oom`` / ``device.lost`` fault sites fire
    here too, mirroring the jax-xla backend's sites.
    """

    NAME = "async-sim"
    SUPPORTS_STAGING = True  # to_device really copies off the staging buf

    def __init__(self):
        super().__init__()
        # one FIFO + one serve thread per simulated device server
        # (mesh_dp sizes the list; the default is the single server)
        self._pending: List["deque[threading.Event]"] = [deque()]
        self._cv = threading.Condition()
        self._workers: List[Optional[threading.Thread]] = [None]
        self._closed = False
        # census (inspected by tests; written under locks / GIL-atomic)
        self.blocking_syncs: List[str] = []
        self.copy_hints = 0
        self.dispatched = 0
        self._attempts = 0  # includes faulted attempts (chaos knobs)
        self.busy_s = 0.0  # actual device-service wall time (not nominal)

    # -- knobs ---------------------------------------------------------------
    def _ms(self, key: str, default: float = 0.0) -> float:
        return float(self.custom_props.get(key, default)) / 1000.0

    @property
    def manual(self) -> bool:
        return self.custom_props.get("manual", "") in ("1", "true")

    @property
    def mesh_dp(self) -> int:
        return max(1, int(self.custom_props.get("mesh_dp", "1")))

    def note_blocking_sync(self) -> None:
        self.blocking_syncs.append(threading.current_thread().name)

    # -- framework info -------------------------------------------------------
    def framework_info(self):
        info = super().framework_info()
        info.run_without_model = True
        return info

    def set_input_info(self, in_spec: StreamSpec) -> StreamSpec:
        return in_spec

    # -- device workers -------------------------------------------------------
    def _ensure_servers(self) -> None:
        nsrv = self.mesh_dp
        with self._cv:
            while len(self._pending) < nsrv:
                self._pending.append(deque())
                self._workers.append(None)
        if self.manual:
            return
        for i in range(nsrv):
            w = self._workers[i]
            if w is None or not w.is_alive():
                self._closed = False
                self._workers[i] = threading.Thread(
                    target=self._serve, args=(i,),
                    name=f"async-sim-device-{i}" if nsrv > 1
                    else "async-sim-device",
                    daemon=True)
                self._workers[i].start()

    def _serve(self, idx: int) -> None:
        # per-shard service: a dp mesh splits the batch, so each server
        # pays its 1/N share of the whole-batch compute knob
        service = self._ms("compute_ms") / self.mesh_dp
        while True:
            with self._cv:
                while not self._pending[idx]:
                    if self._closed:
                        return
                    self._cv.wait()
                ev = self._pending[idx].popleft()
            if service > 0:
                t0 = time.perf_counter()
                time.sleep(service)  # per server: its batches serialize
                # sleep() overshoots by timer granularity: record the
                # ACTUAL service time so overlap ratios divide by what
                # the device really spent, not the nominal knob
                self.busy_s += time.perf_counter() - t0
            ev.set()

    def release_one(self, server: int = 0) -> bool:
        """manual mode: complete ``server``'s oldest in-service shard
        (the single-server default keeps the pre-mesh signature)."""
        with self._cv:
            if server >= len(self._pending) or not self._pending[server]:
                return False
            self._pending[server].popleft().set()
            return True

    def release_all(self) -> int:
        n = 0
        with self._cv:
            for dq in self._pending:
                while dq:
                    dq.popleft().set()
                    n += 1
        return n

    def close(self):
        with self._cv:
            self._closed = True
            for dq in self._pending:
                for ev in dq:
                    ev.set()  # never strand a parked batch at teardown
                dq.clear()
            self._cv.notify_all()
            workers = [w for w in self._workers if w is not None]
            self._workers = [None] * len(self._workers)
        for worker in workers:
            if worker.is_alive():
                worker.join(timeout=2.0)

    # -- execution ------------------------------------------------------------
    def to_device(self, arrays: List[Any]) -> List[Any]:
        h2d = self._ms("h2d_ms")
        if h2d > 0:
            time.sleep(h2d)  # transfer occupies the lane thread
        # a real placement COPIES off the staging buffer (the lane's
        # buffer-reuse contract relies on it)
        return [np.array(a, copy=True) for a in arrays]

    def invoke(self, inputs: List[Any]) -> List[Any]:
        return [np.asarray(a) * 2 + 1 for a in inputs]

    def _maybe_device_fault(self, idx: int) -> None:
        """Deterministic device-resource chaos at invoke index ``idx``
        (see the class docstring knobs), plus the process-wide fault
        sites the jax-xla backend also instruments."""
        if FAULTS.is_armed():
            FAULTS.check("device.oom")
            FAULTS.check("device.lost")
        cp = self.custom_props
        lost_at = cp.get("lost_at")
        if lost_at is not None and idx == int(lost_at):
            self.degraded = True
            raise DeviceLostError(
                "async-sim: simulated mesh-member death", device_ids=(0,))
        oom_at = cp.get("oom_at")
        if oom_at is not None and idx == int(oom_at):
            raise DeviceOomError("async-sim: simulated HBM exhaustion")
        every = int(cp.get("oom_every", "0") or 0)
        if every >= 2 and idx > 0 and (idx % every) == 0:
            raise DeviceOomError("async-sim: simulated sustained HBM pressure")

    def invoke_batch(self, inputs: List[Any]) -> List[Any]:
        dispatch = self._ms("dispatch_ms")
        if dispatch > 0:
            time.sleep(dispatch)  # dispatch cost on the calling thread
        # faults key off the ATTEMPT index (advances even when the
        # attempt faults): "oom_at:K" fires exactly once and the
        # element's retry — a fresh attempt — proceeds
        idx = self._attempts
        self._attempts += 1
        self._maybe_device_fault(idx)
        self.dispatched += 1
        nsrv = self.mesh_dp
        # one completion event per dp shard, each queued on its own
        # server: the output is ready only when EVERY shard completed
        done = tuple(threading.Event() for _ in range(nsrv))
        outs = [
            FakeDeviceArray(
                np.asarray(a) * 2 + 1, done, self._ms("transfer_ms"), self)
            for a in inputs
        ]
        self._ensure_servers()  # grows queues/workers to nsrv (one owner)
        with self._cv:
            for i, ev in enumerate(done):
                self._pending[i].append(ev)
            self._cv.notify_all()
        return outs


for _cls in (Passthrough, Scaler, Average, FrameCounter, AsyncSim):
    register_backend(_cls)
