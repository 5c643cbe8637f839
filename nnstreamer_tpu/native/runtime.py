"""ctypes bindings for the native core (libnns_tpu_core.so).

Builds the library on first use with g++, blocking (about a second; no
pybind11 in this image — the C ABI + ctypes keeps the boundary simple), so
every pipeline of a process, the first included, runs the same dataplane.
If the toolchain or build is unavailable, ``available()`` returns False and
the pipeline runtime runs on ``queue.Queue``; :func:`mailbox_impl` says
which one a process got, and anything that measures treats "not native"
as a failure.

:class:`NativeMailbox` is API-compatible with the ``queue.Queue`` subset
the scheduler uses (put/put_nowait/get/get_nowait raising queue.Full/Empty)
but blocks inside the C++ condvar with the GIL released — immediate
wakeups instead of Python poll loops.  Python object lifetime: a strong
reference is taken (Py_IncRef) before the pointer enters the native queue
and handed back to Python on pop; close() drains and releases leftovers.
"""

from __future__ import annotations

import ctypes
import os
import queue as _pyqueue
import subprocess
import threading
from typing import Any, Optional

from ..core.log import get_logger

log = get_logger("native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "core", "nns_tpu_core.cc")
_BUILD_DIR = os.path.join(_HERE, "build")
_SO = os.path.join(_BUILD_DIR, "libnns_tpu_core.so")

_lib: Optional[ctypes.CDLL] = None
_build_lock = threading.Lock()
_build_failed = False


def _build() -> Optional[str]:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    if _so_fresh():
        return _SO
    # compile to a temp name and rename atomically: a concurrent loader (or
    # a second process) must never dlopen a half-written .so
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread",
        "-o", tmp, _SRC,
    ]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native core build failed to run: %s", e)
        return None
    if r.returncode != 0:
        log.warning("native core build failed:\n%s", r.stderr)
        return None
    os.replace(tmp, _SO)
    return _SO


def _so_fresh() -> bool:
    return os.path.exists(_SO) and os.path.getmtime(_SO) >= os.path.getmtime(_SRC)


def _load() -> Optional[ctypes.CDLL]:
    """dlopen the core library, compiling it first when the .so is missing
    or older than its source.  A failed build is remembered: the process
    stays on queue.Queue rather than re-running g++ per mailbox."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    if os.environ.get("NNS_TPU_NO_NATIVE"):
        return None
    with _build_lock:
        if _lib is not None or _build_failed:
            return _lib
        so = _build()
        if so is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(so)
        lib.nns_oq_create.restype = ctypes.c_void_p
        lib.nns_oq_create.argtypes = [ctypes.c_size_t]
        lib.nns_oq_push.restype = ctypes.c_int
        lib.nns_oq_push.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_double,
        ]
        lib.nns_oq_pop.restype = ctypes.c_int
        lib.nns_oq_pop.argtypes = [
            ctypes.c_void_p, ctypes.c_double,
            ctypes.POINTER(ctypes.c_void_p),
        ]
        lib.nns_oq_pop_n.restype = ctypes.c_int
        lib.nns_oq_pop_n.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_double,
            ctypes.POINTER(ctypes.c_void_p),
        ]
        lib.nns_oq_push_n.restype = ctypes.c_int
        lib.nns_oq_push_n.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_size_t, ctypes.c_double,
        ]
        lib.nns_oq_size.restype = ctypes.c_size_t
        lib.nns_oq_size.argtypes = [ctypes.c_void_p]
        lib.nns_oq_close.argtypes = [ctypes.c_void_p]
        lib.nns_oq_destroy.argtypes = [ctypes.c_void_p]
        lib.nns_pool_create.restype = ctypes.c_void_p
        lib.nns_pool_create.argtypes = [
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_size_t,
        ]
        lib.nns_pool_acquire.restype = ctypes.c_void_p
        lib.nns_pool_acquire.argtypes = [ctypes.c_void_p]
        lib.nns_pool_release.restype = ctypes.c_int
        lib.nns_pool_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.nns_pool_block_size.restype = ctypes.c_size_t
        lib.nns_pool_block_size.argtypes = [ctypes.c_void_p]
        lib.nns_pool_outstanding.restype = ctypes.c_size_t
        lib.nns_pool_outstanding.argtypes = [ctypes.c_void_p]
        lib.nns_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.nns_reader_open.restype = ctypes.c_void_p
        lib.nns_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.nns_reader_total.restype = ctypes.c_uint64
        lib.nns_reader_total.argtypes = [ctypes.c_void_p]
        lib.nns_reader_read.restype = ctypes.c_int
        lib.nns_reader_read.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ]
        lib.nns_reader_prefetch.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.nns_reader_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        log.info("native core loaded: %s", so)
        return _lib


def available() -> bool:
    """True when the native core is loaded (built first if need be)."""
    return _load() is not None


def mailbox_impl() -> str:
    """Which mailbox the pipelines of this process run on: ``"native"``
    (the C++ condvar queue) or ``"queue.Queue"``."""
    return "native" if available() else "queue.Queue"


class NativeMailbox:
    """queue.Queue-compatible bounded mailbox backed by the C++ condvar
    queue.  Raises queue.Full / queue.Empty like the stdlib class."""

    def __init__(self, maxsize: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._h = lib.nns_oq_create(max(0, maxsize))
        self._maxsize = max(0, maxsize)
        self._closed = False

    # -- stdlib-compatible subset -------------------------------------------
    def put(self, item: Any, timeout: Optional[float] = None) -> None:
        if self._closed:
            raise _pyqueue.Full
        ref = ctypes.py_object(item)
        ctypes.pythonapi.Py_IncRef(ref)
        # CPython: id(obj) IS the PyObject* address
        rc = self._lib.nns_oq_push(
            self._h, id(item), -1.0 if timeout is None else float(timeout)
        )
        if rc != 0:
            ctypes.pythonapi.Py_DecRef(ref)
            raise _pyqueue.Full

    def put_nowait(self, item: Any) -> None:
        self.put(item, timeout=0.0)

    def put_many(self, items: list, timeout: Optional[float] = None) -> int:
        """Push a run of items in ONE native call (block handoff): waits
        (bounded) for space for the first, appends the rest as capacity
        allows — one lock/wakeup cycle per run instead of one per frame.
        Returns the number of leading items consumed (0 on timeout)."""
        if self._closed:
            raise _pyqueue.Full
        n_items = len(items)
        if n_items == 0:
            return 0
        arr = (ctypes.c_void_p * n_items)()
        for i, item in enumerate(items):
            # strong ref per item BEFORE the pointer enters the queue
            ctypes.pythonapi.Py_IncRef(ctypes.py_object(item))
            arr[i] = id(item)
        rc = self._lib.nns_oq_push_n(
            self._h, arr, n_items,
            -1.0 if timeout is None else float(timeout),
        )
        consumed = max(0, rc)
        for i in range(consumed, n_items):
            # unconsumed tail: release the refs taken above
            ctypes.pythonapi.Py_DecRef(ctypes.py_object(items[i]))
        if rc == -2:
            raise _pyqueue.Full  # closed
        return consumed

    def _pop(self, timeout: Optional[float]) -> Any:
        out = ctypes.c_void_p()
        rc = self._lib.nns_oq_pop(
            self._h, -1.0 if timeout is None else float(timeout),
            ctypes.byref(out),
        )
        if rc != 0:
            raise _pyqueue.Empty
        obj = ctypes.cast(out, ctypes.py_object).value
        ctypes.pythonapi.Py_DecRef(ctypes.py_object(obj))
        return obj

    def get(self, timeout: Optional[float] = None) -> Any:
        if self._closed:
            raise _pyqueue.Empty
        return self._pop(timeout)

    def get_many(self, max_n: int, timeout: Optional[float] = None) -> list:
        """Pop up to ``max_n`` items in ONE native call: wait (bounded)
        for the first, drain the rest without waiting — the micro-batch
        collector's amortized path (one lock/wakeup cycle per batch
        instead of one per frame).  Raises queue.Empty on timeout."""
        if self._closed or max_n <= 0:
            raise _pyqueue.Empty
        arr = (ctypes.c_void_p * max_n)()
        rc = self._lib.nns_oq_pop_n(
            self._h, max_n,
            -1.0 if timeout is None else float(timeout), arr,
        )
        if rc <= 0:
            raise _pyqueue.Empty
        out = []
        for i in range(rc):
            obj = ctypes.cast(arr[i], ctypes.py_object).value
            ctypes.pythonapi.Py_DecRef(ctypes.py_object(obj))
            out.append(obj)
        return out

    def get_nowait(self) -> Any:
        return self.get(timeout=0.0)

    def qsize(self) -> int:
        if self._closed:
            return 0
        return int(self._lib.nns_oq_size(self._h))

    def empty(self) -> bool:
        return self.qsize() == 0

    @property
    def maxsize(self) -> int:  # parity with queue.Queue introspection
        return self._maxsize

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        """Wake all waiters, drain, release refs.  The native queue itself
        is freed at GC (__del__): destroying here could free it under a
        straggler thread entering put/get — after close they just see the
        closed flag and raise, against still-valid memory."""
        if self._closed:
            return
        self._closed = True
        self._lib.nns_oq_close(self._h)
        while True:
            try:
                self._pop(timeout=0.0)
            except _pyqueue.Empty:
                break

    def __del__(self):  # pragma: no cover — GC order dependent
        try:
            if self._h:
                self.close()
                # no references left -> no concurrent callers; destroy
                # still waits for any waiter mid-exit in C++
                self._lib.nns_oq_destroy(self._h)
                self._h = None
        except Exception:  # allow-silent: __del__ during interpreter exit
            pass


class BufferPool:
    """Aligned recycled buffers (≙ gst_tensor_allocator): acquire() returns
    a writable memoryview over an aligned block; release() recycles it."""

    def __init__(self, block_size: int, prealloc: int = 4, alignment: int = 64):
        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._h = lib.nns_pool_create(block_size, prealloc, alignment)
        if not self._h:
            raise ValueError("bad pool parameters (alignment power of two?)")
        self.block_size = block_size

    def acquire(self):
        ptr = self._lib.nns_pool_acquire(self._h)
        if not ptr:
            raise MemoryError("pool allocation failed")
        buf = (ctypes.c_char * self.block_size).from_address(ptr)
        mv = memoryview(buf).cast("B")
        return ptr, mv

    def release(self, ptr: int) -> None:
        if self._lib.nns_pool_release(self._h, ptr) != 0:
            raise ValueError("double release of pool block")

    @property
    def outstanding(self) -> int:
        return int(self._lib.nns_pool_outstanding(self._h))

    def destroy(self) -> None:
        if self._h:
            self._lib.nns_pool_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.destroy()
        except Exception:  # allow-silent: GC-order-dependent teardown
            pass


class SampleReader:
    """mmap-backed fixed-size sample reader — the native datarepo loader.

    ≙ the reference's C data reader (gstdatareposrc.c): the repo file is
    mapped once; ``read(i)`` is a single memcpy out of the page cache with
    the GIL released, and ``prefetch(i)`` madvises the next sample so
    shuffled epochs stream without per-sample seek/read syscalls.
    """

    def __init__(self, path: str, sample_size: int):
        import numpy as np

        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._np = np
        self._h = lib.nns_reader_open(path.encode(), sample_size)
        if not self._h:
            raise OSError(f"cannot map {path!r} (empty or unreadable)")
        self.sample_size = sample_size
        self.total = int(lib.nns_reader_total(self._h))

    def read(self, index: int):
        """-> uint8 numpy array holding sample `index`."""
        # validate here too (a negative int becomes 2^64-1 through ctypes;
        # the C side also rejects, but never hand it a bad index)
        if not 0 <= int(index) < self.total:
            raise IndexError(f"sample {index} out of range (total {self.total})")
        out = self._np.empty(self.sample_size, self._np.uint8)
        rc = self._lib.nns_reader_read(
            self._h, int(index), out.ctypes.data_as(ctypes.c_void_p)
        )
        if rc != 0:
            raise IndexError(f"sample {index} out of range (total {self.total})")
        return out

    def prefetch(self, index: int) -> None:
        if self._h and 0 <= index < self.total:
            self._lib.nns_reader_prefetch(self._h, int(index))

    def close(self) -> None:
        if self._h:
            self._lib.nns_reader_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover — GC order dependent
        try:
            self.close()
        except Exception:  # allow-silent: GC-order-dependent teardown
            pass
