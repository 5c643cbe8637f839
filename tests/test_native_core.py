"""Native C++ core: object mailbox (refcount-safe, blocking) + buffer pool."""

import queue
import sys
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.native import runtime

pytestmark = pytest.mark.skipif(
    not runtime.available(), reason="native core toolchain unavailable"
)


class TestNativeMailbox:
    def test_fifo_roundtrip(self):
        mb = runtime.NativeMailbox(8)
        items = [(i, np.arange(i + 1)) for i in range(5)]
        for it in items:
            mb.put(it, timeout=1)
        assert mb.qsize() == 5
        out = [mb.get(timeout=1) for _ in range(5)]
        assert [o[0] for o in out] == [0, 1, 2, 3, 4]
        np.testing.assert_array_equal(out[3][1], np.arange(4))
        mb.close()

    def test_full_and_empty(self):
        mb = runtime.NativeMailbox(2)
        mb.put_nowait("a")
        mb.put_nowait("b")
        with pytest.raises(queue.Full):
            mb.put("c", timeout=0.05)
        assert mb.get_nowait() == "a"
        assert mb.get_nowait() == "b"
        with pytest.raises(queue.Empty):
            mb.get(timeout=0.05)
        mb.close()

    def test_refcounts_balanced(self):
        mb = runtime.NativeMailbox(4)
        obj = object()
        base = sys.getrefcount(obj)
        for _ in range(10):
            mb.put(obj, timeout=1)
            got = mb.get(timeout=1)
            assert got is obj
        del got
        assert sys.getrefcount(obj) == base
        # leftover items are released by close()
        mb.put(obj, timeout=1)
        assert sys.getrefcount(obj) == base + 1
        mb.close()
        assert sys.getrefcount(obj) == base

    def test_get_many_bulk_and_refcounts(self):
        mb = runtime.NativeMailbox(32)
        obj = object()
        base = sys.getrefcount(obj)
        for i in range(10):
            mb.put((i, obj), timeout=1)
        first = mb.get_many(4, timeout=1)
        assert [p[0] for p in first] == [0, 1, 2, 3]
        rest = mb.get_many(32, timeout=1)  # drains without waiting
        assert [p[0] for p in rest] == [4, 5, 6, 7, 8, 9]
        with pytest.raises(queue.Empty):
            mb.get_many(4, timeout=0.05)
        del first, rest
        assert sys.getrefcount(obj) == base  # one DecRef per popped item
        mb.close()

    def test_get_many_wakes_blocked_producer(self):
        # bulk pop frees several slots at once; every blocked producer
        # must wake (notify_all path)
        mb = runtime.NativeMailbox(2)
        mb.put_nowait(1)
        mb.put_nowait(2)
        done = []

        def producer(v):
            mb.put(v, timeout=5)
            done.append(v)

        threads = [threading.Thread(target=producer, args=(v,))
                   for v in (3, 4)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        assert mb.get_many(2, timeout=1) == [1, 2]
        for t in threads:
            t.join(timeout=5)
        assert sorted(done) == [3, 4]
        assert sorted(mb.get_many(2, timeout=1)) == [3, 4]
        mb.close()

    def test_blocking_handoff_across_threads(self):
        mb = runtime.NativeMailbox(1)
        got = []

        def consumer():
            for _ in range(20):
                got.append(mb.get(timeout=5))

        t = threading.Thread(target=consumer)
        t.start()
        for i in range(20):
            mb.put(i, timeout=5)
        t.join(timeout=10)
        assert got == list(range(20))
        mb.close()

    def test_blocked_get_is_one_native_wait_woken_by_put(self):
        # the point of the native condvar: a blocked get() is ONE native
        # wait, with no timeout to tick, that put() wakes -- not a loop of
        # timed polls.  Counted at the library boundary, never timed.
        mb = runtime.NativeMailbox(1)
        pops, parked, got = [], threading.Event(), []

        class CountingLib:
            def __init__(self, lib):
                self._lib = lib

            def __getattr__(self, name):
                return getattr(self._lib, name)

            def nns_oq_pop(self, handle, timeout, out):
                pops.append(timeout)
                parked.set()
                return self._lib.nns_oq_pop(handle, timeout, out)

        mb._lib = CountingLib(mb._lib)
        t = threading.Thread(target=lambda: got.append(mb.get()))
        t.start()
        assert parked.wait(timeout=5)  # the consumer entered the wait
        assert got == []
        mb.put("x", timeout=1)
        t.join(timeout=5)
        assert got == ["x"]
        assert pops == [-1.0]  # one call, unbounded: only put() ends it
        mb.close()


class TestBufferPool:
    def test_acquire_release_recycles(self):
        pool = runtime.BufferPool(1024, prealloc=2, alignment=64)
        ptr1, mv1 = pool.acquire()
        assert ptr1 % 64 == 0
        mv1[:4] = b"abcd"
        assert pool.outstanding == 1
        del mv1  # memoryview must be dropped before the block is reused
        pool.release(ptr1)
        assert pool.outstanding == 0
        ptr2, mv2 = pool.acquire()
        del mv2
        pool.release(ptr2)
        pool.destroy()

    def test_bad_alignment_rejected(self):
        with pytest.raises(ValueError):
            runtime.BufferPool(128, alignment=48)

    def test_double_release_rejected(self):
        pool = runtime.BufferPool(64, prealloc=1)
        ptr, mv = pool.acquire()
        del mv
        pool.release(ptr)
        with pytest.raises(ValueError):
            pool.release(ptr)
        pool.destroy()

    def test_use_after_close_raises_not_crashes(self):
        mb = runtime.NativeMailbox(2)
        mb.put("x")
        mb.close()
        with pytest.raises(queue.Full):
            mb.put("y")
        with pytest.raises(queue.Empty):
            mb.get(timeout=0.0)
        assert mb.qsize() == 0
        mb.close()  # idempotent

    def test_close_while_waiter_parked(self):
        mb = runtime.NativeMailbox(1)
        errs = []

        def consumer():
            try:
                mb.get(timeout=5)
            except queue.Empty:
                errs.append("empty")

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.1)  # consumer parked in the native wait
        mb.close()       # must wake it and not free memory under it
        t.join(timeout=5)
        assert errs == ["empty"]


class TestPipelineUsesNative:
    def test_pipeline_runs_on_native_mailboxes(self):
        from nnstreamer_tpu.pipeline import parse_pipeline

        # fuse=False: this test asserts the MAILBOX implementation, and
        # fused chains elide intermediate mailboxes entirely
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_transform mode=arithmetic "
            "option=mul:2 ! tensor_sink name=out",
            fuse=False,
        )
        pipe.start()
        mb = pipe["out"]._mailbox
        assert type(mb).__name__ == "NativeMailbox"
        for i in range(16):
            pipe["src"].push(np.float32([i]))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()
        frames = pipe["out"].frames
        assert len(frames) == 16
        assert float(frames[5].tensors[0][0]) == 10.0


class TestSampleReader:
    def test_reads_match_python_path(self, tmp_path):
        import numpy as np

        from nnstreamer_tpu.native.runtime import SampleReader, available

        if not available():
            pytest.skip("native core not buildable")
        rng = np.random.default_rng(0)
        data = rng.integers(0, 255, (10, 64), np.uint8)
        path = tmp_path / "samples.bin"
        path.write_bytes(data.tobytes())
        r = SampleReader(str(path), 64)
        assert r.total == 10
        for i in (0, 3, 9):
            np.testing.assert_array_equal(r.read(i), data[i])
        r.prefetch(5)  # advisory; must not fail
        with pytest.raises(IndexError):
            r.read(10)
        with pytest.raises(IndexError):
            r.read(-1)  # would wrap to 2^64-1 through ctypes (SIGSEGV bug)
        r.prefetch(-1)  # clamped, must not crash
        r.close()

    def test_open_missing_file(self):
        from nnstreamer_tpu.native.runtime import SampleReader, available

        if not available():
            pytest.skip("native core not buildable")
        with pytest.raises(OSError):
            SampleReader("/nonexistent/x.bin", 8)
