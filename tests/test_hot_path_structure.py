"""The hot path's structure, as counts and orders.

What each optimisation of the dataplane IS, held to exact facts: which
thread ran what, how many mailboxes a frame crossed, how many batches
the window held, how many device steps a token cost, how many programs
steady traffic compiled.  Nothing here compares a clock with a constant:
waits are bounded by deadlines, and every assertion holds however busy
the machine is.  How fast any of it runs is `benchmark/run.py`'s to say,
on the chip.
"""

import contextlib
import threading
import time
import tracemalloc

import numpy as np
import pytest

from nnstreamer_tpu.core.buffer import FRAME_POOL, TensorFrame
from nnstreamer_tpu.pipeline import parse_pipeline

CHAIN = (
    "appsrc name=src max-buffers=256 ! identity ! identity ! identity ! "
    "tensor_sink name=out max-stored=1"
)
WARMUP, FRAMES = 32, 300


def _until(cond, timeout=60.0, step=0.002):
    """Bounded wait for ``cond()``: a deadline, never a measurement."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(step)
    return True


def _push_through(pipe, settled=lambda: True, n=FRAMES):
    """The one push loop: start, ``WARMUP`` frames, ``n`` frames, wait for
    ``settled`` (an instrument's first observation), EOS, stop.  Returns
    what was delivered after the warm-up, the names of the threads the
    sink callback ran on, the names of the segment workers, and the
    pipeline's metrics snapshot as it stood before the stop."""
    pipe.start()
    src, sink = pipe["src"], pipe["out"]
    got, sink_threads = [], set()

    def on_frame(f):
        sink_threads.add(threading.current_thread().name)
        got.append(float(np.asarray(f.tensors[0])[0]))

    sink.connect_new_data(on_frame)
    workers = {seg.chain[0].name for seg in pipe._segments}
    for i in range(WARMUP + n):
        src.push(np.full((64,), i, np.float32))
    assert _until(lambda: len(got) >= WARMUP + n), (
        f"frames lost: {len(got)}/{WARMUP + n}")
    assert _until(settled, timeout=30.0), "the instrument never observed"
    src.end_of_stream()
    pipe.wait(timeout=30)
    snap = pipe.metrics_snapshot()
    pipe.stop()
    return got[WARMUP:], sink_threads, workers, snap


# ---------------------------------------------------------------------------
# Streaming-thread fusion
# ---------------------------------------------------------------------------
QUEUED = (
    "appsrc name=src max-buffers=256 ! identity ! queue name=q ! identity ! "
    "tensor_sink name=out max-stored=1"
)


@pytest.mark.parametrize("chain,fuse,threads,mailboxes", [
    (CHAIN, True, 1, 0),     # everything on the source's thread
    (CHAIN, False, 5, 4),    # a thread and a mailbox per element
    (QUEUED, True, 2, 1),    # an explicit queue keeps its boundary
], ids=["fused", "unfused", "queue_boundary"])
def test_fusion_is_workers_and_mailboxes(chain, fuse, threads, mailboxes):
    """What fusion is, counted: fused, the source, the identities and the
    sink run on ONE streaming thread and a frame crosses no mailbox
    between them; unfused, each of the five has a thread and a frame
    crosses the four mailboxes between them; an explicit ``queue`` splits
    a fused chain into two workers with the one mailbox a frame crosses.
    Nothing is lost or reordered in any of them."""
    pipe = parse_pipeline(chain, name="fusion", fuse=fuse)
    tracer = pipe.enable_tracing()  # stamps one queue-wait per crossing
    got, sink_threads, workers, _ = _push_through(pipe)
    assert got == [float(i) for i in range(WARMUP, WARMUP + FRAMES)]
    chains = [[e.name for e in seg.chain] for seg in pipe._segments]
    boxes = [n for n, e in pipe.elements.items() if e._mailbox is not None]
    crossings = {
        el: h.count for el, name, h in tracer.latency_histograms()
        if name == "nns.element.queue_wait_seconds"}
    handled = {
        el: h.count for el, name, h in tracer.latency_histograms()
        if name == "nns.element.handle_seconds"}
    # every element handled every frame, on whichever thread
    assert len(handled) == len(pipe.elements) - 1
    assert set(handled.values()) == {WARMUP + FRAMES}
    assert sum(len(c) for c in chains) == 5
    assert len(chains) == len(workers) == threads
    assert len(boxes) == mailboxes
    assert crossings == dict.fromkeys(boxes, WARMUP + FRAMES)
    # the sink runs on the worker that heads its segment
    assert sink_threads == {
        c[0] for c in chains if "out" in c}


# ---------------------------------------------------------------------------
# Armed instruments stay off the frame path
# ---------------------------------------------------------------------------
# Each case arms one instrument on the fused identity chain (or beside
# it), yields ``(settled, verify)``: ``settled()`` turns true once the
# instrument has observed, ``verify(snap)`` holds its structural half
# against the pipeline's last metrics snapshot.  Where the test injects
# a callback into the instrument, the callback records the thread it ran
# on in ``seen``.
def _here(seen):
    seen.add(threading.current_thread().name)


def _calm_memory(seen):
    """A memory sample far under every watermark, noting who asked."""
    def sample():
        _here(seen)
        return {"device_frac": 0.1, "host_frac": 0.1}
    return sample


@contextlib.contextmanager
def _disabled(pipe, seen):
    from nnstreamer_tpu.core import telemetry

    def verify(snap):
        # disabled means absent: no tracer, recorder, monitor, sweeper
        # or endpoint object for a frame to reach
        assert pipe.tracer is None and pipe.flight_recorder is None
        assert pipe.memory_monitor is None and pipe._wd_thread is None
        assert pipe.metrics_port is None
        assert telemetry.live_server_count() == 0

    yield (lambda: True), verify


@contextlib.contextmanager
def _histograms(pipe, seen):
    tracer = pipe.enable_tracing()

    def verify(snap):
        hists = {(el, name): h for el, name, h in tracer.latency_histograms()}
        # one observation per call, surfaced in the pipeline's snapshot
        # under the stable names
        assert hists[("out", "nns.element.handle_seconds")].count == (
            WARMUP + FRAMES)
        assert snap.sum("nns.element.handle_seconds_count", element="out") == (
            WARMUP + FRAMES)
        assert snap.get("nns.element.handle_p99_us", element="out") > 0

    yield (lambda: True), verify


@contextlib.contextmanager
def _flight_recorder(pipe, seen):
    rec = pipe.enable_flight_recorder(capacity=64, profile_incidents=False)

    def verify(snap):
        spans = [s for tl in rec.timelines().values() for s in tl]
        # the ring is bounded and full of the newest calls; a healthy
        # run dumped nothing
        assert len(spans) == 64
        assert {s["element"] for s in spans} <= set(pipe.elements)
        assert rec.dumps == 0 and rec.suppressed == 0

    yield (lambda: True), verify


@contextlib.contextmanager
def _memory_monitor(pipe, seen):
    mon = pipe.enable_memory_monitor(
        min_poll_s=0.01, sample=_calm_memory(seen))

    def verify(snap):
        assert mon.polls > 0 and mon.trims == 0
        assert seen == {f"{pipe.name}-watchdog"}
        off = parse_pipeline(CHAIN, name="memoff", fuse=True)
        assert off.memory_monitor is None

    yield (lambda: mon.polls > 0), verify


@contextlib.contextmanager
def _watchdog(pipe, seen):
    pipe["out"].set_property("stall-timeout", 30.0)
    pipe.register_sweep(lambda: _here(seen), 0.02)

    def verify(snap):
        snap = pipe._watchdog.snapshot()["out"]
        # the watch counted every frame the sink finished; the sweeper
        # found nothing to report
        assert snap["frames_done"] == WARMUP + FRAMES
        assert snap["stalls"] == 0
        assert seen == {f"{pipe.name}-watchdog"}

    yield (lambda: bool(seen)), verify


@contextlib.contextmanager
def _metrics_endpoint(pipe, seen):
    import urllib.request

    from nnstreamer_tpu.core.telemetry import REGISTRY

    def collector():
        _here(seen)
        return []

    REGISTRY.register_collector(collector)
    body = []

    def scrape():
        port = pipe.metrics_port or pipe.serve_metrics()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body.append(r.read().decode())
        return True

    def verify(snap):
        assert "nns_" in body[0]
        # collection is scrape-time only, on the endpoint's own thread
        assert seen and threading.main_thread().name not in seen

    try:
        yield scrape, verify
    finally:
        REGISTRY.unregister_collector(collector)


@contextlib.contextmanager
def _fleet_observatory(pipe, seen):
    from nnstreamer_tpu.core.fleet import (
        DigestPublisher,
        FleetObservatory,
        pipeline_digest_stats,
    )
    from nnstreamer_tpu.core.telemetry import REGISTRY, SloTracker

    obs = FleetObservatory(topic="armed", default_ttl_s=60.0)
    REGISTRY.register_collector(obs._collect)
    slo = SloTracker(ttft_p95_s=0.5, token_p99_s=0.01, availability=0.99)
    slo.note_ttft("armed", 0.01)
    slo.note_tokens("armed", 0.02, 8)
    slo.note_stream("armed", "good")

    def source():
        _here(seen)
        return {**pipeline_digest_stats(pipe), "inflight": 0,
                "slo_burn": {t: r.get("ttft_burn", 0.0)
                             for t, r in slo.snapshot().items()}}

    def publish(d):
        _here(seen)
        obs.ingest("nns/query/armed/a", {"host": "x", "port": 1, "digest": d})

    pub = DigestPublisher(source, publish, interval_s=0.02, name="armed")
    pipe.register_sweep(pub.poll, 0.02)

    def verify(snap):
        assert pub.published > 0 and pub.publish_failures == 0
        assert obs.rollup()["digests"] > 0
        assert seen == {f"{pipe.name}-watchdog"}

    try:
        yield (lambda: pub.published > 0), verify
    finally:
        REGISTRY.unregister_collector(obs._collect)


@contextlib.contextmanager
def _autoscale_controller(pipe, seen):
    from nnstreamer_tpu.core.autoscale import FleetController, NullActuator
    from nnstreamer_tpu.core.fleet import FleetObservatory

    class Observed(FleetObservatory):
        def snapshot(self, *a, **kw):
            _here(seen)
            return super().snapshot(*a, **kw)

    obs = Observed(topic="armed", default_ttl_s=60.0)
    # one healthy idle server: without it the envelope floor would spawn
    obs.ingest("nns/query/armed/a", {"host": "x", "port": 1, "digest": {
        "v": 1, "seq": 1, "age_s": 0.0, "interval_s": 1.0, "ttl_s": 60.0,
        "draining": False, "degraded": False, "swap": "idle",
        "inflight": 0, "admitted": 0, "shed": 0, "tokens_per_s": 0.0,
        "slots": 4, "occupied": 0}})
    actuator = NullActuator()
    ctrl = FleetController(obs, actuator).attach(pipe, interval_s=0.02)

    def verify(snap):
        # the loop ran and stayed calm: ticks, no decision, no actuation
        assert ctrl.ticks > 0 and ctrl.state.decisions == 0
        assert actuator.calls == []
        assert seen == {f"{pipe.name}-watchdog"}

    try:
        yield (lambda: ctrl.ticks > 0 and bool(seen)), verify
    finally:
        ctrl.stop()


@contextlib.contextmanager
def _prefix_cache_cold(pipe, seen):
    gen_pipe = parse_pipeline(
        "appsrc name=src ! tensor_generator name=gen slots=2 custom=sim:1 "
        "max-new=4 prefix-cache=on prefix-grain=32 prefill-chunk=4 ! "
        "tensor_sink name=out", name="prefixidle")
    gen_pipe.start()
    gen_pipe.enable_memory_monitor(
        high=0.99, low=0.9, min_poll_s=0.01, sample=_calm_memory(seen))

    def verify(snap):
        h = gen_pipe.health()["gen"]
        # armed, idle, cold: the pool exists and was never consulted
        assert gen_pipe["gen"]._prefix_pool is not None
        assert (h["prefix_hits"], h["prefix_misses"],
                h["prefix_entries"], h["gen_tokens"]) == (0, 0, 0, 0)
        assert seen <= {"prefixidle-watchdog"}

    try:
        yield (lambda: True), verify
    finally:
        gen_pipe["src"].end_of_stream()
        gen_pipe.wait(timeout=30)
        gen_pipe.stop()


@contextlib.contextmanager
def _control_plane(pipe, seen):
    from nnstreamer_tpu.core.autoscale import (
        FleetController, FleetPolicy, LeaderLease, LeaseChannel,
        NullActuator)
    from nnstreamer_tpu.core.fleet import FleetObservatory
    from nnstreamer_tpu.distributed.mqtt import MiniBroker

    broker = MiniBroker()
    obs = FleetObservatory(topic="armedcp", default_ttl_s=5.0)
    chan = None
    stop = threading.Event()
    try:
        obs.start("127.0.0.1", broker.port)
        lease = LeaderLease("armed-ctl", ttl_s=1.0)
        chan = LeaseChannel("127.0.0.1", broker.port, "armedcp", lease)
        ctrl = FleetController(obs, NullActuator(),
                               policy=FleetPolicy(min_servers=0),
                               lease=lease)

        def acquire():
            ctrl.tick()          # vacancy watch, then acquire
            return lease.held

        assert _until(acquire, timeout=10.0, step=0.02), (
            "lease never acquired against a live broker")
        ticks0 = ctrl.ticks

        def churn():
            while not stop.is_set():
                _here(seen)
                ctrl.tick()      # renew + assess_plane every 20ms
                time.sleep(0.02)

        th = threading.Thread(target=churn, name="armed-ctl", daemon=True)
        th.start()

        def verify(snap):
            stop.set()
            th.join(timeout=5.0)
            assert ctrl.ticks > ticks0
            assert lease.held and lease.self_fences == 0
            assert seen == {"armed-ctl"}

        yield (lambda: ctrl.ticks > ticks0), verify
    finally:
        stop.set()
        if chan is not None:
            chan.close()
        obs.stop()
        broker.close()


_ARMED = {
    "telemetry_disabled": _disabled,
    "histograms": _histograms,
    "flight_recorder": _flight_recorder,
    "memory_monitor": _memory_monitor,
    "liveness_watchdog": _watchdog,
    "metrics_endpoint": _metrics_endpoint,
    "fleet_observatory": _fleet_observatory,
    "autoscale_controller": _autoscale_controller,
    "prefix_cache_cold": _prefix_cache_cold,
    "control_plane": _control_plane,
}


@pytest.mark.parametrize("case", list(_ARMED))
def test_armed_instrument_stays_off_the_frame_path(case):
    """With the instrument armed and observing, the fused identity chain
    delivers every frame in order on its one worker, and whatever the
    instrument runs, it runs on a thread of its own (the sweeper, the
    endpoint, the controller), never on the segment worker."""
    pipe = parse_pipeline(CHAIN, name=f"armed-{case}", fuse=True)
    seen = set()
    with _ARMED[case](pipe, seen) as (settled, verify):
        got, sink_threads, workers, snap = _push_through(pipe, settled)
        verify(snap)
    assert got == [float(i) for i in range(WARMUP, WARMUP + FRAMES)]
    assert workers == sink_threads == {"src"}
    assert not seen & workers, f"instrument ran on a segment worker: {seen}"


# ---------------------------------------------------------------------------
# Unchanged exact gates: OOM ladder parity, allocation budget, pools,
# block handoff
# ---------------------------------------------------------------------------
def test_oom_retry_accounting_parity_fused_vs_unfused():
    """PR-14 satellite: the OOM shrink-retry ladder produces IDENTICAL
    outputs and identical ``oom_retries``/``oom_shrinks`` accounting
    fused and unfused — recovery must not depend on the threading
    topology."""
    def run(fuse: bool):
        pipe = parse_pipeline(
            "appsrc name=src ! "
            "tensor_filter name=f framework=async-sim custom=oom_at:0 "
            "max-batch=8 ! tensor_sink name=out max-stored=64",
            name=f"oomparity{fuse}", fuse=fuse)
        pipe.start()
        got = []
        pipe["out"].connect_new_data(
            lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
        pipe["src"].push_block(
            np.arange(8, dtype=np.float32).reshape(8, 1))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        h = pipe.health()["f"]
        pipe.stop()
        # oom_evictions excluded from the parity tuple: it counts
        # whatever the PROCESS-WIDE staging pool happened to hold when
        # the trim fired, which earlier tests legitimately vary
        return got, (h["oom_retries"], h["oom_shrinks"],
                     h["dead_letters"], h["restarts"])
    got_f, acc_f = run(True)
    got_u, acc_u = run(False)
    assert got_f == got_u == [v * 2.0 + 1.0 for v in range(8)]
    assert acc_f == acc_u == (1, 1, 0, 0)


def test_hot_path_allocation_budget():
    """tracemalloc gate: the fused dispatch loop must not RETAIN
    allocations per frame in steady state (frame-pool regression, a
    per-frame cache that never evicts, stash leaks...).  Budget: <= 5
    retained allocations and <= 2 KiB retained bytes per frame, measured
    over 300 frames after warmup — actual steady state is ~0.1/frame, so
    the margin is >10x."""
    pipe = parse_pipeline(CHAIN, name="alloc", fuse=True)
    pipe.start()
    src, sink = pipe["src"], pipe["out"]
    done = {"n": 0}
    sink.connect_new_data(lambda f: done.__setitem__("n", done["n"] + 1))
    arr = np.zeros((64,), np.float32)
    for _ in range(200):  # warmup: pool/jit/thread steady state
        src.push(TensorFrame([arr]))
    _until(lambda: done["n"] >= 200, timeout=30)
    n = 300
    # frames pre-created OUTSIDE the traced window: the budget pins the
    # dispatch loop, not the application's ingest allocations
    frames = [TensorFrame([arr]) for _ in range(n)]
    done["n"] = 0
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for f in frames:
        src.push(f)
    _until(lambda: done["n"] >= n, timeout=30)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    src.end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    assert done["n"] == n
    diff = after.compare_to(before, "filename")
    count = sum(max(0, d.count_diff) for d in diff)
    size = sum(max(0, d.size_diff) for d in diff)
    assert count / n <= 5, f"retained {count / n:.1f} allocations/frame"
    assert size / n <= 2048, f"retained {size / n:.0f} bytes/frame"


def test_frame_pool_reuses_carcasses():
    """The free-list actually cycles: a capped sink evicting frames feeds
    the pool, and BatchFrame.split / filter emission draw from it."""
    reused_before = FRAME_POOL.reused
    recycled_before = FRAME_POOL.recycled
    from nnstreamer_tpu.core.buffer import BatchFrame

    block = BatchFrame(
        tensors=[np.zeros((8, 4), np.float32)],
        frames_info=[(float(i), None, {}) for i in range(8)],
    )
    for _ in range(10):
        lfs = block.split()
        while lfs:
            # recycle() demands the caller hold the LAST reference: pop
            # the frame out of the list before handing it over
            f = lfs.pop()
            assert FRAME_POOL.recycle(f)
    assert FRAME_POOL.recycled >= recycled_before + 80
    assert FRAME_POOL.reused >= reused_before + 72  # rounds 2-10 reuse


def test_block_handoff_single_queue_op():
    """_push_outs delivers a run of outputs bound for one destination as
    one bulk mailbox operation, preserving order and events."""
    from nnstreamer_tpu.pipeline.pipeline import _LeakyMailbox

    box = _LeakyMailbox(8, "upstream")
    items = [(0, TensorFrame([np.zeros(2)])) for _ in range(5)]
    n = box.put_many(items, timeout=0.0)
    assert n == 5 and box.qsize() == 5
    # order preserved
    out = [box.get(timeout=0.1) for _ in range(5)]
    assert out == items
    # leaky policy under one lock: 10 frames into depth 8 drops 2
    n = box.put_many(
        [(0, TensorFrame([np.zeros(2)])) for _ in range(10)], timeout=0.0
    )
    assert n == 10 and box.qsize() == 8


# ---------------------------------------------------------------------------
# Async device feed: the dispatch window, the staging lane, its pool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("costs,depth", [
    ("transfer_ms:4,dispatch_ms:1", 8),      # the window's acceptance costs
    ("transfer_ms:2,dispatch_ms:0.5", 8),    # the pipeline-vs-raw costs
    ("transfer_ms:4,dispatch_ms:1", 2),      # the shallowest window
], ids=["window", "pipeline_vs_raw", "depth2"])
def test_dispatch_window_holds_depth_batches_and_never_syncs(costs, depth):
    """The async window, counted: with the fake device holding every
    completion (``manual``), the dispatch thread parks exactly
    ``dispatch-depth`` batches and dispatches no further (more than one
    batch in flight is what hides framework cost behind compute); once
    completions flow, every output arrives, in order; and the dispatch
    thread is never seen inside a device_get-style blocking sync — the
    window's reaper owns every pre-completion wait."""
    mb, nbatches = 8, 14
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=512 ! tensor_filter name=f "
        f"framework=async-sim custom=manual:1,{costs} "
        f"max-batch={mb} dispatch-depth={depth} ingest-lane=off ! "
        "tensor_sink name=out max-stored=1",
        name="window",
    )
    pipe.start()
    got = []
    pipe["out"].connect_new_data(
        lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
    be, el = pipe["f"].backend, pipe["f"]
    n = mb * nbatches
    for i in range(n):
        pipe["src"].push(np.full((64,), i, np.float32))
    assert _until(lambda: be.dispatched >= depth, timeout=30)
    time.sleep(0.2)  # room for a dispatch past the window to show itself
    held = (be.dispatched, len(el._inflight))
    early = list(got)
    deadline = time.monotonic() + 60
    while len(got) < n and time.monotonic() < deadline:
        be.release_all()  # completions flow: the window drains and refills
        time.sleep(0.002)
    foreign_syncs = [
        t for t in be.blocking_syncs if not t.endswith("-reaper")]
    dispatched = be.dispatched
    pipe["src"].end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    assert held == (depth, depth), (
        f"window held {held[1]} batches after {held[0]} dispatches")
    # a frame that left before any completion came alone through the
    # synchronous single-frame invoke, ahead of the first batch
    assert early == [2.0 * i + 1.0 for i in range(len(early))]
    assert got == [2.0 * i + 1.0 for i in range(n)]
    assert depth < dispatched <= n
    assert foreign_syncs == [], (
        f"dispatch thread blocked in device_get: {foreign_syncs}")


def test_host_ingest_lane_stages_ahead_of_the_consumer():
    """The double buffer, as an order: the lane's own thread enters the
    transfer of batch i+1 while the consumer still computes on batch i,
    before anyone awaits it, for every i; transfers run in submit order
    and never on the submitting thread."""
    from nnstreamer_tpu.core.feed import HostStagingLane

    nb = 16
    log, lock = [], threading.Lock()
    entered = [threading.Event() for _ in range(nb)]
    seq = iter(range(nb))

    def to_dev(arrs):
        i = next(seq)
        with lock:
            log.append(("enter", i, threading.current_thread().name))
        entered[i].set()
        out = [np.array(a) for a in arrs]
        with lock:
            log.append(("exit", i, threading.current_thread().name))
        return out

    frames = [[np.zeros((256,), np.float32)] for _ in range(8)]
    lane = HostStagingLane(to_dev, name="overlap")
    try:
        jobs = [lane.submit(frames)]
        for i in range(nb):
            if i + 1 < nb:
                jobs.append(lane.submit(frames))   # stage i+1 ...
            with lock:
                log.append(("await", i, None))
            jobs[i].result()                       # ... collect i ...
            if i + 1 < nb:                         # ... "compute" on i:
                assert entered[i + 1].wait(30), (  # i+1 is already moving
                    f"batch {i + 1} not staged until it was awaited")
    finally:
        lane.close()
    order = {(kind, i): k for k, (kind, i, _) in enumerate(log)}
    for i in range(1, nb):
        assert order[("enter", i)] < order[("await", i)]
        assert order[("exit", i - 1)] < order[("enter", i)]
    assert {t for kind, _, t in log if kind != "await"} == {"overlap-stage"}


def test_device_buffer_pool_reuse_rate():
    """Acceptance gate: steady-state staging performs zero per-batch
    buffer allocations — the lane's double-buffered ring settles on <= 3
    buffers per (shape, dtype) and every later batch reuses one
    (reuse rate >= 0.8 over 20 batches)."""
    from nnstreamer_tpu.core.buffer import DeviceBufferPool
    from nnstreamer_tpu.core.feed import HostStagingLane

    pool = DeviceBufferPool(max_per_key=8)
    lane = HostStagingLane(
        lambda arrs: [np.array(a) for a in arrs], pool=pool, name="pool")
    frames = [[np.zeros((128,), np.float32)] for _ in range(8)]
    try:
        prev = None
        for _ in range(20):
            job = lane.submit(frames)
            if prev is not None:
                prev.result()
            prev = job
        prev.result()
    finally:
        lane.close()
    assert pool.allocated <= 3, (
        f"staging ring allocates per batch: {pool.allocated} allocations"
    )
    assert pool.reuse_rate >= 0.8, (
        f"staging-buffer reuse regressed: {pool.reuse_rate:.2f} < 0.8 "
        f"({pool.reused} reused / {pool.allocated} allocated)"
    )


def test_ingest_lane_end_to_end_zero_alloc_steady_state():
    """The lane wired through the element: a host-ingest pipeline with
    ingest-lane=on stages every micro-batch through the pool (global
    DEVICE_POOL counters grow, reuse dominates) and loses nothing."""
    from nnstreamer_tpu.core.buffer import DEVICE_POOL

    pipe = parse_pipeline(
        "appsrc name=src max-buffers=512 ! tensor_filter name=f "
        "framework=async-sim custom=compute_ms:3 max-batch=8 "
        "dispatch-depth=4 ingest-lane=on ! tensor_sink name=out",
        name="laneperf",
    )
    pipe.start()
    reused0, alloc0 = DEVICE_POOL.reused, DEVICE_POOL.allocated
    n = 8 * 16
    for i in range(n):
        pipe["src"].push(np.float32([i]))
    pipe["src"].end_of_stream()
    lane = pipe["f"]._lane
    pipe.wait(timeout=30)
    staged = lane.staged
    pipe.stop()
    outs = [float(f.tensors[0][0]) for f in pipe["out"].frames]
    assert outs == [2.0 * i + 1.0 for i in range(n)]  # FIFO, zero loss
    assert staged >= 8  # the lane really carried the ingest
    reused = DEVICE_POOL.reused - reused0
    allocated = DEVICE_POOL.allocated - alloc0
    # every staged batch acquired its buffer from the pool (one tensor
    # per frame here, so acquires == staged); ragged scheduler batching
    # mints a few distinct (n, 1) shape keys, each allowed its small
    # double-buffer ring — a pool bypass (acquires == 0) or a broken
    # release (allocated == staged) both fail loudly
    assert reused + allocated == staged, (
        f"pool bypass on the lane path: {reused} reused + "
        f"{allocated} allocated != {staged} staged batches"
    )
    assert allocated <= 10, (
        f"staging ring allocates per batch: {allocated} allocations "
        f"over {staged} staged batches"
    )


@pytest.mark.parametrize("first", [0, 1])
def test_sharded_feed_serves_both_shards_and_waits_for_both(first):
    """The dp:2 feed over the async-sim mesh twin, as a ledger: every
    batch queues one shard on EACH shard server (the same number on
    both), no batch leaves the window while one of its shards is still in
    service — even with the other server done with everything — and each
    completion of the slower one frees exactly one batch, in order."""
    mb, nbatches = 4, 6
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=64 ! tensor_filter name=f "
        "framework=async-sim custom=manual:1,mesh_dp:2 "
        f"max-batch={mb} dispatch-depth={nbatches} ingest-lane=off ! "
        "tensor_sink name=out max-stored=1",
        name="meshfeed",
    )
    pipe.start()
    got = []
    pipe["out"].connect_new_data(
        lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
    be = pipe["f"].backend
    try:
        for b in range(nbatches):
            pipe["src"].push_block(
                np.arange(b * mb, (b + 1) * mb, dtype=np.float32)
                .reshape(mb, 1))
        assert _until(lambda: be.dispatched >= nbatches, timeout=30)

        def queued():
            with be._cv:
                return [len(q) for q in be._pending]

        assert queued() == [nbatches, nbatches]
        # one shard server finishes ALL its shards: still no batch is ready
        for _ in range(nbatches):
            assert be.release_one(first)
        time.sleep(0.2)
        assert queued()[first] == 0 and queued()[1 - first] == nbatches
        assert got == [] and len(pipe["f"]._inflight) == nbatches, (
            f"a batch left the window with only shard {first} ready")
        # the other finishes them one by one: each frees one batch
        for b in range(nbatches):
            assert be.release_one(1 - first)
            assert _until(
                lambda: len(pipe["f"]._inflight) == nbatches - b - 1,
                timeout=30)
        assert _until(lambda: len(got) >= mb * nbatches, timeout=30)
        assert got == [2.0 * i + 1.0 for i in range(mb * nbatches)]
        assert all(t.endswith("-reaper") for t in be.blocking_syncs)
    finally:
        be.release_all()
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()


# ---------------------------------------------------------------------------
# Fleet routing
# ---------------------------------------------------------------------------
class _CountingLock:
    """A lock that counts its acquisitions (context-manager use only)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acquired = 0

    def __enter__(self):
        self._lock.acquire()
        self.acquired += 1
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False


@pytest.mark.parametrize("policy,want", [
    ("least-inflight", [2, 1, 0]),   # fewest requests in flight first
    ("ewma", [1, 2, 0]),             # lowest smoothed latency first
])
def test_routing_decision_is_lock_free_and_follows_its_signals(policy, want):
    """The routing layer's contract with the RPC hot path: one
    ``_route_order`` call takes none of the client's locks but (at most)
    ``_breakers_lock``, touches no connection, and returns a permutation
    of the pool ordered by the signals it was given."""
    from nnstreamer_tpu.elements.query import _PoolState
    from nnstreamer_tpu.pipeline.element import make_element

    el = make_element("tensor_query_client", "q")
    el.props["routing"] = policy
    targets = [("127.0.0.1", 7310 + i) for i in range(3)]
    # bare objects for connections: dialing, or any call on one, raises
    ps = _PoolState([object()] * 3, targets, 0)
    el._pstate = ps
    for t in targets:
        el._breaker_for(t)  # pre-create (steady-state shape)
    # each policy gets a signal only it reads: requests in flight, or
    # smoothed end-to-end latency
    inflight = {7310: 5, 7311: 2, 7312: 1} if policy != "ewma" else {}
    e2e_ms = ({7310: 30.0, 7311: 4.0, 7312: 9.0} if policy == "ewma"
              else dict.fromkeys((7310, 7311, 7312), 10.0))
    with el._breakers_lock:
        for h, p in targets:
            if p in inflight:
                el._remote_inflight[f"{h}:{p}"] = inflight[p]
            el._remote_spans[f"{h}:{p}"] = {
                "e2e_ms": e2e_ms[p], "requests": 100}
        el._spans_rev += 1
    locks = {}
    for name in ("_breakers_lock", "_rediscover_lock", "_discover_leader"):
        locks[name] = _CountingLock()
        setattr(el, name, locks[name])
    orders = [el._route_order(ps, None, first) for first in range(6)]
    assert all(sorted(o) == [0, 1, 2] for o in orders)
    assert all(o == want for o in orders), orders
    taken = {name for name, lk in locks.items() if lk.acquired}
    assert taken <= {"_breakers_lock"}, f"routing took {taken}"


# ---------------------------------------------------------------------------
# Continuous batching, as step counts
# ---------------------------------------------------------------------------
def _sim_tokens(prompt, n, vocab=997):
    t = int(prompt.sum()) % vocab
    out = [t]
    for _ in range(n - 1):
        t = (31 * t + 17) % vocab
        out.append(t)
    return out


def _tokens_by_stream(frames):
    """Each stream's tokens, its chunk frames joined in chunk order."""
    streams = {}
    for f in sorted(frames, key=lambda f: (f.meta["stream_seq"],
                                           f.meta["chunk_index"])):
        if f.tensors:
            streams.setdefault(f.meta["stream_seq"], []).extend(
                np.asarray(f.tensors[0]).reshape(-1).tolist())
    return sorted(streams.values())


def _generate_through_slots(slots, prompts, max_new=64, chunk=8):
    """Four prompts as ONE block through a slotted generator pipeline;
    returns the engine's ledger and each stream's tokens."""
    pipe = parse_pipeline(
        f"appsrc name=src max-buffers=64 ! tensor_generator name=gen "
        f"slots={slots} custom=sim:1,sim_step_ms:1.0,sim_per_slot_ms:0.05,"
        f"sim_prefill_ms:0.02,vocab:997 max-new={max_new} chunk={chunk} ! "
        "tensor_sink name=out",
        name=f"multiplex{slots}",
    )
    pipe.start()
    chunks = []
    pipe["out"].connect_new_data(chunks.append)
    try:
        pipe["src"].push_block(np.stack(prompts))
        assert _until(
            lambda: sum(1 for f in chunks if f.meta.get("final"))
            >= len(prompts), timeout=60)
        health = pipe.health()["gen"]
    finally:
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()
    return health, _tokens_by_stream(chunks)


def test_continuous_batching_multiplexes_decode_steps():
    """The multiplex claim, as counts: four streams x 64 tokens through
    4 shared slots cost at most HALF the decode dispatches the same
    requests cost one at a time (1 slot: exactly one stream per step, 32
    dispatches of 8), every token is the stream's own, and the shared
    dispatches ran at least half full."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 997, (1, 8)).astype(np.int32)
               for _ in range(4)]
    want = sorted(_sim_tokens(p, 64) for p in prompts)
    h4, s4 = _generate_through_slots(4, prompts)
    h1, s1 = _generate_through_slots(1, prompts)
    assert s4 == s1 == want
    assert h4["gen_tokens"] == h1["gen_tokens"] == 256
    assert h4["gen_joins"] == h1["gen_joins"] == 4
    d4, d1 = h4["gen_decode_steps"], h1["gen_decode_steps"]
    assert d1 == 32 and h1["gen_tokens_per_step"] == 1.0
    assert d1 >= 2 * d4, f"4 slots took {d4} dispatches, 1 slot {d1}"
    occupancy = h4["gen_tokens"] / (d4 * 8 * 4)
    assert occupancy >= 0.5, f"shared dispatches ran {occupancy:.2f} full"


@pytest.mark.parametrize("slots,streams,steps", [
    (1, 4, 32), (2, 4, 16), (4, 4, 8), (8, 4, 8), (4, 8, 16), (8, 8, 8)])
def test_slot_engine_dispatch_ledger(slots, streams, steps):
    """The slot engine's dispatch ledger on the simulator with every
    request waiting before the pump starts (so the schedule is exact):
    four streams x 64 tokens at chunk 8 take 32 decode dispatches through
    one slot, 16 through two, 8 through four — and no fewer through
    eight, because there are only four streams; eight streams take 16
    through four slots and 8 through eight.  Every token at every width,
    each stream's own."""
    from nnstreamer_tpu.core.slots import SimSlotModel, SlotEngine

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 997, (1, 8)).astype(np.int32)
               for _ in range(streams)]
    model = SimSlotModel(slots, vocab=997, sleep=lambda s: None)
    eng = SlotEngine(model, None, max_seq=1 << 20, chunk=8, name="ledger")
    for p in prompts:
        eng.submit(TensorFrame([p]), p, max_new=64, chunk=8)
    eng.start()
    outs = []
    try:
        def finals():
            outs.extend(f for _pad, f in eng.pop_ready())
            return sum(1 for f in outs if f.meta["final"]) >= streams

        assert _until(finals, timeout=60)
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert _tokens_by_stream(outs) == sorted(
        _sim_tokens(p, 64) for p in prompts)
    assert snap["gen_tokens"] == 64 * streams
    assert snap["gen_completed"] == snap["gen_joins"] == streams
    assert snap["gen_decode_steps"] == steps
    assert snap["gen_prefill_chunks"] == streams
    # every dispatch ran as full as the streams allow
    assert snap["gen_tokens"] == steps * 8 * min(slots, streams)


def test_the_engine_wakes_its_consumer_once_per_batch_of_ready_frames():
    """``on_ready`` fires once for a batch of ready frames: a batch is due
    when a frame lands in an EMPTY ready list and is announced after the
    pump's next device dispatch or as it goes idle; with nothing popped, a
    whole stream's frames (8 of them) are ONE wake; after a pop the next
    frame is a wake again.  It is what lets the generator's dispatch thread
    release a turn's frames when they exist instead of at its next 20 ms
    poll (``Element.wake_dispatch``)."""
    from nnstreamer_tpu.core.slots import SimSlotModel, SlotEngine

    wakes = []
    p = np.arange(8, dtype=np.int32)[None]
    eng = SlotEngine(SimSlotModel(2, vocab=997, sleep=lambda s: None), None,
                     max_seq=1 << 20, chunk=8, name="wake",
                     on_ready=lambda: wakes.append(len(eng._ready)))
    eng.start()
    try:
        eng.submit(TensorFrame([p]), p, max_new=64, chunk=8)
        assert _until(lambda: eng.snapshot()["gen_completed"] == 1, timeout=60)
        assert _until(lambda: len(wakes) == 1, timeout=10)    # the list grew unpopped: one batch
        assert len(eng.pop_ready()) == 8
        eng.submit(TensorFrame([p]), p, max_new=8, chunk=8)
        assert _until(lambda: eng.snapshot()["gen_completed"] == 2, timeout=60)
        assert _until(lambda: len(wakes) == 2, timeout=10) and len(eng.pop_ready()) == 1
        # every wake found its batch in the list
        assert all(n >= 1 for n in wakes)
    finally:
        eng.stop()


def test_a_wake_in_the_mailbox_runs_the_idle_hook_at_once():
    """The generator's frames reach the sink through ``WAKE`` items the pump
    posts into the element's own mailbox: every ready batch is one
    ``wake_dispatch`` call, and the dispatch loop answers each with a
    ``handle_idle`` (counted, not timed: the poll stays as the fallback)."""
    from nnstreamer_tpu.pipeline import parse_pipeline

    pipe = parse_pipeline(
        "appsrc name=src ! tensor_generator name=gen slots=2 custom=sim:1,vocab:101 "
        "max-new=32 chunk=4 ! tensor_sink name=out max-stored=64")
    gen, frames, calls = pipe["gen"], [], {"wake": 0, "idle": 0}
    wake, idle = gen.wake_dispatch, gen.handle_idle

    def counted_wake():
        calls["wake"] += 1
        wake()

    def counted_idle():
        calls["idle"] += 1
        return idle()

    gen.wake_dispatch, gen.handle_idle = counted_wake, counted_idle
    pipe["out"].connect_new_data(frames.append)
    pipe.start()
    try:
        pipe["src"].push(np.arange(5, dtype=np.int32)[None])
        assert _until(lambda: any(f.meta.get("final") for f in frames), timeout=60)
    finally:
        pipe.stop()
    assert sum(len(np.asarray(f.tensors[0]).reshape(-1)) for f in frames if f.tensors) == 32
    # at least one batch, never more wakes than frames, and an idle flush for each
    assert 1 <= calls["wake"] <= len(frames) and calls["idle"] >= calls["wake"]


# ---------------------------------------------------------------------------
# The per-token read of the KV cache, bounded by fill
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layers", [2, 3])
def test_decode_scan_reads_the_cache_through_one_bounded_call_a_layer(
        layers, monkeypatch):
    """With the fill-bounded kernel forced (ops/decode_attention.py, as a
    program lowered for one TPU takes it): the scan's body holds exactly
    one ``nns_decode_attention`` call a layer, and no ``dot_general``
    anywhere has a whole cache leaf for an operand: the leaves are read by
    those calls and by nothing else."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.transformer import build_slot_stream
    from nnstreamer_tpu.ops import decode_attention
    from test_slot_cache_path import _eqns

    monkeypatch.setattr(decode_attention, "INTERPRET", True)
    model, params, _ = build_slot_stream(
        {"dtype": "bfloat16", "vocab": "61", "d_model": "128", "heads": "2",
         "layers": str(layers), "d_ff": "64", "seq": "384", "seed": "11"}, 4)
    vec = jnp.ones((4,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: model._decode_scan(3, *a))(
        params, model.init_cache(), vec, vec, vec)
    eqns = list(_eqns(jaxpr.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["nns_decode_attention"] * layers
    leaf = 4 * 384 * 128
    whole = [e for e in eqns if e.primitive.name == "dot_general"
             and any(getattr(v.aval, "size", 0) >= leaf for v in e.invars)]
    assert not whole, whole


def test_kv_rows_read_counts_whole_blocks_of_live_slots_only(monkeypatch):
    """One stream of 8 prompt tokens and 9 new ones through 4 slots of 384
    positions, kernel forced: its 8 decode steps (one dispatch) read the
    one 128-row block the stream has filled, 8 x 128 rows, of the 8 x 4 x
    384 the leaf holds; the three idle slots read nothing.  Without the
    kernel (this CPU, not forced) every step reads every row."""
    from nnstreamer_tpu.core.slots import SlotEngine
    from nnstreamer_tpu.models.transformer import build_slot_stream
    from nnstreamer_tpu.ops import decode_attention

    def serve():
        model, params, max_seq = build_slot_stream(
            {"dtype": "float32", "vocab": "61", "d_model": "128", "heads": "2",
             "layers": "2", "d_ff": "64", "seq": "384", "seed": "11"}, 4)
        eng = SlotEngine(model, params, max_seq=max_seq, chunk=8, name="kvrows")
        prompt = np.arange(8, dtype=np.int32)[None] % 61
        eng.submit(TensorFrame([prompt]), prompt, max_new=9, chunk=8)
        eng.start()
        outs = []
        try:
            def final():
                outs.extend(f for _pad, f in eng.pop_ready())
                return any(f.meta["final"] for f in outs)

            assert _until(final, timeout=120)
            return eng.snapshot(), _tokens_by_stream(outs)
        finally:
            eng.stop()

    plain, want = serve()
    assert plain["gen_decode_steps"] == 1 and plain["gen_tokens"] == 9
    assert plain["gen_kv_rows_read"] == plain["gen_kv_rows_held"] == 8 * 4 * 384
    monkeypatch.setattr(decode_attention, "INTERPRET", True)
    bounded, got = serve()
    assert got == want
    assert bounded["gen_kv_rows_held"] == 8 * 4 * 384
    assert bounded["gen_kv_rows_read"] == 8 * 128


@pytest.mark.parametrize("prefill_chunk,chunks", [(512, 1), (128, 3)],
                         ids=["one-300-row-chunk", "three-short-chunks"])
def test_only_a_chunk_past_the_small_batch_kernel_counts_grouped_rows(prefill_chunk, chunks):
    """One 300-token prompt and 5 new tokens through the hybrid family.  As
    ONE chunk it is past ``ops/expert_ffn.py``'s small-batch rows: every
    expert layer goes through the grouped kernel, and the prefill program
    counts the rows that carry a pick (every local pick of the chunk) and
    the rows the tiles ran (on this CPU the picks themselves; on a TPU the
    groups padded to whole tiles).  In chunks of 128 it stays under, as a
    decode step always does: neither counter moves."""
    from nnstreamer_tpu.core.slots import SlotEngine
    from nnstreamer_tpu.models import hybrid_lm
    from nnstreamer_tpu.ops import expert_ffn

    props = dict(kv.split(":") for kv in HYBRID.replace("seq:128", "seq:384").split(","))
    model, params, max_seq = hybrid_lm.build_slot_stream(props, 2)
    eng = SlotEngine(model, params, max_seq=max_seq, chunk=4,
                     prefill_chunk=prefill_chunk, name="grouped-rows")
    prompt = (np.arange(300, dtype=np.int32)[None] * 7) % 97
    assert (300 > expert_ffn.MAX_TOKENS) and (128 <= expert_ffn.MAX_TOKENS)
    eng.submit(TensorFrame([prompt]), prompt, max_new=5, chunk=4)
    eng.start()
    outs = []
    try:
        def final():
            outs.extend(f for _pad, f in eng.pop_ready())
            return any(f.meta["final"] for f in outs)

        assert _until(final, timeout=120)
        snap = eng.snapshot()
    finally:
        eng.stop()
    assert snap["gen_prefill_chunks"] == chunks and snap["gen_moe_local"] > 0
    rows, run = snap["gen_moe_grouped_rows"], snap["gen_moe_grouped_rows_run"]
    if chunks == 1:
        assert run >= rows > 0 and rows == snap["gen_moe_prefill_local"]
    else:
        assert rows == run == 0 and snap["gen_moe_prefill_local"] > 0


# ---------------------------------------------------------------------------
# The shared-prefix cache's ledger
# ---------------------------------------------------------------------------
def test_prefix_cache_ledger_cold_then_warm():
    """The prefix cache on the zoo transformer, as a ledger: one cold and
    two warm requests share a 256-token head at grain 64.  The cold one
    misses, publishes the head's four pages and prefills every chunk;
    each warm one hits once, attaches all 256 tokens and prefills only
    its suffix's chunks — and answers exactly what an uncached server
    answers."""
    head_len, tail_len, grain, pchunk, max_new = 256, 16, 64, 32, 2
    props = ("dtype:float32,vocab:61,d_model:32,heads:2,layers:2,d_ff:64,"
             f"seq:{head_len + tail_len + max_new + 32},seed:11")
    rng = np.random.default_rng(7)
    head = rng.integers(0, 61, (1, head_len)).astype(np.int32)
    prompts = [
        np.concatenate(
            [head, rng.integers(0, 61, (1, tail_len)).astype(np.int32)],
            axis=1)
        for _ in range(3)]

    def serve(prefix_cache):
        pipe = parse_pipeline(
            "appsrc name=src max-buffers=64 ! tensor_generator name=gen "
            f"slots=1 custom={props} max-new={max_new} chunk=1 "
            f"prefill-chunk={pchunk} {prefix_cache}! tensor_sink name=out",
            name="prefixledger",
        )
        pipe.start()
        chunks = []
        pipe["out"].connect_new_data(lambda f: chunks.append(
            (bool(f.meta.get("final")),
             np.asarray(f.tensors[0]).reshape(-1).tolist()
             if f.tensors else [])))
        ledger, answers = [], []
        try:
            for p in prompts:
                finals = sum(1 for c in chunks if c[0])
                mark = len(chunks)
                pipe["src"].push(p)
                assert _until(
                    lambda: sum(1 for c in chunks if c[0]) > finals,
                    timeout=120)
                answers.append([t for _f, toks in chunks[mark:] for t in toks])
                h = pipe.health()["gen"]
                ledger.append({k: h.get(k, 0) for k in (
                    "prefix_hits", "prefix_misses", "prefix_hit_tokens",
                    "prefix_publishes", "gen_prefill_chunks")})
        finally:
            pipe["src"].end_of_stream()
            pipe.wait(timeout=30)
            pipe.stop()
        return ledger, answers

    cached, got = serve(f"prefix-cache=on prefix-grain={grain} ")
    plain, want = serve("")
    assert got == want and all(len(a) == max_new for a in got)
    full = (head_len + tail_len) // pchunk        # chunks of a whole prompt
    tail = tail_len // pchunk + 1                  # chunks of a suffix
    assert [r["prefix_misses"] for r in cached] == [1, 1, 1]
    assert [r["prefix_hits"] for r in cached] == [0, 1, 2]
    assert [r["prefix_hit_tokens"] for r in cached] == [
        0, head_len, 2 * head_len]
    assert cached[0]["prefix_publishes"] == head_len // grain
    assert [r["gen_prefill_chunks"] for r in cached] == [
        full + 1, full + 1 + tail, full + 1 + 2 * tail]
    assert [r["gen_prefill_chunks"] for r in plain] == [
        (full + 1) * (i + 1) for i in range(3)]


# ---------------------------------------------------------------------------
# Steady traffic compiles nothing
# ---------------------------------------------------------------------------
_COMPILED = []


def _programs_compiled() -> int:
    """jax's process-wide count of backend compiles (the event the
    cells' ``compiles_in_window.*`` metrics read)."""
    if not _COMPILED:
        import jax.monitoring

        _COMPILED.append(0)

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILED[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _COMPILED[0]


VIT = ("arch:vit,size:32,patch:16,d_model:32,heads:2,layers:1,d_ff:64,"
       "classes:10,dtype:float32,seed:1")
DENSE = "vocab:97,d_model:64,heads:4,layers:2,d_ff:256,seq:128,dtype:float32,seed:1"
HYBRID = (
    "arch:nemotron_h,layers:MEM*EME,vocab:97,d_model:64,ssm_heads:4,"
    "ssm_head_dim:16,ssm_groups:2,ssm_state:16,conv:4,scan_chunk:8,heads:4,"
    "kv_heads:2,head_dim:16,experts:8,experts_held:4,expert_offset:0,"
    "experts_per_tok:2,d_expert:32,d_shared:64,routed_scale:2.5,eps:1e-5,"
    "seq:128,dtype:float32,seed:1")


def _stream_wave(pipe, got):
    """One full bucket as a block, then one frame alone."""
    n0 = len(got)
    pipe["src"].push_block(
        np.stack([np.full((32, 32, 3), i, np.uint8) for i in range(8)]))
    assert _until(lambda: len(got) >= n0 + 8, timeout=120)
    pipe["src"].push(np.full((32, 32, 3), 9, np.uint8))
    assert _until(lambda: len(got) >= n0 + 9, timeout=120)


def _chat_wave(pipe, got):
    """Four prompts of the rehearsal ladder's lengths, all to the end."""
    n0 = sum(1 for final in got if final)
    rng = np.random.default_rng(3)
    for n in (5, 12, 33, 40):
        pipe["src"].push(rng.integers(0, 97, (1, n)).astype(np.int32))
    assert _until(
        lambda: sum(1 for final in got if final) >= n0 + 4, timeout=300)


@pytest.mark.parametrize("path", ["stream_vit", "dense_slots", "hybrid_slots"])
def test_steady_traffic_compiles_nothing(path):
    """Each cell's path at tiny width: after one warm-up wave, an
    identical wave compiles no program at all — the model step, and the
    prefill, reset and emit programs that ``gen_decode_compiles`` does
    not see."""
    if path == "stream_vit":
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter name=f framework=jax-xla "
            f"model=zoo custom={VIT} max-batch=8 ! "
            "tensor_decoder mode=image_labeling ! "
            "tensor_sink name=out max-stored=1", name="steady-vit")
        wave, note = _stream_wave, (lambda f: True)
    else:
        custom = DENSE if path == "dense_slots" else HYBRID
        pipe = parse_pipeline(
            "appsrc name=src max-buffers=64 ! tensor_generator name=gen "
            f"slots=4 custom={custom} max-new=16 chunk=4 prefill-chunk=16 ! "
            "tensor_sink name=out max-stored=1", name=f"steady-{path}")
        wave, note = _chat_wave, (lambda f: bool(f.meta.get("final")))
    got = []
    pipe["out"].connect_new_data(lambda f: got.append(note(f)))
    cold = _programs_compiled()
    pipe.start()
    try:
        wave(pipe, got)
        warm = _programs_compiled()
        wave(pipe, got)
        steady = _programs_compiled()
    finally:
        pipe["src"].end_of_stream()
        pipe.wait(timeout=60)
        pipe.stop()
    assert warm > cold, "the compile counter saw no warm-up"
    assert steady == warm, f"steady traffic compiled {steady - warm} programs"


# ---------------------------------------------------------------------------
# The stream path's bucket ledger
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def warm_stream():
    """A tiny ViT stream pipeline at max-batch 8 with every bucket the
    window can form compiled up front, as the stream cell's set-up does,
    and one frame sent alone (a lone frame takes the unbatched program,
    not bucket 1)."""
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=64 ! tensor_filter name=f "
        f"framework=jax-xla model=zoo custom={VIT} max-batch=8 ! "
        "tensor_sink name=out max-stored=1", name="buckets")
    got = []
    pipe["out"].connect_new_data(
        lambda f: got.append((f.pts, np.asarray(f.tensors[0]).shape)))
    pipe.start()
    frames = np.stack([np.full((32, 32, 3), i, np.uint8) for i in range(8)])
    for n in (1, 2, 4, 8):
        np.asarray(pipe["f"].backend.invoke_batch_donated(
            [np.ascontiguousarray(frames[:n])])[0])
    pipe["src"].push(frames[0], pts=-1.0)
    assert _until(lambda: len(got) >= 1, timeout=120)
    yield pipe, got
    pipe["src"].end_of_stream()
    pipe.wait(timeout=60)
    pipe.stop()


@pytest.mark.parametrize("pushed", [16, 19, 8, 1])
def test_stream_bucket_ledger(warm_stream, pushed):
    """What ``batch_fill_pct.stream`` and ``bucket_fill_pct.stream`` are
    computed from, held exact for a push of 8k, 8k + 3, 8 and one frame:
    every frame delivered once and in order, the frames summed over
    invokes equal the frames pushed, no invoke holds more than max-batch,
    and however the window cut the batches, every bucket it formed was
    one of the compiled set: the backend holds the same model programs
    after as before."""
    pipe, got = warm_stream
    n0 = len(got)
    before = dict(pipe["f"].metrics_info())
    programs = set(pipe["f"].backend._jit_cache)
    assert len(programs) == 5  # buckets 1, 2, 4, 8 and the lone frame's
    for i in range(pushed):
        pipe["src"].push(np.full((32, 32, 3), i, np.uint8), pts=float(i))
    assert _until(lambda: len(got) >= n0 + pushed, timeout=120)
    after = dict(pipe["f"].metrics_info())
    assert [pts for pts, _ in got[n0:]] == [float(i) for i in range(pushed)]
    assert {shape for _, shape in got[n0:]} == {(10,)}
    frames = (after["nns.filter.invoked_frames"]
              - before["nns.filter.invoked_frames"])
    invokes = after["nns.filter.invokes"] - before["nns.filter.invokes"]
    assert frames == pushed
    assert -(-pushed // 8) <= invokes <= pushed
    assert set(pipe["f"].backend._jit_cache) == programs
