"""Scheduler-overhead smoke gates (`pytest -m perf`).

Real clocks, no fake time, generous margins: every threshold here sits at
~half of what the dataplane measures on a loaded CI box, so a pass means
"the tentpole optimizations still exist", not "the machine was fast
today".  All tests finish in seconds — they run inside the tier-1 budget.
"""

import time
import tracemalloc

import numpy as np
import pytest

from nnstreamer_tpu.core.buffer import FRAME_POOL, TensorFrame
from nnstreamer_tpu.pipeline import parse_pipeline

pytestmark = pytest.mark.perf

CHAIN = (
    "appsrc name=src max-buffers=256 ! identity ! identity ! identity ! "
    "tensor_sink name=out max-stored=1"
)


def _passthrough_fps(fuse: bool, n_frames: int = 2500) -> float:
    pipe = parse_pipeline(CHAIN, name="perf", fuse=fuse)
    pipe.start()
    src, sink = pipe["src"], pipe["out"]
    done = {"n": 0}
    sink.connect_new_data(lambda f: done.__setitem__("n", done["n"] + 1))
    pool = [np.zeros((64,), np.float32) for _ in range(16)]
    for i in range(128):  # warmup: settle thread scheduling
        src.push(pool[i % 16])
    t_w = time.time()
    while done["n"] < 128 and time.time() - t_w < 30:
        time.sleep(0.005)
    assert done["n"] >= 128, "warmup stalled"
    done["n"] = 0
    t0 = time.perf_counter()
    for i in range(n_frames):
        src.push(pool[i % 16])
    while done["n"] < n_frames and time.perf_counter() - t0 < 60:
        time.sleep(0.002)
    dt = time.perf_counter() - t0
    fps = done["n"] / dt
    src.end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    assert done["n"] == n_frames, "frames lost in passthrough"
    return fps


def test_fusion_speedup_and_absolute_floor():
    """Tentpole gate: the fused 5-element identity chain must beat the
    unfused seed dataplane by >= 2x (measured 4-10x; threshold at the
    acceptance floor with the rest as CI-noise margin), and clear an
    absolute 4000 fps floor (measured 12-25k on this container)."""
    fused = _passthrough_fps(True)
    unfused = _passthrough_fps(False)
    assert fused >= 2.0 * unfused, (
        f"fusion speedup regressed: fused {fused:.0f} fps vs "
        f"unfused {unfused:.0f} fps ({fused / unfused:.2f}x < 2x)"
    )
    assert fused >= 4000


def test_histograms_armed_identity_floor():
    """PR-11 pin: with the ALWAYS-ON log2 latency histograms armed (a
    tracer attached records per-element handle latency per call plus a
    mailbox queue-wait stamp per crossing), the fused identity chain
    still clears the PR-3/PR-6 absolute 4000 fps floor — the lock-free
    array-increment record path is cheap enough to leave on in
    production."""
    from nnstreamer_tpu.pipeline import parse_pipeline as parse

    n = 2500
    pipe = parse(CHAIN, name="histperf", fuse=True)
    tracer = pipe.enable_tracing()
    pipe.start()
    src, sink = pipe["src"], pipe["out"]
    done = {"n": 0}
    sink.connect_new_data(lambda f: done.__setitem__("n", done["n"] + 1))
    pool = [np.zeros((64,), np.float32) for _ in range(16)]
    for i in range(128):
        src.push(pool[i % 16])
    t_w = time.time()
    while done["n"] < 128 and time.time() - t_w < 30:
        time.sleep(0.005)
    assert done["n"] >= 128, "warmup stalled"
    done["n"] = 0
    t0 = time.perf_counter()
    for i in range(n):
        src.push(pool[i % 16])
    while done["n"] < n and time.perf_counter() - t0 < 60:
        time.sleep(0.002)
    fps = done["n"] / (time.perf_counter() - t0)
    src.end_of_stream()
    pipe.wait(timeout=30)
    hists = {
        (el, name): h for el, name, h in tracer.latency_histograms()
    }
    snap = pipe.metrics_snapshot()
    pipe.stop()
    assert done["n"] == n, "frames lost with histograms armed"
    assert fps >= 4000, (
        f"histogram-armed dataplane regressed: {fps:.0f} fps < 4000"
    )
    # the instruments really recorded: every element's handle histogram
    # holds one observation per call, and the percentiles surface in the
    # snapshot under their stable names
    h_out = hists[("out", "nns.element.handle_seconds")]
    assert h_out.count == n + 128
    assert snap.get("nns.element.handle_p99_us", element="out") > 0
    assert snap.sum("nns.element.handle_seconds_count", element="out") == (
        n + 128)


def test_perf_truth_fast_check_against_committed_baseline():
    """The per-PR perf-truth gate (tier-1, next to the three lint
    gates): the FAST axis subset must land inside the committed
    PERF_BASELINE.json distribution — median beyond ``median - tol``
    counts as a regression (tolerance math pinned by
    tests/test_perf_truth.py; best-of-k with early exit absorbs ambient
    load).  This replaces hand-picked binary floors with the committed
    distribution for every PR, chip or no chip."""
    import importlib.util
    import os

    pt_path = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "tools",
        "perf_truth.py")
    spec = importlib.util.spec_from_file_location("perf_truth_gate", pt_path)
    pt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pt)
    report = pt.check(fast=True, k=3, verbose=False)
    bad = {
        name: ax for name, ax in report["axes"].items()
        if ax["verdict"] != "ok"
    }
    assert report["ok"], (
        "perf-truth regression vs committed baseline "
        f"(PERF_BASELINE.json, captured {report['baseline_captured_at']}"
        f"): {bad}"
    )


def test_telemetry_disabled_per_frame_overhead():
    """PR-7 pin: with the telemetry layer present but DISABLED (the
    default — no tracer, no flight recorder, no exposition endpoint),
    per-frame cost stays the tracer's single `is not None` branch, so
    the fused identity chain still clears the PR-3/PR-6 absolute floor.
    Structural half of the pin: a started pipeline holds no tracer or
    recorder object at all (registry collection is scrape-time only),
    so that branch IS the telemetry integration's entire hot-path
    footprint."""
    from nnstreamer_tpu.core import telemetry

    pipe = parse_pipeline(CHAIN, name="teloff", fuse=True)
    pipe.start()
    try:
        assert pipe.tracer is None
        assert pipe.flight_recorder is None
        assert telemetry.live_server_count() == 0
        pipe["src"].end_of_stream()
        pipe.wait(timeout=10)
    finally:
        pipe.stop()
    fps = _passthrough_fps(True)
    assert fps >= 4000, (
        f"telemetry-disabled dataplane regressed: {fps:.0f} fps < 4000"
    )


def test_memory_monitor_armed_identity_floor():
    """PR-14 pin: with the memory-pressure watermark monitor ARMED
    (sweeper-thread polling of real device/host memory stats), the
    fused identity chain still clears the PR-3/PR-6 absolute 4000 fps
    floor — the monitor touches NO per-frame path; its entire cost is
    a rate-limited poll on the sweeper cadence plus one bool read per
    ADMISSION (and this chain has no admission at all).  Structural
    half: a pipeline without enable_memory_monitor holds no monitor
    object, so the disabled dataplane is byte-identical to PR-13's."""
    pipe = parse_pipeline(CHAIN, name="memperf", fuse=True)
    mon = pipe.enable_memory_monitor(min_poll_s=0.01)
    pipe.start()
    src, sink = pipe["src"], pipe["out"]
    done = {"n": 0}
    sink.connect_new_data(lambda f: done.__setitem__("n", done["n"] + 1))
    pool = [np.zeros((64,), np.float32) for _ in range(16)]
    for i in range(128):
        src.push(pool[i % 16])
    t_w = time.time()
    while done["n"] < 128 and time.time() - t_w < 30:
        time.sleep(0.005)
    assert done["n"] >= 128, "warmup stalled"
    done["n"] = 0
    n = 2500
    t0 = time.perf_counter()
    for i in range(n):
        src.push(pool[i % 16])
    while done["n"] < n and time.perf_counter() - t0 < 60:
        time.sleep(0.002)
    fps = done["n"] / (time.perf_counter() - t0)
    src.end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    assert done["n"] == n, "frames lost with the memory monitor armed"
    assert fps >= 4000, (
        f"memory-monitor-armed dataplane regressed: {fps:.0f} fps < 4000"
    )
    # the monitor really ran on the sweeper (not on the frame path)
    assert mon.polls > 0
    # structural: a default pipeline holds no monitor at all
    off = parse_pipeline(CHAIN, name="memoff", fuse=True)
    assert off.memory_monitor is None


def test_fleet_observatory_armed_identity_floor():
    """PR-15 pin: with the FLEET OBSERVATORY fully armed in-process — a
    digest publisher polling on the sweeper cadence, a live
    FleetObservatory ingesting every digest, its ``nns.fleet.*``
    registry collector registered, and SLO instruments holding
    observations — the fused identity chain still clears the absolute
    4000 fps floor.  The whole plane is sweeper- and scrape-time-only:
    an armed-but-idle observatory costs ZERO on the per-frame path."""
    from nnstreamer_tpu.core.fleet import (
        DigestPublisher,
        FleetObservatory,
        pipeline_digest_stats,
    )
    from nnstreamer_tpu.core.telemetry import REGISTRY, SloTracker

    pipe = parse_pipeline(CHAIN, name="fleetperf", fuse=True)
    obs = FleetObservatory(topic="perf", default_ttl_s=60.0)
    REGISTRY.register_collector(obs._collect)
    slo = SloTracker(ttft_p95_s=0.5, token_p99_s=0.01, availability=0.99)
    slo.note_ttft("perf", 0.01)
    slo.note_tokens("perf", 0.02, 8)
    slo.note_stream("perf", "good")
    pub = DigestPublisher(
        lambda: {**pipeline_digest_stats(pipe), "inflight": 0,
                 "slo_burn": {t: r.get("ttft_burn", 0.0)
                              for t, r in slo.snapshot().items()}},
        lambda d: obs.ingest(
            "nns/query/perf/a", {"host": "x", "port": 1, "digest": d}),
        interval_s=0.02, name="perf")
    pipe.register_sweep(pub.poll, 0.02)
    try:
        pipe.start()
        src, sink = pipe["src"], pipe["out"]
        done = {"n": 0}
        sink.connect_new_data(lambda f: done.__setitem__("n", done["n"] + 1))
        pool = [np.zeros((64,), np.float32) for _ in range(16)]
        for i in range(128):
            src.push(pool[i % 16])
        t_w = time.time()
        while done["n"] < 128 and time.time() - t_w < 30:
            time.sleep(0.005)
        assert done["n"] >= 128, "warmup stalled"
        done["n"] = 0
        n = 2500
        t0 = time.perf_counter()
        for i in range(n):
            src.push(pool[i % 16])
        while done["n"] < n and time.perf_counter() - t0 < 60:
            time.sleep(0.002)
        fps = done["n"] / (time.perf_counter() - t0)
        # the sweeper is a thread of its own: on a fast, quiet machine the
        # timed frames can be through before its first tick, so give it a
        # bounded moment (outside the timed window) before the stop
        t_s = time.time()
        while pub.published == 0 and time.time() - t_s < 10:
            time.sleep(0.01)
        src.end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()
        assert done["n"] == n, "frames lost with the observatory armed"
        assert fps >= 4000, (
            f"observatory-armed dataplane regressed: {fps:.0f} fps < 4000"
        )
        # the digest plane really ran on the sweeper, not the frame path
        assert pub.published > 0
        assert obs.rollup()["digests"] > 0
    finally:
        REGISTRY.unregister_collector(obs._collect)


def test_autoscale_controller_armed_identity_floor():
    """PR-16 pin: with the AUTOSCALE CONTROLLER fully armed in-process —
    a FleetController ticking on the sweeper cadence over a live
    observatory (one healthy idle server ingested, so the envelope is
    satisfied and every tick runs the full reap/snapshot/feed/plan
    path), its ``nns.autoscale.*`` collector registered — the fused
    identity chain still clears the absolute 4000 fps floor.  The loop
    is sweeper- and scrape-time-only: an armed-but-calm controller
    makes ZERO decisions and costs ZERO on the per-frame path."""
    from nnstreamer_tpu.core.autoscale import FleetController, NullActuator
    from nnstreamer_tpu.core.fleet import FleetObservatory

    pipe = parse_pipeline(CHAIN, name="autoscaleperf", fuse=True)
    obs = FleetObservatory(topic="perf", default_ttl_s=60.0)
    # one healthy idle server: without it the envelope floor would spawn
    obs.ingest("nns/query/perf/a", {"host": "x", "port": 1, "digest": {
        "v": 1, "seq": 1, "age_s": 0.0, "interval_s": 1.0, "ttl_s": 60.0,
        "draining": False, "degraded": False, "swap": "idle",
        "inflight": 0, "admitted": 0, "shed": 0, "tokens_per_s": 0.0,
        "slots": 4, "occupied": 0}})
    actuator = NullActuator()
    ctrl = FleetController(obs, actuator).attach(pipe, interval_s=0.02)
    try:
        pipe.start()
        src, sink = pipe["src"], pipe["out"]
        done = {"n": 0}
        sink.connect_new_data(lambda f: done.__setitem__("n", done["n"] + 1))
        pool = [np.zeros((64,), np.float32) for _ in range(16)]
        for i in range(128):
            src.push(pool[i % 16])
        t_w = time.time()
        while done["n"] < 128 and time.time() - t_w < 30:
            time.sleep(0.005)
        assert done["n"] >= 128, "warmup stalled"
        done["n"] = 0
        n = 2500
        t0 = time.perf_counter()
        for i in range(n):
            src.push(pool[i % 16])
        while done["n"] < n and time.perf_counter() - t0 < 60:
            time.sleep(0.002)
        fps = done["n"] / (time.perf_counter() - t0)
        # as above: let the sweeper reach its first tick before the stop
        t_s = time.time()
        while ctrl.ticks == 0 and time.time() - t_s < 10:
            time.sleep(0.01)
        src.end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()
        assert done["n"] == n, "frames lost with the controller armed"
        assert fps >= 4000, (
            f"controller-armed dataplane regressed: {fps:.0f} fps < 4000"
        )
        # the loop really ran on the sweeper and stayed calm: ticks
        # accumulated, zero decisions, zero actuation
        assert ctrl.ticks > 0
        assert ctrl.state.decisions == 0
        assert actuator.calls == []
    finally:
        ctrl.stop()


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
    t_w = time.time()
    while done["n"] < 200 and time.time() - t_w < 30:
        time.sleep(0.005)
    n = 300
    # frames pre-created OUTSIDE the traced window: the budget pins the
    # dispatch loop, not the application's ingest allocations
    frames = [TensorFrame([arr]) for _ in range(n)]
    done["n"] = 0
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for f in frames:
        src.push(f)
    t0 = time.time()
    while done["n"] < n and time.time() - t0 < 30:
        time.sleep(0.002)
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
# Async device feed gates (PR-6): the pipeline-vs-raw gap can only shrink
# between chip windows — CPU-proxy floors for the window, the donated
# buffer ring, and the staging lane (ROADMAP item 5, first slice).
# ---------------------------------------------------------------------------
def test_dispatch_window_nonblocking_tracks_backend():
    """Acceptance gate: at dispatch-depth 8 over a slow single-server
    fake device, pipeline throughput tracks BACKEND throughput within
    10% — the device is busy >= 90% of wall time because stacking,
    dispatch, and the device->host sync all hide behind compute (the
    pre-async design was bounded by serial block-on-oldest: compute +
    transfer + dispatch per batch, ~55% busy at these costs).  And the
    structural claim behind the number: the dispatch thread is NEVER
    observed inside a device_get-style blocking sync — the window's
    reaper thread owns every pre-completion wait."""
    from nnstreamer_tpu.pipeline import parse_pipeline as parse

    compute_ms, mb, nbatches = 8.0, 8, 60
    pipe = parse(
        "appsrc name=src max-buffers=512 ! tensor_filter name=f "
        "framework=async-sim "
        f"custom=compute_ms:{compute_ms},transfer_ms:4,dispatch_ms:1 "
        f"max-batch={mb} dispatch-depth=8 ingest-lane=off ! "
        "tensor_sink name=out max-stored=1",
        name="awperf",
    )
    pipe.start()
    done = {"n": 0}
    pipe["out"].connect_new_data(
        lambda f: done.__setitem__("n", done["n"] + 1))
    be = pipe["f"].backend
    arr = np.zeros((64,), np.float32)
    for _ in range(mb * 4):  # warmup: fill the window, settle batching
        pipe["src"].push(arr)
    t_w = time.time()
    while done["n"] < mb * 4 and time.time() - t_w < 30:
        time.sleep(0.005)
    assert done["n"] >= mb * 4, "warmup stalled"
    done["n"] = 0
    b0 = be.busy_s
    n = mb * nbatches
    t0 = time.perf_counter()
    for _ in range(n):
        pipe["src"].push(arr)
    while done["n"] < n and time.perf_counter() - t0 < 60:
        time.sleep(0.002)
    elapsed = time.perf_counter() - t0
    busy_s = be.busy_s - b0
    foreign_syncs = [
        t for t in be.blocking_syncs if not t.endswith("-reaper")
    ]
    pipe["src"].end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    assert done["n"] == n, "frames lost in the async window"
    # device-busy fraction: the single server's ACTUAL service seconds
    # over wall time; overlap means wall time barely exceeds service.
    # Steady state measures >= 0.95; the serial block-on-oldest design
    # measures compute/(compute+transfer+dispatch) ~= 0.62 at these
    # costs — 0.85 keeps CI-scheduling headroom while separating the
    # two structures by a wide margin.
    busy = busy_s / elapsed
    assert busy >= 0.85, (
        f"dispatch window no longer hides framework cost: device busy "
        f"{busy:.2f} < 0.85 ({busy_s * 1000:.0f}ms service in "
        f"{elapsed * 1000:.0f}ms wall)"
    )
    assert foreign_syncs == [], (
        f"dispatch thread blocked in device_get: {foreign_syncs}"
    )


def _load_bench():
    import importlib.util
    import os

    bench_path = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "bench.py")
    spec = importlib.util.spec_from_file_location("bench_for_perf", bench_path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_pipeline_vs_raw_proxy_floor():
    """ROADMAP items 1+5 gate: the full dataplane must deliver >= 60% of
    the bare backend's throughput when both run the same async-sim
    device costs with the same depth-8 window structure (measured
    ~0.9-1.0x — the async feed hides framework cost behind compute; the
    pre-async serial design measured ~0.6x).  SAME harness bench.py
    publishes as `pipeline_vs_raw` in its cpu_proxy evidence, so the
    gate and the evidence cannot drift — the PR-6 gains can only shrink
    loudly."""
    bench = _load_bench()
    best = (0.0, 0.0, 0.0)
    for _attempt in range(2):  # best-of-2: CI scheduling noise, not code
        raw_fps, pipe_fps = bench.measure_pipeline_vs_raw(nbatches=24)
        assert raw_fps > 0 and pipe_fps > 0
        ratio = pipe_fps / raw_fps
        if ratio > best[0]:
            best = (ratio, raw_fps, pipe_fps)
        if best[0] >= 0.6:
            break
    ratio, raw_fps, pipe_fps = best
    assert ratio >= 0.6, (
        f"pipeline_vs_raw proxy regressed: pipeline {pipe_fps:.0f} fps vs "
        f"raw {raw_fps:.0f} fps ({ratio:.2f}x < 0.6x; steady state "
        "measures ~0.75-0.9x)"
    )


def test_host_ingest_overlap_speedup():
    """Acceptance gate: the double-buffered staging lane beats serialized
    stack+transfer+compute by >= 1.3x on equal costs (measured ~1.8x at
    4ms/4ms; the lane hides the whole transfer behind compute).  Runs
    the SAME harness bench.py publishes as `ingest_overlap_speedup` in
    its cpu_proxy evidence — the gate and the evidence cannot drift."""
    bench = _load_bench()

    t_serial, t_lane = bench.measure_ingest_overlap(nb=16)
    speedup = t_serial / t_lane
    assert speedup >= 1.3, (
        f"staging lane overlap regressed: {speedup:.2f}x < 1.3x "
        f"(serial {t_serial * 1000:.0f}ms vs lane {t_lane * 1000:.0f}ms)"
    )


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
    from nnstreamer_tpu.pipeline import parse_pipeline as parse

    pipe = parse(
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


def test_routing_decision_overhead_floor():
    """Fleet-routing gate: choosing a remote with least-inflight or
    ewma costs <= 2 us/request MORE than blind rotation on the CPU
    proxy harness (measured ~0.3-0.8 us of policy delta on a 3-remote
    pool; the tier partition + breaker peek is paid by every policy,
    rotation included).  A routing layer that shows up on the RPC hot
    path has failed its design contract."""
    from nnstreamer_tpu.elements.query import _PoolState
    from nnstreamer_tpu.pipeline.element import make_element

    el = make_element("tensor_query_client", "q")
    targets = [("127.0.0.1", 7310 + i) for i in range(3)]
    ps = _PoolState([object()] * 3, targets, 0)
    el._pstate = ps
    # realistic signal state: live EWMA rows + in-flight counts
    with el._breakers_lock:
        for i, (h, p) in enumerate(targets):
            el._remote_spans[f"{h}:{p}"] = {
                "e2e_ms": 10.0 * (i + 1), "requests": 100}
            el._remote_inflight[f"{h}:{p}"] = i
    for t in targets:
        el._breaker_for(t)  # pre-create (steady-state shape)

    def per_call(policy: str, iters: int = 5_000) -> float:
        el.props["routing"] = policy
        t0 = time.perf_counter()
        for i in range(iters):
            el._route_order(ps, None, i)
        return (time.perf_counter() - t0) / iters

    for policy in ("rotate", "least-inflight", "ewma"):
        per_call(policy, 1_000)  # warm every path
    for policy in ("least-inflight", "ewma"):
        # interleaved rounds, min-of-deltas: each delta compares two
        # ADJACENT-in-time loops so ambient box load cancels instead of
        # being attributed to the policy
        deltas = [per_call(policy) - per_call("rotate") for _ in range(8)]
        delta = min(deltas)
        assert delta <= 2e-6, (
            f"routing={policy} adds {delta * 1e6:.2f} us/request over "
            "rotate (floor 2 us)"
        )


def test_continuous_batching_multiplex_floor():
    """Continuous-batching gate (ROADMAP item 2): >= 4 concurrent
    generation streams through shared slots must sustain >= 2x the
    aggregate token throughput of the same requests served one at a
    time, at bounded p50 per-token latency (measured ~2.5-3x on the
    async-sim proxy, whose simulated decode step pays the batch-
    independent weight-streaming cost real accelerator decode pays;
    threshold at the acceptance floor with the rest as CI-noise
    margin).  SAME harness bench.py publishes as `sim_speedup`, so the
    banked evidence and this gate cannot drift."""
    import bench

    res = bench.measure_slot_multiplex_speedup(
        slots=4, streams=4, max_new=64, chunk=8)
    assert res["sim_speedup"] >= 2.0, (
        f"slotted vs request-serial generation: {res['sim_speedup']}x "
        f"aggregate tokens/s (floor 2x; measured ~2.5-3x): {res}"
    )
    # bounded per-token latency: the roofline per-token cost is
    # ~1.2ms (base 1.0 + 4 slots x 0.05); 10ms means the scheduler,
    # not the device, is pacing tokens
    assert res["sim_p50_ms_per_token"] <= 10.0, res
    # slots are genuinely multiplexed, not serialized
    assert res["sim_slot_occupancy"] >= 0.5, res


@pytest.mark.slow  # tier-1 budget: ~17s live zoo re-measurement; the banked
# prefix_ttft axis is still gated every tier-1 run by
# test_perf_truth_fast_check_against_committed_baseline above
def test_prefix_ttft_floor():
    """Shared-prefix KV cache gate (ROADMAP item 4 arc): at 256 shared
    prefix tokens on the CPU-proxy zoo transformer, warm-hit TTFT must
    be <= 0.5x cold TTFT (ratio >= 2.0; measured ~3-3.4x — the
    remainder is CI-noise margin).  SAME harness bench.py publishes
    (BENCH_PREFIX_CACHE=1) and the perf-truth `prefix_ttft_speedup`
    axis trend-gates, so the banked evidence, the trend floor, and this
    product gate cannot measure different things.  The harness asserts
    the hit/miss ledger internally — a silently-cold cache fails loudly
    instead of publishing a 1.0x ratio."""
    import bench

    res = bench.measure_prefix_ttft(trials=3)
    assert res["prefix_ttft_speedup"] >= 2.0, (
        f"warm-prefix TTFT not <= 0.5x cold: "
        f"{res['prefix_ttft_speedup']}x (floor 2x; measured ~3x): {res}"
    )


def test_prefix_cache_armed_cold_identity_floor():
    """Tentpole zero-cost pin: with a prefix-cache=on (armed but COLD)
    slotted generator pipeline live in the process AND the memory
    monitor armed on the identity pipeline — so the PR-14 trim ladder's
    new first rung (prefix trim) is wired — the fused identity chain
    still clears the absolute 4000 fps floor.  The pool does no work
    until a prompt arrives and the trim rung runs on the watchdog
    cadence only: arming the cache must cost the dataplane nothing."""
    gen_pipe = parse_pipeline(
        "appsrc name=src ! tensor_generator slots=2 custom=sim:1 "
        "max-new=4 prefix-cache=on prefix-grain=32 prefill-chunk=4 ! "
        "tensor_sink name=out", name="prefixidle")
    gen_pipe.start()
    gen_pipe.enable_memory_monitor(high=0.99, low=0.9)
    try:
        assert gen_pipe["out"] is not None  # armed, idle, cold
        fps = _passthrough_fps(True)
    finally:
        gen_pipe["src"].end_of_stream()
        gen_pipe.wait(timeout=30)
        gen_pipe.stop()
    assert fps >= 4000, (
        f"armed-but-cold prefix cache dented the dataplane: "
        f"{fps:.0f} fps < 4000"
    )


@pytest.mark.slow  # tier-1 budget: ~12s live sharded re-measurement; the
# banked sharded_overhead axis is still gated every tier-1 run by
# test_perf_truth_fast_check_against_committed_baseline
def test_sharded_serving_floors():
    """The two mesh-sharded dataplane gates (ROADMAP item 4), both over
    the ONE bench.measure_sharded_overhead harness the cpu_proxy
    evidence and the perf-truth `sharded_overhead` axis publish:

    * dispatch overhead <= 15% on a single-device-equivalent mesh —
      jax-xla invoke_batch through the FULL sharded machinery
      (mesh=dp:1: NamedSharding in/out specs, scatter path, mesh-keyed
      pooling) must reach >= 0.85x the unsharded fps (measured ~1.0:
      the plumbing is free; interleaved rounds cancel ambient load);
    * >= 1.5x dp:2 aggregate throughput — the full pipeline over the
      async-sim mesh twin (2 concurrent shard servers, compute-bound
      knobs; measured ~1.9x).  The device layer is simulated because a
      single-core box cannot exhibit real XLA-CPU dp parallelism (both
      virtual devices share the one core) — what this floor pins is
      the sharded FEED structure: even scatter, all-shards readiness,
      no per-shard serialization anywhere in the dataplane.
    """
    import bench

    res = bench.measure_sharded_overhead()
    assert res["sharded_ratio"] >= 0.85, (
        f"single-device-equivalent mesh costs more than 15% dispatch "
        f"overhead: sharded/unsharded fps = {res['sharded_ratio']} "
        f"(floor 0.85; measured ~1.0): {res}"
    )
    assert res["dp2_speedup"] >= 1.5, (
        f"dp:2 aggregate throughput only {res['dp2_speedup']}x the "
        f"single-server dataplane (floor 1.5x; measured ~1.9x): {res}"
    )


def test_control_plane_armed_identity_floor():
    """PR-17 pin: with the WHOLE control plane armed and healthy — a
    live broker, a leader-elected lease renewing over its retained
    topic, a broker-backed observatory ingesting digests, and a ticking
    controller running the fail-static plane assessment — the fused
    identity chain still clears the absolute 4000 fps floor.  Lease
    renewal, plane grading, and freeze bookkeeping all live on the
    controller's slow cadence and broker reader threads: none of it may
    show up on the per-frame hot path."""
    import threading

    from nnstreamer_tpu.core.autoscale import (
        FleetController, FleetPolicy, LeaderLease, LeaseChannel,
        NullActuator)
    from nnstreamer_tpu.core.fleet import FleetObservatory
    from nnstreamer_tpu.distributed.mqtt import MiniBroker

    broker = MiniBroker()
    obs = FleetObservatory(topic="perfcp", default_ttl_s=5.0)
    chan = None
    stop = threading.Event()
    try:
        obs.start("127.0.0.1", broker.port)
        lease = LeaderLease("perf-ctl", ttl_s=1.0)
        chan = LeaseChannel("127.0.0.1", broker.port, "perfcp", lease)
        ctrl = FleetController(obs, NullActuator(),
                               policy=FleetPolicy(min_servers=0),
                               lease=lease)
        t0 = time.monotonic()
        while not lease.held and time.monotonic() - t0 < 10.0:
            ctrl.tick()          # vacancy watch, then acquire
            time.sleep(0.02)
        assert lease.held, "lease never acquired against a live broker"

        def churn():
            while not stop.is_set():
                ctrl.tick()      # renew + assess_plane every 20ms
                time.sleep(0.02)

        th = threading.Thread(target=churn, daemon=True)
        th.start()
        fps = _passthrough_fps(True)
        stop.set()
        th.join(timeout=5.0)
        assert lease.held and lease.self_fences == 0
        assert fps >= 4000, (
            f"armed control plane invaded the dataplane: {fps:.0f} fps "
            "< 4000"
        )
    finally:
        stop.set()
        if chan is not None:
            chan.close()
        obs.stop()
        broker.close()
