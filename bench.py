#!/usr/bin/env python
"""Headline benchmark: MobileNet-v2 image-labeling pipeline, fps per chip.

Mirrors the reference's flagship configuration (BASELINE.md: MobileNet-v2
labeling via tensor_filter; target >= 1000 fps/chip on TPU v5e-1): a full
streaming pipeline — source -> tensor_filter(jax-xla, MobileNet-v2 bf16,
micro-batched) -> tensor_decoder(image_labeling) -> tensor_sink — measured
end-to-end, not a bare model loop.

ONE process: the row is measured in the process that prints it, on
whatever device jax gives that process (a chip belongs to one process at
a time).  Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
...} with self-describing fields (model/batch/dtype/input and the device:
platform, device_kind, device_count).  A row that cannot be measured is
printed with an "error" field and the process exits non-zero — there is
no stored row to answer with and no stand-in from another platform.

The ``measure_*`` functions are the shared CPU harnesses of
``pytest -m perf`` and ``tools/perf_truth.py``.  The benchmark the perf
ledger records is ROADMAP S1's job, not this file's.

Env knobs:
  BENCH_MODEL     mobilenet|ssd|yolov5|posenet|vit|mnist_trainer|overhead|generate
                  (default mobilenet; overhead = 5-element identity
                  passthrough isolating scheduler cost)
  BENCH_FUSE      0|1 (default 1) streaming-thread fusion for every
                  pipeline the bench builds (the overhead row always
                  reports BOTH dataplanes: fused_fps/unfused_fps)
  BENCH_BATCH     micro-batch size (default 128)
  BENCH_FRAMES    measured frames (default 4096)
  BENCH_DTYPE     model dtype (default bfloat16)
  BENCH_HOST      1 = frames sourced from host memory (includes transfer)
  BENCH_HOST_CAP  per-row seconds cap for input=host rows (default 180);
                  an over-cap row is emitted labeled timed_out
  BENCH_INGEST_LANE  auto|on|off (default auto) — the filter's
                  double-buffered host->device staging lane
  BENCH_RAW       1 = also measure the bare jitted model at the same
                  batch (adds raw_fps / pipeline_vs_raw to the row — the
                  framework-overhead contract: pipeline >= 0.9x raw)
  BENCH_DEPTH     micro-batches kept in flight by the filter (default 4)
  BENCH_BATCH_TIMEOUT  ms a partial micro-batch waits for fill (default
                  20; latency-optimized rows use 2)
  BENCH_INGEST    block = frames enter pre-batched (one BatchFrame per
                  micro-batch, ≙ converter frames-per-tensor); default
                  per-frame pushes
  BENCH_SINK_SPLIT 0 = sink delivers whole blocks to callbacks (skips the
                  per-frame fan-out; counters use batch_size)
  BENCH_MESH      mesh spec for the filter ('tp:4' / 'dp:2,tp:2'; empty
                  = unsharded)
  BENCH_DEADLINE  seconds the row may take (default 420); internal waits
                  are carved from what remains
"""

import json
import os
import re
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_T0 = time.time()  # process start; deadline windows anchor here
NORTH_STAR_FPS = 1000.0  # BASELINE.json north star, MobileNet headline row

_HERE = os.path.dirname(os.path.abspath(__file__))

BATCH_TIMEOUT_DEFAULT_MS = "20"


def _utc_iso(ts: float = None) -> str:
    return time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(time.time() if ts is None else ts)
    )


def age_days(captured_at: str, now: float = None) -> "float | None":
    """Days since an ISO-8601 ``captured_at`` stamp (None when
    unparseable) — the perf-truth report (tools/perf_truth.py) labels the
    committed baseline's age with it."""
    import calendar

    try:
        # timegm, not mktime-minus-timezone: the stamp is UTC, and
        # mktime's DST guess for the stamp's date would skew the epoch
        # by up to an hour on DST-observing boxes
        then = calendar.timegm(time.strptime(
            str(captured_at), "%Y-%m-%dT%H:%M:%SZ"))
    except (ValueError, OverflowError):
        return None
    now = time.time() if now is None else now
    return round(max(0.0, (now - then) / 86400.0), 1)


def git_rev() -> "str | None":
    """Short git revision of the harness tree (None outside a checkout).
    Stamped onto perf-truth baselines so their history aligns with
    commits."""
    try:
        r = subprocess.run(
            ["git", "-C", _HERE, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
    except (OSError, subprocess.SubprocessError):
        return None
    rev = r.stdout.strip()
    return rev if r.returncode == 0 and rev else None


def emit(result: dict) -> None:
    print(json.dumps(result), flush=True)


def measure_ingest_overlap(nb: int = 14, h2d_s: float = 0.004,
                           comp_s: float = 0.004) -> "tuple[float, float]":
    """(t_serial, t_lane) for the host-ingest structure on equal sleep
    costs: serialized stack+transfer-then-compute vs the double-buffered
    staging lane (transfer overlaps the previous batch's compute).
    Shared by tools/perf_truth.py and the `pytest -m perf` overlap
    floor, so the two published ratios measure the SAME harness."""
    import numpy as np

    from nnstreamer_tpu.core.feed import HostStagingLane

    frames = [[np.zeros((256,), np.float32)] for _ in range(8)]

    def to_dev(arrs):
        time.sleep(h2d_s)
        return [np.array(a) for a in arrs]

    t0 = time.perf_counter()
    for _ in range(nb):  # serialized: stack+transfer then compute
        to_dev([np.stack([f[0] for f in frames])])
        time.sleep(comp_s)
    t_serial = time.perf_counter() - t0

    lane = HostStagingLane(to_dev, name="overlap")
    try:
        t0 = time.perf_counter()
        prev = None
        for _ in range(nb):  # double-buffered: transfer overlaps compute
            job = lane.submit(frames)
            if prev is not None:
                prev.result()
                time.sleep(comp_s)
            prev = job
        prev.result()
        time.sleep(comp_s)
        t_lane = time.perf_counter() - t0
    finally:
        lane.close()
    return t_serial, t_lane


def measure_pipeline_vs_raw(nbatches: int = 24) -> "tuple[float, float]":
    """(raw_fps, pipeline_fps) for the SAME async-sim device costs — the
    CPU-proxy of the headline ``pipeline_vs_raw`` roofline ratio
    (ROADMAP item 1: the gap may only shrink).

    raw: the bare backend driven with the same depth-8 in-flight
    structure ``measure_raw_fps`` uses on a real chip (async dispatch,
    sync at window granularity).  pipeline: the full
    appsrc!tensor_filter!tensor_sink dataplane over the identical
    backend knobs.  Shared by tools/perf_truth.py and the
    ``pytest -m perf`` floor, so the published ratio and the pinned
    gate measure the SAME harness."""
    import numpy as np

    from nnstreamer_tpu.backends.base import find_backend
    from nnstreamer_tpu.pipeline import parse_pipeline

    compute_ms, transfer_ms, dispatch_ms, mb = 4.0, 2.0, 0.5, 8
    custom = (
        f"compute_ms:{compute_ms},transfer_ms:{transfer_ms},"
        f"dispatch_ms:{dispatch_ms}"
    )
    # -- raw ceiling: bare invoke_batch, depth-8 window, periodic sync --
    be = find_backend("async-sim")()
    be.open(None, {"custom": custom})
    try:
        batch = np.zeros((mb, 64), np.float32)
        window = []
        done = 0
        t0 = time.perf_counter()
        for _ in range(nbatches):
            window.append(be.invoke_batch([batch]))
            if len(window) >= 8:
                for o in window.pop(0):
                    np.asarray(o)  # device_get at window granularity
            done += mb
        for out in window:
            for o in out:
                np.asarray(o)
        raw_fps = done / (time.perf_counter() - t0)
    finally:
        be.close()
    # -- pipeline: the full dataplane over identical device knobs -------
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=512 ! tensor_filter name=f "
        f"framework=async-sim custom={custom} max-batch={mb} "
        "dispatch-depth=8 ingest-lane=off ! tensor_sink name=out "
        "max-stored=1",
        name="pvr",
    )
    pipe.start()
    try:
        done_d = {"n": 0}
        pipe["out"].connect_new_data(
            lambda f: done_d.__setitem__("n", done_d["n"] + 1))
        arr = np.zeros((64,), np.float32)
        n = mb * nbatches
        for _ in range(mb * 4):  # warmup: fill the window, settle batching
            pipe["src"].push(arr)
        t_w = time.time()
        while done_d["n"] < mb * 4 and time.time() - t_w < 20:
            time.sleep(0.002)
        if done_d["n"] < mb * 4:
            raise RuntimeError(
                f"pipeline_vs_raw warmup incomplete: {done_d['n']}/"
                f"{mb * 4} frames in 20s")
        # stability drain: a straggler warmup completion counted inside
        # the timed window would inflate pipeline_fps (always in the
        # passing direction)
        stable_since, last = time.time(), done_d["n"]
        while time.time() - stable_since < 0.3:
            time.sleep(0.02)
            if done_d["n"] != last:
                stable_since, last = time.time(), done_d["n"]
        done_d["n"] = 0
        t0 = time.perf_counter()
        for _ in range(n):
            pipe["src"].push(arr)
        while done_d["n"] < n and time.perf_counter() - t0 < 30:
            time.sleep(0.002)
        pipeline_fps = done_d["n"] / (time.perf_counter() - t0)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=15)
    finally:
        pipe.stop()
    return raw_fps, pipeline_fps


GEN_PROPS = (
    "dtype:float32,vocab:61,d_model:32,heads:2,layers:2,d_ff:64,"
    "seq:128,seed:11"
)


def _drive_generate(custom: str, slot_width: int, prompts, max_new: int,
                    chunk: int, timeout_s: float) -> dict:
    """Drive one tensor_generator pipeline with ``prompts`` pushed
    concurrently; measure aggregate tokens/s + per-stream latency at
    the sink.  A warmup wave (first prompt alone) runs outside the
    timed window so compile/jit-bucket costs never land in it."""
    import numpy as np

    from nnstreamer_tpu.pipeline import parse_pipeline

    streams = len(prompts)
    pipe = parse_pipeline(
        f"appsrc name=src max-buffers=64 ! "
        f"tensor_generator name=gen slots={slot_width} "
        f"custom={custom} max-new={max_new} chunk={chunk} ! "
        "tensor_sink name=out",
        name=f"genbench{slot_width}",
    )
    pipe.start()
    try:
        arrivals = []  # (t, tokens_in_chunk, stream_seq, final)
        pipe["out"].connect_new_data(
            lambda f: arrivals.append((
                time.perf_counter(),
                int(np.asarray(f.tensors[0]).shape[1])
                if f.tensors else 0,
                f.meta.get("stream_seq"), bool(f.meta.get("final")),
            )))
        pipe["src"].push(prompts[0])
        t_w = time.perf_counter()
        while (not any(a[3] for a in arrivals)
               and time.perf_counter() - t_w < timeout_s):
            time.sleep(0.005)
        if not any(a[3] for a in arrivals):
            raise RuntimeError(
                f"generate warmup incomplete after {timeout_s}s")
        arrivals.clear()
        # fleet-rollup evidence (slotted runs): digest the pipeline at
        # the window edges through the SAME builder the serversrc
        # publishes with, so generation rows carry the capacity
        # view (tokens/s, occupancy, headroom) a fleet controller reads
        digest_pub = None
        if slot_width > 0:
            from nnstreamer_tpu.core.fleet import (
                DigestPublisher,
                pipeline_digest_stats,
            )

            digest_pub = DigestPublisher(
                lambda: pipeline_digest_stats(pipe), lambda d: None,
                interval_s=0.05, name="bench")
            digest_pub.poll(force=True)  # tokens baseline at the window
        t0 = time.perf_counter()
        for p in prompts:
            pipe["src"].push(p)
        finals = 0
        while finals < streams and time.perf_counter() - t0 < timeout_s:
            finals = sum(1 for a in arrivals if a[3])
            time.sleep(0.002)
        dt = time.perf_counter() - t0
        if finals < streams:
            raise RuntimeError(
                f"generate run incomplete: {finals}/{streams} "
                f"streams finished in {timeout_s}s")
        got = sum(a[1] for a in arrivals)
        # per-stream wall / tokens -> per-token latency, p50 across
        # streams (every stream's tokens arrived by its final chunk)
        per_stream_end: dict = {}
        for t, _ntok, seq, _fin in arrivals:
            per_stream_end[seq] = max(t, per_stream_end.get(seq, t))
        per_token_ms = sorted(
            (end - t0) * 1e3 / max_new for end in per_stream_end.values()
        )
        gen_health = pipe.health()["gen"]
        out = {
            "tokens": got,
            "tokens_per_s": got / dt,
            "p50_ms_per_token": per_token_ms[len(per_token_ms) // 2],
            # EWMA of ACTIVE SLOTS per decode scan (scan length varies,
            # so tokens/steps would conflate the two)
            "tokens_per_step": (
                gen_health.get("gen_tokens_per_step", 0.0)
                if slot_width > 0 else 1.0
            ),
        }
        if digest_pub is not None:
            from nnstreamer_tpu.core.fleet import FleetObservatory

            d = digest_pub.poll(force=True)  # window-end digest
            obs = FleetObservatory(topic="bench")
            obs.ingest("bench", {"host": "local", "port": 0, "digest": d})
            roll = obs.rollup()
            out["fleet"] = {
                k: roll[k] for k in (
                    "tokens", "tokens_per_s", "occupancy",
                    "slot_headroom", "mem_headroom_bytes", "slots")
            }
        return out
    finally:
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()


def measure_generate_throughput(slots: int = 4, streams: int = 4,
                                max_new: int = 48, chunk: int = 8,
                                prompt_len: int = 8,
                                timeout_s: float = 120.0) -> dict:
    """Continuous batching vs request-serial generation on the zoo
    transformer (REAL tokens — functional truth for the bench row):
    ``streams`` concurrent prompts through a slotted ``tensor_generator``
    vs the SAME prompts through the pre-slot per-request path.

    NOTE on the speedup field: XLA-CPU batch economics at zoo-model
    sizes do not match an accelerator's (decode there is weight-
    streaming-bound, i.e. step cost is nearly batch-independent), so
    the SCHEDULER's multiplexing win is pinned by
    :func:`measure_slot_multiplex_speedup` (async-sim proxy) — this
    function reports what the real model measures on this host."""
    import numpy as np

    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, 61, (1, prompt_len)).astype(np.int32)
        for _ in range(streams)
    ]
    total = streams * max_new
    slotted = _drive_generate(GEN_PROPS, slots, prompts, max_new, chunk,
                              timeout_s)
    serial = _drive_generate(GEN_PROPS, 0, prompts, max_new, chunk,
                             timeout_s)
    for tag, r in (("slotted", slotted), ("serial", serial)):
        if r["tokens"] != total:
            raise RuntimeError(
                f"generate {tag} run lost tokens: {r['tokens']} != {total}")
    return {
        "tokens_per_s": round(slotted["tokens_per_s"], 1),
        "serialized_tokens_per_s": round(serial["tokens_per_s"], 1),
        "speedup": round(
            slotted["tokens_per_s"] / serial["tokens_per_s"], 2)
        if serial["tokens_per_s"] else None,
        "concurrent_streams": streams,
        "p50_ms_per_token": round(slotted["p50_ms_per_token"], 3),
        "serialized_p50_ms_per_token": round(
            serial["p50_ms_per_token"], 3),
        "slot_occupancy": round(
            slotted["tokens_per_step"] / max(1, slots), 3),
        # fleet-rollup capacity view of the slotted run (observatory
        # machinery — tokens/s, occupancy, admittable headroom) rides
        # the row next to the telemetry dump
        "fleet": slotted.get("fleet"),
    }


def measure_prefix_ttft(prefix_tokens: int = 256, suffix_tokens: int = 16,
                        trials: int = 3, grain: int = 64,
                        max_new: int = 2,
                        timeout_s: float = 180.0) -> dict:
    """Cold vs warm time-to-first-token with the shared-prefix KV cache
    (zoo transformer, REAL tokens): every prompt carries a
    ``prefix_tokens`` prefix + a fresh suffix; cold trials use a fresh
    random prefix (cache miss — full chunked prefill), warm trials reuse
    ONE shared prefix whose pages are already published (attach skips
    the covered tokens).  Trials interleave cold/warm so ambient load
    drift cancels; a separate warmup stream pays every compile bucket
    outside the timed windows.

    Shared by the BENCH_PREFIX_CACHE=1 generate row, the perf-truth
    ``prefix_ttft_speedup`` axis, and the ``pytest -m perf`` >=2x floor
    (warm TTFT <= 0.5x cold at 256 shared tokens), so the published
    ratio and the pinned gate measure the same harness.  The hit/miss
    ledger is asserted exactly — a silently-cold cache would otherwise
    publish a plausible-looking 1.0x ratio."""
    import numpy as np

    from nnstreamer_tpu.pipeline import parse_pipeline

    seq = prefix_tokens + suffix_tokens + max_new + 32
    # d_model 128 (not the 32-wide zoo default): prefill must COST
    # something on CPU or TTFT is pure pipeline overhead and the ratio
    # measures nothing (at d_model 32 cold ~= warm ~= 22ms fixed cost)
    props = (
        "dtype:float32,vocab:61,d_model:128,heads:4,layers:4,d_ff:512,"
        f"seq:{seq},seed:11"
    )
    pipe = parse_pipeline(
        f"appsrc name=src max-buffers=64 ! "
        f"tensor_generator name=gen slots=1 custom={props} "
        f"max-new={max_new} chunk=1 prefix-cache=on prefix-grain={grain} "
        "! tensor_sink name=out",
        name="prefixbench",
    )
    pipe.start()
    try:
        arrivals = []  # (t, final)
        pipe["out"].connect_new_data(
            lambda f: arrivals.append(
                (time.perf_counter(), bool(f.meta.get("final")))))
        rng = np.random.default_rng(7)

        def rand(n):
            return rng.integers(0, 61, (1, n)).astype(np.int32)

        def run_one(prefix):
            prompt = np.concatenate(
                [prefix, rand(suffix_tokens)], axis=1)
            finals = sum(1 for a in arrivals if a[1])
            mark = len(arrivals)
            t0 = time.perf_counter()
            pipe["src"].push(prompt)
            while time.perf_counter() - t0 < timeout_s:
                if sum(1 for a in arrivals if a[1]) > finals:
                    return (arrivals[mark][0] - t0) * 1e3
                time.sleep(0.0005)
            raise RuntimeError(
                f"prefix-ttft stream incomplete after {timeout_s}s")

        run_one(rand(prefix_tokens))  # warmup: compile buckets, untimed
        shared = rand(prefix_tokens)
        run_one(shared)               # prime: publish the shared prefix
        run_one(shared)               # attach warmup: compile the
        warmup_hits = 1               # export/concat/update ops, untimed
        cold, warm = [], []
        for _ in range(trials):
            cold.append(run_one(rand(prefix_tokens)))
            warm.append(run_one(shared))
        health = pipe.health()["gen"]
        # functional truth: exactly one hit per warm trial, one miss per
        # cold trial + warmup + prime, and every warm hit covered the
        # full shared-prefix grain span
        grain_eff = pipe["gen"]._prefix_pool.grain
        covered = (prefix_tokens // grain_eff) * grain_eff
        want_hits = trials + warmup_hits
        if health["prefix_hits"] != want_hits:
            raise RuntimeError(
                f"prefix-ttft cache never warmed: "
                f"{health['prefix_hits']} hits != {want_hits}")
        if health["prefix_misses"] != trials + 2:
            raise RuntimeError(
                f"prefix-ttft miss ledger off: {health['prefix_misses']} "
                f"!= {trials + 2}")
        if health["prefix_hit_tokens"] != want_hits * covered:
            raise RuntimeError(
                f"prefix-ttft short attach: {health['prefix_hit_tokens']} "
                f"hit tokens != {want_hits} * {covered}")
        c_med = sorted(cold)[len(cold) // 2]
        w_med = sorted(warm)[len(warm) // 2]
        return {
            "cold_ttft_ms": round(c_med, 3),
            "warm_ttft_ms": round(w_med, 3),
            "prefix_ttft_speedup": round(c_med / w_med, 2),
            "prefix_tokens": prefix_tokens,
            "prefix_hit_tokens": int(health["prefix_hit_tokens"]),
        }
    finally:
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()


def measure_slot_multiplex_speedup(slots: int = 4, streams: int = 4,
                                   max_new: int = 64, chunk: int = 8,
                                   step_base_ms: float = 1.0,
                                   per_slot_ms: float = 0.05,
                                   timeout_s: float = 60.0) -> dict:
    """The continuous-batching SCHEDULER win on the async-sim proxy
    (PR-6 discipline): simulated device steps pay a batch-independent
    base cost (the weight-streaming/dispatch regime of real LLM decode)
    plus a small per-active-slot increment, so the measured ratio
    isolates what this PR builds — slot multiplexing through the full
    pipeline — from host GEMM quirks.  slots=1 is the request-serial
    baseline: SAME engine, same emission path, one request at a time.

    Shared by the BENCH_MODEL=generate row (``sim_speedup``) and the
    ``pytest -m perf`` >=2x floor, so the published ratio and the
    pinned gate measure the same harness."""
    import numpy as np

    custom = (
        f"sim:1,sim_step_ms:{step_base_ms},sim_per_slot_ms:{per_slot_ms},"
        "sim_prefill_ms:0.02,vocab:997"
    )
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, 997, (1, 8)).astype(np.int32) for _ in range(streams)
    ]
    total = streams * max_new
    slotted = _drive_generate(custom, slots, prompts, max_new, chunk,
                              timeout_s)
    serial = _drive_generate(custom, 1, prompts, max_new, chunk, timeout_s)
    for tag, r in (("slotted", slotted), ("serial", serial)):
        if r["tokens"] != total:
            raise RuntimeError(
                f"sim {tag} run lost tokens: {r['tokens']} != {total}")
    return {
        "sim_speedup": round(
            slotted["tokens_per_s"] / serial["tokens_per_s"], 2),
        "sim_tokens_per_s": round(slotted["tokens_per_s"], 1),
        "sim_serialized_tokens_per_s": round(serial["tokens_per_s"], 1),
        "sim_p50_ms_per_token": round(slotted["p50_ms_per_token"], 3),
        "sim_slot_occupancy": round(
            slotted["tokens_per_step"] / max(1, slots), 3),
    }


def measure_dispatch_overlap(nbatches: int = 24,
                             budget_s: float = 8.0) -> dict:
    """``{"dispatch_overlap", "dispatch_thread_blocking_syncs"}`` for the
    async dispatch window on the async-sim fake device (compute 4ms
    single-server, transfer 3ms on the syncing thread, dispatch 1ms):
    pipeline throughput over the device's own serial service rate (1.0 =
    the window hides all framework cost), plus the structural count of
    dispatch-thread blocking syncs (must be 0 — the reaper owns those
    waits).  Shared by the `pytest -m perf` floor and the perf-truth
    baseline, so the published ratio and the gated one measure the SAME
    harness."""
    import numpy as np

    from nnstreamer_tpu.pipeline import parse_pipeline

    compute_ms, transfer_ms, dispatch_ms, mb = 4.0, 3.0, 1.0, 8
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=512 ! tensor_filter name=f "
        "framework=async-sim "
        f"custom=compute_ms:{compute_ms},transfer_ms:{transfer_ms},"
        f"dispatch_ms:{dispatch_ms} "
        f"max-batch={mb} dispatch-depth=8 ! tensor_sink name=out "
        "max-stored=1",
        name="proxy",
    )
    pipe.start()
    done = {"n": 0}
    pipe["out"].connect_new_data(
        lambda f: done.__setitem__("n", done["n"] + 1))
    n = mb * nbatches
    arr = np.zeros((64,), np.float32)
    t0 = time.perf_counter()
    for _ in range(n):
        pipe["src"].push(arr)
    cap = max(5.0, budget_s)
    while done["n"] < n and time.perf_counter() - t0 < cap:
        time.sleep(0.002)
    elapsed = time.perf_counter() - t0
    be = pipe["f"].backend
    blocked = [
        t for t in be.blocking_syncs if not t.endswith("-reaper")
    ]
    pipe["src"].end_of_stream()
    pipe.wait(timeout=15)
    pipe.stop()
    # device service rate = 1000/compute_ms batches/s (single server);
    # 1.0 means the window hid every framework cost behind compute
    pipeline_rate = (done["n"] / mb) / elapsed if elapsed else 0.0
    return {
        "dispatch_overlap": round(pipeline_rate / (1000.0 / compute_ms), 3),
        "dispatch_thread_blocking_syncs": len(blocked),
    }


def _simmesh_pipeline_fps(mesh_dp: int, nbatches: int = 30,
                          compute_ms: float = 6.0,
                          budget_s: float = 10.0) -> float:
    """Full-dataplane fps over the async-sim MESH twin: ``mesh_dp``
    independent sleeping shard servers, each serving its 1/N batch shard
    concurrently, outputs ready only when every shard is.  What the dp
    aggregate-throughput floor actually measures is the sharded FEED
    STRUCTURE (scatter, window readiness over all shards, no per-shard
    serialization) — deliberately NOT XLA-CPU dp scaling, which a
    single-core box cannot exhibit (both virtual devices share the one
    core; the PR-9 SimSlotModel discipline)."""
    import numpy as np

    from nnstreamer_tpu.pipeline import parse_pipeline

    mb = 8
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=512 ! tensor_filter name=f "
        "framework=async-sim "
        f"custom=compute_ms:{compute_ms},transfer_ms:0.5,dispatch_ms:0.2,"
        f"mesh_dp:{mesh_dp} "
        f"max-batch={mb} dispatch-depth=8 ! tensor_sink name=out "
        "max-stored=1",
        name=f"simmesh{mesh_dp}",
    )
    pipe.start()
    try:
        done = {"n": 0}
        pipe["out"].connect_new_data(
            lambda f: done.__setitem__("n", done["n"] + 1))
        n = mb * nbatches
        arr = np.zeros((64,), np.float32)
        t0 = time.perf_counter()
        for _ in range(n):
            pipe["src"].push(arr)
        while done["n"] < n and time.perf_counter() - t0 < budget_s:
            time.sleep(0.002)
        elapsed = time.perf_counter() - t0
        if done["n"] < n:
            raise RuntimeError(
                f"simmesh dp:{mesh_dp} run incomplete: {done['n']}/{n} "
                f"in {budget_s:.0f}s")
        pipe["src"].end_of_stream()
        pipe.wait(timeout=15)
    finally:
        pipe.stop()
    return done["n"] / elapsed


SHARDED_PROPS = (
    "arch:transformer,dtype:float32,vocab:64,d_model:64,heads:4,"
    "layers:3,d_ff:256,seq:32,seed:5"
)


def measure_sharded_overhead(batch: int = 16, rounds: int = 6,
                             iters: int = 4) -> dict:
    """The two sharded-dataplane truths, chip-free:

    * ``sharded_ratio`` — jax-xla ``invoke_batch`` fps on a
      SINGLE-DEVICE-EQUIVALENT mesh (``mesh=dp:1``: the full sharded
      machinery — NamedSharding in/out specs, scatter path, mesh-keyed
      pooling — with zero parallelism to hide it) over the unsharded
      backend on the same zoo transformer.  1.0 = the mesh plumbing is
      free; the perf gate floors it at 0.85 (<= 15% dispatch overhead).
      Rounds INTERLEAVE the two configs and the ratio takes best-of-
      round, so ambient box load cancels instead of biasing one side.
    * ``dp2_speedup`` — aggregate full-pipeline fps of the sharded
      dataplane over the async-sim mesh twin, ``mesh_dp:2`` vs
      ``mesh_dp:1`` on identical compute-bound knobs (see
      :func:`_simmesh_pipeline_fps` for why the device layer is
      simulated).  Floor >= 1.5x.

    Shared by the ``pytest -m perf`` floors and the perf-truth
    ``sharded_overhead`` axis — the published numbers and the gated ones
    measure the SAME harness."""
    import numpy as np

    from nnstreamer_tpu.elements.filter import SingleShot

    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, (batch, 32)).astype(np.int32)

    def fps_of(shot) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            out = shot.invoke_batch([toks])
        np.asarray(out[0])
        return iters * batch / (time.perf_counter() - t0)

    with SingleShot(framework="jax-xla", model="zoo",
                    custom=SHARDED_PROPS) as plain, \
            SingleShot(framework="jax-xla", model="zoo",
                       custom=SHARDED_PROPS, mesh="dp:1") as sharded:
        # warmup: compile both buckets outside the timed rounds
        np.asarray(plain.invoke_batch([toks])[0])
        np.asarray(sharded.invoke_batch([toks])[0])
        best = 0.0
        for _ in range(rounds):
            # interleaved A/B: ambient load hits both sides of a round
            f_plain = fps_of(plain)
            f_shard = fps_of(sharded)
            best = max(best, f_shard / f_plain)
    dp1 = _simmesh_pipeline_fps(1)
    dp2 = _simmesh_pipeline_fps(2)
    return {
        "sharded_ratio": round(best, 3),
        "dp2_speedup": round(dp2 / dp1, 2),
        "simmesh_dp1_fps": round(dp1, 1),
        "simmesh_dp2_fps": round(dp2, 1),
    }


def quant_applied(which: str) -> bool:
    """True when BENCH_QUANT actually changes the model that runs —
    mobilenet/ssd/yolov5 (int8 convs) and vit (int8 dense) have int8
    paths; one definition keeps the executed pipeline and the emitted row
    label in agreement."""
    return which in ("mobilenet", "ssd", "yolov5", "vit") and os.environ.get(
        "BENCH_QUANT", ""
    ) in ("1", "int8")


def measure_raw_fps(fn, params, pool, batch: int, n_frames: int,
                    host_input: bool = False, cap_s: float = 20.0,
                    out_meta: dict = None) -> float:
    """Bare jitted-model throughput at `batch` — the ceiling the pipeline
    is judged against (shared by bench.py BENCH_RAW and
    tools/bench_overhead.py so the two published ratios can't diverge).

    Bounded iterations with a periodic sync every 8 dispatches: async
    dispatch must be allowed to pipeline (that's the ceiling) but never
    to queue minutes of executions and their output buffers.  With
    ``host_input`` the per-iteration host->device put is INSIDE the timed
    loop, matching what a BENCH_HOST pipeline pays — a slow link makes
    this loop deadline-risky, so ``cap_s`` is a hard per-row cap and
    ``out_meta`` (when given) records ``timed_out``/completed iterations
    instead of letting the row eat the whole bench budget."""
    import jax
    import numpy as np

    jit_fn = jax.jit(lambda xs: fn(params, [xs]))
    host_batch = np.stack(
        [np.asarray(pool[i % len(pool)]) for i in range(batch)]
    )
    stacked = jax.device_put(host_batch)
    jax.block_until_ready(jit_fn(stacked))  # compile
    n_iters = max(1, n_frames // batch)
    t0 = time.perf_counter()
    out = None
    done = 0
    capped = False
    for i in range(n_iters):
        x = jax.device_put(host_batch) if host_input else stacked
        out = jit_fn(x)
        done += 1
        if done % 8 == 0:
            jax.block_until_ready(out)
        if time.perf_counter() - t0 > cap_s:
            capped = done < n_iters
            break
    jax.block_until_ready(out)
    if out_meta is not None:
        out_meta["timed_out"] = capped
        out_meta["iters_done"] = done
        out_meta["iters_wanted"] = n_iters
    return done * batch / (time.perf_counter() - t0)


METRICS = {
    "mobilenet": ("mobilenet_v2_image_labeling_fps_per_chip", "fps"),
    "ssd": ("ssd_mobilenet_v2_bbox_fps_per_chip", "fps"),
    "yolov5": ("yolov5s_bbox_fps_per_chip", "fps"),
    "posenet": ("posenet_pose_fps_per_chip", "fps"),
    "vit": ("vit_image_labeling_fps_per_chip", "fps"),
    "mnist_trainer": ("mnist_cnn_trainer_epoch_seconds", "s"),
    # scheduler-overhead row: 5-element identity passthrough (CPU, no
    # accelerator, no model) — isolates the dataplane's per-frame cost so
    # a fusion/handoff regression is a one-line measurable delta
    "overhead": ("scheduler_overhead_passthrough_fps", "fps"),
    # continuous-batching row: N concurrent generation streams share one
    # slot batch (zoo transformer) vs the same requests served
    # one at a time — decode must be token-batch-bound, not request-bound
    "generate": ("continuous_batching_tokens_per_s", "tokens/s"),
}


def bench_fuse() -> bool:
    """BENCH_FUSE=0|1 (default 1): streaming-thread fusion for every
    pipeline this bench builds; exported to the pipeline layer as
    NNS_FUSE so parse_pipeline picks it up."""
    return os.environ.get("BENCH_FUSE", "1").lower() not in (
        "0", "false", "no",
    )


def bench_prefix_cache() -> bool:
    """BENCH_PREFIX_CACHE=0|1 (default 0): make the generate row also
    measure the shared-prefix KV cache (cold vs warm TTFT) and stamp the
    ``prefix_cache`` signature axis — warm-prefix evidence must never
    stand in for a cold-cache row or vice versa."""
    return os.environ.get("BENCH_PREFIX_CACHE", "0").lower() in (
        "1", "true", "yes", "on",
    )


def bench_mesh():
    """BENCH_MESH ('tp:4' / 'dp:2,tp:2'; empty = unsharded): the mesh
    signature-axis value — 0 (the pre-mesh implicit default, matching
    _SIG_DEFAULTS) when unset, else the CANONICAL spec string so two
    spellings of one mesh can't mint two evidence signatures."""
    raw = os.environ.get("BENCH_MESH", "").strip()
    if not raw or raw == "0":
        return 0
    from nnstreamer_tpu.parallel.mesh import mesh_spec_str, parse_mesh_spec

    axes = parse_mesh_spec(raw)
    if any(v == -1 for v in axes.values()):
        # a wildcard resolves differently per box, so one signature
        # string would label physically different meshes — evidence
        # rows must name the mesh they actually measured
        raise SystemExit(
            f"BENCH_MESH={raw!r}: -1 wildcards are not allowed in bench "
            "signatures; spell out the axis sizes")
    return mesh_spec_str(axes) if axes else 0


def measure_fuse_overhead(n_frames: int = 30000, cap_s: float = 60.0,
                          deadline_ts: float = None) -> dict:
    """Fused vs unfused identity-chain fps on the 5-element scheduler-
    overhead chain (appsrc ! identity x3 ! tensor_sink, CPU-safe) —
    ``{"fused_fps", "unfused_fps", "fuse_speedup", "telemetry"}``.
    Shared by the BENCH_MODEL=overhead row and the perf-truth baseline,
    so the published speedup and the regression-gated one measure the
    SAME harness.

    Both runs are measured with the TRACER ARMED (always-on latency
    histograms recording), symmetrically — the ratio stays fair, the
    published fps IS the histograms-armed number (the per-frame cost
    claim is in the evidence, not beside it), and the row's telemetry
    dump carries the per-element p50/p95/p99."""
    import numpy as np

    from nnstreamer_tpu.pipeline import parse_pipeline

    pool = [np.zeros((64,), np.float32) for _ in range(16)]

    def run(fuse: bool):
        # the cap is re-derived PER RUN from the absolute deadline (when
        # given): a stalled fused run must shrink the unfused run's
        # window, not grant it a second full budget past the deadline
        cap = cap_s
        if deadline_ts is not None:
            cap = max(10.0, min(cap_s, deadline_ts - time.time() - 15.0))
        pipe = parse_pipeline(
            "appsrc name=src max-buffers=256 ! identity ! identity ! "
            "identity ! tensor_sink name=out max-stored=1",
            name="overhead", fuse=fuse,
        )
        pipe.enable_tracing()
        pipe.start()
        src, sink = pipe["src"], pipe["out"]
        done = {"n": 0}
        sink.connect_new_data(
            lambda f: done.__setitem__("n", done["n"] + 1)
        )
        for i in range(256):  # warmup: settle thread scheduling
            src.push(pool[i % 16])
        t_w = time.time()
        while done["n"] < 256 and time.time() - t_w < cap:
            time.sleep(0.005)
        done["n"] = 0
        t0 = time.perf_counter()
        for i in range(n_frames):
            src.push(pool[i % 16])
        while done["n"] < n_frames and time.perf_counter() - t0 < cap:
            time.sleep(0.002)
        dt = time.perf_counter() - t0
        measured = done["n"]
        src.end_of_stream()
        pipe.wait(timeout=30)
        telemetry = pipe.telemetry_summary()
        pipe.stop()
        return measured / dt, telemetry

    fused, fused_telemetry = run(True)
    unfused, _ = run(False)
    return {
        "fused_fps": round(fused, 1),
        "unfused_fps": round(unfused, 1),
        "fuse_speedup": round(fused / unfused, 2) if unfused else None,
        "telemetry": fused_telemetry,
    }


def overhead_row(deadline_ts: float) -> dict:
    """Scheduler-overhead microbench: appsrc ! identity x3 ! tensor_sink
    (5 elements), tiny host frames, CPU-safe (no accelerator, no model).
    Measures BOTH dataplanes every run — `value` is the configured
    BENCH_FUSE mode's fps, `fused_fps`/`unfused_fps`/`fuse_speedup`
    record the tentpole's delta explicitly."""
    n_frames = int(os.environ.get("BENCH_FRAMES", "30000"))
    res = measure_fuse_overhead(
        n_frames=n_frames, cap_s=60.0, deadline_ts=deadline_ts,
    )
    value = res["fused_fps"] if bench_fuse() else res["unfused_fps"]
    return {
        "metric": METRICS["overhead"][0],
        "value": round(value, 1),
        "unit": "fps",
        "vs_baseline": None,
        "fused_fps": res["fused_fps"],
        "unfused_fps": res["unfused_fps"],
        "fuse_speedup": res["fuse_speedup"],
        "chain": "appsrc!identity!identity!identity!tensor_sink",
        "frames": n_frames,
        "telemetry": res["telemetry"],
    }


def generate_row(deadline_ts: float) -> dict:
    """Continuous-batching row (zoo transformer, on this process's device):
    N concurrent generation streams multiplexed into shared slots vs the
    same requests served one at a time.  ``value`` is the slotted
    aggregate tokens/s; the serialized baseline and speedup ride along so
    the roofline claim (token-batch-bound, not request-bound) is a
    one-line delta."""
    slots = int(os.environ.get("BENCH_SLOTS", "4"))
    streams = int(os.environ.get("BENCH_STREAMS", str(max(4, slots))))
    budget = max(30.0, min(240.0, deadline_ts - time.time() - 30.0))
    res = measure_generate_throughput(
        slots=slots, streams=streams, timeout_s=budget)
    res.update(measure_slot_multiplex_speedup(
        slots=slots, streams=streams, timeout_s=min(60.0, budget)))
    if bench_prefix_cache():
        res.update(measure_prefix_ttft(
            timeout_s=min(180.0, max(30.0, deadline_ts - time.time() - 30.0))))
    return {
        "metric": METRICS["generate"][0],
        "value": res["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": None,
        **{k: v for k, v in res.items() if k != "tokens_per_s"},
    }


def pipeline_row(which: str, batch: int, n_frames: int, dtype: str,
                 host_frames: bool, deadline_ts: float) -> dict:
    """``deadline_ts`` is the absolute time.time() by which this function
    should have returned: every internal wait is carved from
    time-remaining, so imports/model-build/compile time spent before any
    given phase shrinks that phase's window."""
    import numpy as np

    # fail BEFORE any pipeline/device work: a zero-block run would
    # otherwise publish a plausible-looking 0-fps row
    if os.environ.get("BENCH_INGEST", "") == "block" and n_frames < batch:
        raise SystemExit(
            f"BENCH_INGEST=block needs BENCH_FRAMES >= batch "
            f"({n_frames} < {batch})"
        )

    # host rows additionally get a PER-ROW cap: frames crossing the
    # host->device link make every phase link-speed-bound, and a slow
    # link must produce a labeled `timed_out` row instead of eating the
    # entire bench budget
    if host_frames:
        host_cap = float(os.environ.get("BENCH_HOST_CAP", "180"))
        deadline_ts = min(deadline_ts, time.time() + host_cap)

    from nnstreamer_tpu.backends.jax_xla import register_jax_model
    from nnstreamer_tpu.models import build
    from nnstreamer_tpu.pipeline import parse_pipeline

    labels_path = "/tmp/nns_bench_labels.txt"
    with open(labels_path, "w") as f:
        f.write("\n".join(f"class{i}" for i in range(1001)))

    # BASELINE.md tracked rows: mobilenet (headline), ssd+bbox decode,
    # yolov5, posenet+pose decode — all measured as full pipelines
    if which == "mobilenet":
        size, family, props = 224, "mobilenet_v2", {"dtype": dtype}
        if quant_applied(which):
            # int8 MXU path ≙ the reference's quantized-tflite flagship
            # (mobilenet_v2_1.0_224_quant.tflite)
            props["quantize"] = "int8"
        decoder = f"tensor_decoder mode=image_labeling option1={labels_path} ! "
    elif which == "ssd":
        from nnstreamer_tpu.models.ssd_mobilenet import write_box_priors

        priors = write_box_priors("/tmp/nns_bench_priors.txt")
        size, family, props = 300, "ssd_mobilenet_v2", {"dtype": dtype}
        if quant_applied(which):
            props["quantize"] = "int8"
        decoder = (
            "tensor_decoder mode=bounding_boxes option1=mobilenet-ssd "
            f"option2={labels_path} option3={priors} option4=300:300 "
            "option5=300:300 ! "
        )
    elif which == "yolov5":
        size = int(os.environ.get("BENCH_SIZE", "640"))
        family, props = "yolov5s", {"dtype": dtype, "size": str(size)}
        if quant_applied(which):
            props["quantize"] = "int8"
        decoder = (
            "tensor_decoder mode=bounding_boxes option1=yolov5 "
            f"option2={labels_path} option4={size}:{size} "
            f"option5={size}:{size} ! "
        )
    elif which == "posenet":
        size, family, props = 257, "posenet", {"dtype": dtype}
        decoder = (
            "tensor_decoder mode=pose_estimation option1=257:257 "
            "option2=257:257 option4=heatmap-offset ! "
        )
    elif which == "vit":
        # transformer-era vision row (net-new vs BASELINE.md): the fused
        # attention kernel on a TPU (chosen by the model from platform and
        # shape, no prop), same labeling pipeline as the headline
        size, family, props = 224, "vit", {"dtype": dtype}
        if quant_applied(which):
            props["quantize"] = "int8"
        decoder = f"tensor_decoder mode=image_labeling option1={labels_path} ! "
    else:
        raise SystemExit(f"unknown BENCH_MODEL {which!r}")

    metric = METRICS[which][0]
    fn, params, in_spec, out_spec = build(family, props)
    register_jax_model("bench_model", fn, params, in_spec, out_spec)

    sink_split = os.environ.get("BENCH_SINK_SPLIT", "1") not in ("0", "false")
    if not sink_split:
        # whole-block delivery: the decoder's host half must also keep
        # blocks whole (vectorized decode_fused_batch) or it re-splits.
        # Fail LOUD for decoders without that path — a silently-split
        # pipeline would publish a row labeled sink_split:false that
        # measured the default configuration
        from nnstreamer_tpu.core import registry as _registry

        m = re.search(r"mode=([a-z_0-9]+)", decoder)
        if m is None:
            raise SystemExit(
                "BENCH_SINK_SPLIT=0: whole-block delivery needs a "
                f"tensor_decoder with a mode= (got {decoder!r})"
            )
        mode = m.group(1)
        dec_cls = _registry.get(_registry.KIND_DECODER, mode)
        if not hasattr(dec_cls, "decode_fused_batch"):
            raise SystemExit(
                f"BENCH_SINK_SPLIT=0: decoder mode {mode!r} has no "
                "decode_fused_batch (whole-block delivery unsupported)"
            )
        decoder = decoder.replace(
            "tensor_decoder ", "tensor_decoder split-batches=false ", 1
        )
    # batch-timeout: how long a partial micro-batch waits for fill.  20 ms
    # suits throughput configs (the e2e latency instrument below pushes
    # LONE frames, which would eat the whole wait); the latency-optimized
    # row overrides it down so p50 measures serving, not the fill timer.
    batch_timeout_ms = os.environ.get(
        "BENCH_BATCH_TIMEOUT", BATCH_TIMEOUT_DEFAULT_MS
    )
    mesh_spec = bench_mesh()
    pipe = parse_pipeline(
        "appsrc name=src max-buffers=512 ! "
        "tensor_filter name=f framework=jax-xla model=bench_model "
        f"max-batch={batch} batch-timeout={batch_timeout_ms} "
        "latency=1 throughput=1 "
        f"dispatch-depth={os.environ.get('BENCH_DEPTH', '4')} "
        f"ingest-lane={os.environ.get('BENCH_INGEST_LANE', 'auto')} "
        + (f"mesh={mesh_spec} " if mesh_spec != 0 else "")
        + "! " + decoder
        + "tensor_sink name=out max-stored=1"
        + ("" if sink_split else " split-batches=false"),
        name="bench",
    )
    # frame pool: realistic uint8 camera frames, cycled (generation off the
    # measured path).  Device-resident by default (isolates the dataplane
    # from the host->device link); BENCH_HOST=1 measures host-sourced
    # frames, which is how real streams arrive.
    rng = np.random.default_rng(0)
    pool = [
        rng.integers(0, 255, (size, size, 3), dtype=np.uint8) for _ in range(16)
    ]
    # BENCH_INGEST=block: frames enter pre-batched, one BatchFrame per
    # micro-batch (≙ the reference converter's frames-per-tensor batching)
    # — per-frame Python ingest/stacking costs are paid once per block.
    # fps still counts LOGICAL frames (the sink splits the batch).
    ingest_block = os.environ.get("BENCH_INGEST", "") == "block"
    blocks = []
    if ingest_block:
        blocks = [
            np.stack([pool[(i + j) % len(pool)] for j in range(batch)])
            for i in range(4)
        ]
    if not host_frames:
        import jax

        pool = [jax.device_put(p) for p in pool]
        blocks = [jax.device_put(b) for b in blocks]
        jax.block_until_ready(pool)
        jax.block_until_ready(blocks)

    pipe.start()
    src, sink = pipe["src"], pipe["out"]

    # compile time dominates warmup; whatever remains is the measure cap
    warmup_cap = max(30.0, (deadline_ts - time.time()) * 0.7)

    # warmup: trigger compiles for the full bucket and any tail buckets
    done = {"n": 0}
    # counts LOGICAL frames either way: split mode delivers per-frame
    # (batch_size absent -> 1), block-delivery mode delivers whole blocks
    sink.connect_new_data(
        lambda f: done.__setitem__(
            "n", done["n"] + getattr(f, "batch_size", 1)
        )
    )
    if ingest_block:
        for i in range(2):
            src.push_block(blocks[i % len(blocks)])
    else:
        for i in range(batch * 2):
            src.push(pool[i % len(pool)])
    t_wait = time.time()
    while done["n"] < batch * 2 and time.time() - t_wait < warmup_cap:
        time.sleep(0.01)
    if done["n"] < batch * 2:
        pipe.stop()
        if host_frames:
            # deadline-safe host row: the link couldn't even finish
            # warmup inside the per-row cap — report that, labeled,
            # instead of dying rc!=0 with the budget burned
            return {
                "metric": metric, "value": None, "unit": "fps",
                "vs_baseline": None, "timed_out": True,
                "error": (
                    f"host ingest warmup incomplete: {done['n']}/"
                    f"{batch * 2} frames in {warmup_cap:.0f}s"
                ),
            }
        raise RuntimeError(
            f"warmup incomplete: {done['n']}/{batch * 2} frames in "
            f"{warmup_cap:.0f}s"
        )
    # drain stragglers so leftover warmup completions can never leak into
    # the measured counter: wait until the count is stable for 2 s
    stable_since, last = time.time(), done["n"]
    while time.time() - stable_since < 2.0:
        time.sleep(0.1)
        if done["n"] != last:
            stable_since, last = time.time(), done["n"]

    # measured run (cap: whatever remains of the budget, minus EOS margin)
    measure_cap = max(30.0, deadline_ts - time.time() - 15.0)
    done["n"] = 0
    t0 = time.perf_counter()
    if ingest_block:
        n_frames = (n_frames // batch) * batch
        for i in range(n_frames // batch):
            src.push_block(blocks[i % len(blocks)])
    else:
        for i in range(n_frames):
            src.push(pool[i % len(pool)])
    while done["n"] < n_frames and time.perf_counter() - t0 < measure_cap:
        time.sleep(0.005)
    dt = time.perf_counter() - t0
    fps = done["n"] / dt
    # a host row that ran out of its per-row cap mid-measure still
    # reports the throughput it sustained, labeled — partial evidence
    # beats a dead 480s window
    row_timed_out = host_frames and done["n"] < n_frames

    # BASELINE.md tracks p50 per-frame latency alongside fps for the
    # detector/pose rows.  Two instruments: the filter's latency prop
    # measures the (async) invoke DISPATCH per logical frame; true
    # end-to-end latency is measured below with lone frames — push one,
    # wait for its arrival at the sink — which includes batching wait,
    # device time, decode, and delivery.
    dispatch_latency_us = round(pipe["f"].latency_us, 1)
    lat_samples = []
    lat_deadline = time.time() + max(5.0, deadline_ts - time.time() - 10.0)
    for i in range(0 if row_timed_out else 13):
        if time.time() > lat_deadline:
            break
        c0 = done["n"]
        t_send = time.perf_counter()
        src.push(pool[i % len(pool)])
        while done["n"] <= c0 and time.time() < lat_deadline:
            time.sleep(0.001)
        if done["n"] > c0 and i > 0:
            # sample 0 discarded: a lone frame hits the batch-1 bucket's
            # first compile, which is startup cost, not serving latency
            lat_samples.append(time.perf_counter() - t_send)

    src.end_of_stream()
    pipe.wait(timeout=60)
    # labeled telemetry snapshot (registry dump) rides the evidence row:
    # perf claims and live metrics come from ONE source and cannot drift
    telemetry = pipe.telemetry_summary()
    pipe.stop()

    extra = {
        "dispatch_latency_us": dispatch_latency_us,
        "telemetry": telemetry,
    }
    if row_timed_out:
        extra["timed_out"] = True
        extra["frames_done"] = done["n"]
        extra["frames_wanted"] = n_frames
    if lat_samples:
        import numpy as _np

        extra["e2e_latency_ms_p50"] = round(
            float(_np.percentile(lat_samples, 50)) * 1e3, 2
        )
        extra["e2e_latency_ms_max"] = round(max(lat_samples) * 1e3, 2)
        # the floor under every e2e number: a bare device round trip
        # (tiny op, block_until_ready) — the framework-attributable
        # latency is e2e_p50 MINUS this, not e2e_p50 itself.
        try:
            import jax as _jax
            import jax.numpy as _jnp

            x = _jax.device_put(_jnp.ones((8, 8), _jnp.bfloat16))
            f = _jax.jit(lambda a: a @ a)
            _jax.block_until_ready(f(x))  # compile
            rtts = []
            for _ in range(5):
                t_r = time.perf_counter()
                _jax.block_until_ready(f(x))
                rtts.append(time.perf_counter() - t_r)
            extra["device_rtt_ms"] = round(
                float(_np.median(rtts)) * 1e3, 2
            )
        except Exception as e:  # noqa: BLE001 — diagnostic field only
            sys.stderr.write(f"[bench] rtt probe failed: {e}\n")
    if os.environ.get("BENCH_RAW", "0").lower() in ("1", "true", "yes"):
        # bare-model reference in the SAME window/process: the r2 verdict
        # contract is pipeline >= 0.9x raw — measure both or the ratio
        # claim is unfalsifiable
        raw_meta = {}
        raw_fps = measure_raw_fps(
            fn, params, pool, batch,
            n_frames=min(n_frames, 4096),
            host_input=host_frames,
            cap_s=min(20.0, max(10.0, deadline_ts - time.time() - 10.0)),
            out_meta=raw_meta,
        )
        extra["raw_fps"] = round(raw_fps, 1)
        extra["pipeline_vs_raw"] = round(fps / raw_fps, 3)
        if raw_meta.get("timed_out"):
            extra["raw_timed_out"] = True

    # the >=1000 fps/chip north-star target applies to the MobileNet
    # headline row only; the other BASELINE.md rows are "tracked" (no
    # numeric target), so vs_baseline is null for them
    return {
        "metric": metric,
        "value": round(fps, 1),
        "unit": "fps",
        "vs_baseline": (
            round(fps / NORTH_STAR_FPS, 3) if which == "mobilenet" else None
        ),
        **extra,
    }


def trainer_row(dtype: str, deadline_ts: float) -> dict:
    """BASELINE.md row: tensor_trainer MNIST CNN epoch time (tracked)."""
    from nnstreamer_tpu.trainer.jax_trainer import mnist_epoch_benchmark

    secs, acc = mnist_epoch_benchmark(
        dtype=dtype, timeout_s=max(60.0, deadline_ts - time.time() - 30.0)
    )
    return {
        "metric": METRICS["mnist_trainer"][0],
        "value": round(secs, 2),
        "unit": "s",
        "vs_baseline": None,
        "train_accuracy": round(acc, 4),
    }


def main() -> int:
    """Measure the one row BENCH_MODEL names, in THIS process; print it as
    the last line of stdout.  Returns the exit code: non-zero when the row
    has no value."""
    which = os.environ.get("BENCH_MODEL", "mobilenet")
    if which not in METRICS:
        emit({
            "metric": "invalid", "value": None, "unit": None,
            "vs_baseline": None,
            "error": f"unknown BENCH_MODEL {which!r}; "
                     f"expected one of {sorted(METRICS)}",
        })
        return 2
    metric, unit = METRICS[which]
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    n_frames = int(os.environ.get("BENCH_FRAMES", "4096"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    host_frames = os.environ.get("BENCH_HOST", "0").lower() in (
        "1", "true", "yes",
    )
    # BENCH_FUSE -> pipeline layer (read at Pipeline construction)
    os.environ["NNS_FUSE"] = "1" if bench_fuse() else "0"
    meta = {
        "model": which,
        "batch": batch,
        "dtype": dtype,
        "quantize": "int8" if quant_applied(which) else None,
        "dispatch_depth": int(os.environ.get("BENCH_DEPTH", "4")),
        "ingest": (
            "block" if os.environ.get("BENCH_INGEST", "") == "block"
            else "frame"
        ),
        "sink_split": os.environ.get("BENCH_SINK_SPLIT", "1") not in (
            "0", "false"
        ),
        "batch_timeout_ms": int(os.environ.get(
            "BENCH_BATCH_TIMEOUT", BATCH_TIMEOUT_DEFAULT_MS
        )),
        "fuse": 1 if bench_fuse() else 0,
        "ingest_lane": os.environ.get("BENCH_INGEST_LANE", "auto"),
        "input": "host" if host_frames else "device",
        "slots": (int(os.environ.get("BENCH_SLOTS", "4"))
                  if which == "generate" else 0),
        "mesh": bench_mesh(),
        "prefix_cache": (1 if which == "generate" and bench_prefix_cache()
                         else 0),
    }
    # absolute deadline anchored at process start (_T0, module import),
    # so import/build/compile time shrinks later windows
    deadline_ts = _T0 + float(os.environ.get("BENCH_DEADLINE", "420"))
    try:
        if which == "mnist_trainer":
            row = trainer_row(dtype, deadline_ts)
        elif which == "overhead":
            row = overhead_row(deadline_ts)
        elif which == "generate":
            row = generate_row(deadline_ts)
        else:
            row = pipeline_row(
                which, batch, n_frames, dtype, host_frames, deadline_ts
            )
    except Exception as e:  # noqa: BLE001 — the row boundary: report, fail
        import traceback

        traceback.print_exc()
        row = {"metric": metric, "value": None, "unit": unit,
               "vs_baseline": None, "error": f"{type(e).__name__}: {e}"}
    # the device the row ran on, as jax reports it for this process
    import jax

    dev = jax.devices()[0]
    emit({**row, **meta, "platform": dev.platform,
          "device_kind": dev.device_kind,
          "device_count": len(jax.devices())})
    return 0 if row.get("value") is not None else 1


if __name__ == "__main__":
    sys.exit(main())
