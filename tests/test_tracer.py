"""Pipeline tracer: the GstShark-analog proctime/interlatency/framerate/
queuelevel/bitrate measurements (SURVEY §5.1; reference delegates these to
GstShark tracer hooks, ``tools/tracing/README.md``)."""

import numpy as np

from nnstreamer_tpu.pipeline import parse_pipeline


def _run_traced(n_frames=32, detail=False):
    # fuse=False: queue-level tracing samples mailboxes, which only exist
    # at thread boundaries — the unfused dataplane gives every element one
    # (fused chains have no intermediate queues to sample, by design)
    pipe = parse_pipeline(
        "appsrc name=src ! "
        "tensor_transform mode=arithmetic option=add:1.0 ! "
        "tensor_sink name=out max-stored=64",
        name="traced",
        fuse=False,
    )
    tracer = pipe.enable_tracing(detail=detail)
    pipe.start()
    src = pipe["src"]
    for i in range(n_frames):
        src.push(np.full((4, 4), float(i), np.float32))
    src.end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    return tracer, n_frames


def test_tracer_counts_and_latency():
    tracer, n = _run_traced()
    rep = tracer.report()
    # the transform and the sink both processed every frame
    els = {name: r for name, r in rep.items()}
    transform = next(r for name, r in els.items() if "transform" in name)
    sink = els["out"]
    assert transform["frames"] == n
    assert sink["frames"] == n
    # proctime measured
    assert transform["proctime_us_avg"] > 0
    assert transform["proctime_us_p99"] >= transform["proctime_us_p50"]
    # interlatency: frames carried a source stamp through the chain
    assert transform["interlatency_ms_avg"] is not None
    assert sink["interlatency_ms_avg"] >= 0
    # bitrate: 4x4 float32 = 64 bytes per frame flowed
    assert transform["bitrate_mbps"] >= 0
    # queue levels sampled with a real capacity
    assert sink["queue_capacity"] > 0
    # scheduletime: inter-dequeue gap measured after the first call
    assert transform["scheduletime_us_avg"] is not None
    assert transform["scheduletime_us_avg"] > 0
    assert tracer.cpu_usage() >= 0.0


def test_tracer_summary_renders():
    tracer, _ = _run_traced(8)
    lines = tracer.summary_lines()
    assert len(lines) >= 3  # header + 2 elements
    assert "fps" in lines[0] and "inter ms" in lines[0]


def test_chrome_trace_export(tmp_path):
    import json

    tracer, n = _run_traced(16, detail=True)
    path = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(path)
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    # detail mode: one real span per element call, with timestamps
    assert len(spans) >= 2 * n
    assert all(e["dur"] > 0 for e in spans)
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert any("out" == nm for nm in names)
    assert any(e["ph"] == "C" for e in events)  # fps counters


def test_no_tracer_by_default():
    pipe = parse_pipeline(
        "appsrc name=src ! tensor_sink name=out", name="untraced"
    )
    assert pipe.tracer is None
    pipe.start()
    pipe["src"].push(np.zeros((2,), np.float32))
    pipe["src"].end_of_stream()
    pipe.wait(timeout=10)
    pipe.stop()
