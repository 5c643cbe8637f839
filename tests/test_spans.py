"""Spans on the profiler's clock (core/tracer.py ``span``/``record``/
``spans_between``), the sites that open them, and the slot engine's wait
counters.  No wall-clock time is asserted: the engine tests run on a clock
that only the simulated device advances."""

import glob
import threading
import time

import jax
import numpy as np
import pytest

from benchmark import xplane
from nnstreamer_tpu.core import profiler, tracer
from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.slots import SimSlotModel, SlotEngine
from nnstreamer_tpu.pipeline import parse_pipeline


@pytest.fixture
def session(tmp_path):
    """A live profiler session, started the way the benchmark starts its
    own (host events and the device; no Python-call tracing)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    box = {"dir": str(tmp_path), "t0": time.perf_counter(), "live": True}

    def stop():
        if box["live"]:
            box["live"] = False
            jax.profiler.stop_trace()
        return tracer.spans_between(box["t0"], time.perf_counter())

    box["stop"] = stop
    yield box
    stop()


def names(records):
    return [r.name for r in records]


# -- the primitive ----------------------------------------------------------
def test_off_is_the_shared_noop_and_leaves_the_ring_alone():
    before = len(tracer._ring)
    assert tracer.armed() is False
    sp = tracer.span("nns.test.off")
    assert sp is tracer.NO_SPAN and tracer.span("nns.test.other") is sp
    with sp as inside:
        assert inside is sp and inside.live is False
        inside.set(anything=1)
    tracer.note(bucket=8)  # no span open: nothing
    assert len(tracer._ring) == before


def test_the_profiler_module_exports_the_span_api_and_no_annotate():
    assert profiler.span is tracer.span and profiler.record is tracer.record
    assert profiler.spans_between is tracer.spans_between
    assert not hasattr(profiler, "annotate")


def test_a_live_session_arms_spans_and_the_trace_holds_their_names(session):
    assert tracer.armed() is True
    with tracer.span("nns.test.outer", request="r1", k=8):
        with tracer.span("nns.test.inner") as sp:
            assert sp.live
            sp.set(seq=3)
            tracer.note(bucket=4)
    recs = {r.name: r for r in session["stop"]()}
    assert tracer.armed() is False
    assert tracer.span("nns.test.after") is tracer.NO_SPAN
    outer, inner = recs["nns.test.outer"], recs["nns.test.inner"]
    assert outer.attrs == {"k": 8} and outer.parent is None
    assert inner.attrs == {"seq": 3, "bucket": 4}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    path = glob.glob(session["dir"] + "/**/*.xplane.pb", recursive=True)[0]
    host = xplane.read(path)["host"]
    on_the_profilers_clock = {n for evs in host.values() for n, _, _ in evs}
    # the clean names, no attribute leaked into them
    assert {"nns.test.outer", "nns.test.inner"} <= on_the_profilers_clock


def test_every_thread_that_opens_a_span_has_a_line_of_its_own(session):
    """Python leaves its threads with the process's OS name, the profiler
    names a thread's line by it, and ``xplane.read`` keys lines by name:
    without a name of its own per thread all but one line would be lost,
    and a device gap could not be blamed on the pump's phase."""
    go, done = threading.Event(), threading.Barrier(4)

    def work(tag):
        go.wait(10)
        with tracer.span(f"nns.test.on.{tag}"):
            pass
        done.wait(10)                    # all alive at once: distinct ids

    threads = [threading.Thread(target=work, args=(k,), name=f"el{k}-stage")
               for k in range(3)]
    for t in threads:
        t.start()
    go.set()
    done.wait(10)
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    session["stop"]()
    path = glob.glob(session["dir"] + "/**/*.xplane.pb", recursive=True)[0]
    host = xplane.read(path)["host"]
    where = {n: line for line, evs in host.items() for n, _, _ in evs
             if n.startswith("nns.test.on.")}
    assert set(where) == {f"nns.test.on.{k}" for k in range(3)}
    assert len(set(where.values())) == 3
    assert all("-stage-" in line for line in where.values())


def test_parent_and_request_follow_nesting_threads_and_records(session):
    def other_thread():
        with tracer.span("nns.test.elsewhere"):
            pass

    with tracer.span("nns.test.a", request=41):
        with tracer.span("nns.test.b"):
            with tracer.span("nns.test.c", request=42):
                pass
        t = threading.Thread(target=other_thread, name="spans-other")
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        tracer.record("nns.test.wait", 1.0, 2.0, request=43, why="queue")
    recs = {r.name: r for r in session["stop"]()
            if r.name.startswith("nns.test.")}
    recs["nns.test.wait"] = next(
        r for r in tracer._ring if r.name == "nns.test.wait")
    assert recs["nns.test.a"].parent is None
    assert recs["nns.test.b"].parent == "nns.test.a"
    assert recs["nns.test.c"].parent == "nns.test.b"
    # a span without a request id inherits its parent's
    assert [recs[f"nns.test.{k}"].request for k in "abc"] == [41, 41, 42]
    # another thread has its own stack: no parent, no request
    other = recs["nns.test.elsewhere"]
    assert other.parent is None and other.request is None
    assert other.thread == "spans-other"
    # an interval that crossed threads belongs to none
    wait = recs["nns.test.wait"]
    assert (wait.thread, wait.parent, wait.request) == (None, None, 43)
    assert wait.attrs == {"why": "queue"} and (wait.t0, wait.t1) == (1.0, 2.0)


def test_spans_between_keeps_what_ended_inside_the_interval():
    tracer.record("nns.test.iv", 10.0, 20.0)
    tracer.record("nns.test.iv", 15.0, 30.0)
    got = [r for r in tracer.spans_between(12.0, 25.0)
           if r.name == "nns.test.iv"]
    assert [(r.t0, r.t1) for r in got] == [(10.0, 20.0)]


def test_a_compile_inside_a_span_is_recorded_with_its_cause(session):
    with tracer.span("nns.test.caller"):
        jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    compiles = [r for r in session["stop"]() if r.name == "nns.compile"]
    assert compiles and {r.parent for r in compiles} == {"nns.test.caller"}
    assert all(r.thread == threading.current_thread().name for r in compiles)


# -- the stream path's sites --------------------------------------------------
def _stream_pipeline(extra=""):
    return parse_pipeline(
        "appsrc name=src ! tensor_filter name=f framework=jax-xla model=zoo "
        "custom=arch:vit,size:32,patch:16,d_model:32,heads:2,layers:1,"
        f"d_ff:64,classes:10,dtype:float32,seed:1 max-batch=8 {extra}! "
        "tensor_decoder name=d mode=image_labeling ! "
        "tensor_sink name=out max-stored=1")


def _push(pipe, n):
    for i in range(n):
        pipe["src"].push(np.full((32, 32, 3), i % 251, np.uint8))
    pipe["src"].end_of_stream()
    pipe.wait(timeout=120)


@pytest.mark.parametrize("fused", [True, False])
def test_a_traced_stream_yields_one_invoke_span_per_invoke(session, fused):
    # fused: the batch leaves device-resident and the decoder brings it to
    # the host; unfused it goes through the window (park, reap, emit)
    pipe = _stream_pipeline("" if fused else "dispatch-depth=2 ")
    if not fused:
        pipe["d"].set_property("device-fused", "never")
    pipe.start()
    try:
        _push(pipe, 37)
    finally:
        pipe.stop()
    recs = session["stop"]()
    invokes = [r for r in recs if r.name == "nns.filter.invoke"]
    assert len(invokes) == pipe["f"]._invokes > 0
    assert sum(r.attrs["frames"] for r in invokes) == 37
    assert all(1 <= r.attrs["frames"] <= r.attrs["bucket"] for r in invokes)
    batches = [r for r in recs if r.name == "nns.filter.batch"]
    seqs = [r.attrs["seq"] for r in batches]
    assert seqs == sorted(set(seqs))               # one number per batch
    by_seq = {r.attrs["seq"]: r for r in invokes if "seq" in r.attrs}
    for b in batches:                              # invoke nests in its batch
        inv = by_seq[b.attrs["seq"]]
        assert inv.parent == "nns.filter.batch" and b.t0 <= inv.t0 <= b.t1
    got = set(names(recs))
    assert {"nns.appsrc.push", "nns.feed.stage", "nns.sink.render",
            "nns.decoder.labels"} <= got
    staged = {r.attrs["seq"] for r in recs if r.name == "nns.feed.stage"}
    assert staged and staged <= set(seqs)
    assert all(r.attrs["bytes"] == r.attrs["frames"] * 32 * 32 * 3
               for r in recs if r.name == "nns.feed.stage")
    if not fused:
        assert {"nns.feed.reap", "nns.filter.emit"} <= got
        assert {r.attrs["seq"] for r in recs
                if r.name == "nns.filter.emit"} <= set(seqs)
    else:
        down = [r for r in recs if r.name == "nns.decoder.batch"]
        assert down and {r.attrs["seq"] for r in down} <= set(seqs)
        assert "nns.batch.materialize" in got
    # every frame got its request id at the source and kept it to the sink
    pushed = {r.request for r in recs if r.name == "nns.appsrc.push"}
    rendered = {r.request for r in recs if r.name == "nns.sink.render"}
    assert len(pushed) == 37 and None not in pushed and rendered == pushed


def test_an_untraced_stream_records_nothing_and_mints_no_ids():
    before = len(tracer._ring)
    pipe = _stream_pipeline()
    pipe.start()
    try:
        _push(pipe, 9)
        assert "_nns_batch_seq" not in pipe["out"].frames[-1].meta
    finally:
        pipe.stop()
    assert len(tracer._ring) == before


def test_the_filters_own_trace_prop_arms_the_spans(tmp_path):
    t0 = time.perf_counter()
    pipe = _stream_pipeline(f"trace=1 trace-dir={tmp_path} ")
    pipe.start()
    try:
        _push(pipe, 9)
    finally:
        pipe.stop()
    assert tracer.armed() is False
    got = set(names(tracer.spans_between(t0, time.perf_counter())))
    assert {"nns.filter.batch", "nns.filter.invoke", "nns.feed.stage"} <= got


def test_detail_tracing_renders_from_the_one_ring(tmp_path):
    import json

    pipe = parse_pipeline(
        "appsrc name=src ! tensor_transform mode=arithmetic option=add:1.0 ! "
        "tensor_sink name=out", fuse=False)
    tr = pipe.enable_tracing(detail=True)
    assert not hasattr(tr, "_spans")
    pipe.start()
    for i in range(5):
        pipe["src"].push(np.zeros((2,), np.float32))
    pipe["src"].end_of_stream()
    pipe.wait(timeout=30)
    pipe.stop()
    calls = [r for r in tracer.spans_between(tr.t_started, time.perf_counter())
             if r.name == "out"]
    assert len(calls) == 5 and all(r.attrs == {"frames": 1} for r in calls)
    path = str(tmp_path / "t.json")
    tr.export_chrome_trace(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert sum(1 for e in events if e["ph"] == "X" and e["name"] == "out") == 5


# -- the slot engine ------------------------------------------------------------
class DeviceClock:
    """Time that only the simulated device's steps advance."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def step(self, dt):
        self.t += dt


WAITS = ("gen_admit_wait_s", "gen_lane_wait_s", "gen_pump_host_s",
         "gen_first_tokens")


def _engine(slots, clock=None, **kw):
    sleep = clock.step if clock else time.sleep
    model = SimSlotModel(slots, vocab=97, step_base_ms=0.2,
                         step_per_slot_ms=0.01, prefill_ms_per_token=0.01,
                         sleep=sleep)
    return SlotEngine(model, None, max_seq=1 << 30, chunk=4, name="spans",
                      **({"clock": clock.now} if clock else {}), **kw), model


def _run(eng, prompts, max_new=9, watch=None):
    for p in prompts:
        eng.submit(TensorFrame([p]), p, max_new=max_new, chunk=4)
    eng.start()
    done = 0
    deadline = time.monotonic() + 60
    while done < len(prompts) and time.monotonic() < deadline:
        done += sum(1 for _, f in eng.pop_ready() if f.meta["final"])
        if watch is not None:
            watch(eng.snapshot())
        eng.wait_progress(0.02)
    assert done == len(prompts)
    return eng.snapshot()


def test_one_caller_waits_for_nobody(rng):
    clock = DeviceClock()
    eng, _ = _engine(1, clock)
    try:
        snap = _run(eng, [rng.integers(0, 97, (1, 70)).astype(np.int32)])
    finally:
        eng.stop()
    assert snap["gen_first_tokens"] == snap["gen_joins"] == 1
    # everything between its join and its first token was its own prefill,
    # and the pump did nothing a device step does not account for
    for key in ("gen_admit_wait_s", "gen_lane_wait_s", "gen_pump_host_s"):
        assert snap[key] == pytest.approx(0.0, abs=1e-9), key


def test_two_callers_wait_for_each_other_and_the_lane_counts_it(rng):
    clock = DeviceClock()
    eng, model = _engine(2, clock, prefill_chunk=32, prefill_priority=1)
    prompts = [rng.integers(0, 97, (1, n)).astype(np.int32) for n in (8, 90)]
    try:
        snap = _run(eng, prompts)
    finally:
        eng.stop()
    assert snap["gen_first_tokens"] == snap["gen_joins"] == 2
    assert snap["gen_admit_wait_s"] == pytest.approx(0.0, abs=1e-9)
    # the long prompt's three chunks took turns with the short one's
    # prefill and decode scans: that is lane wait, and it cannot exceed
    # what the device did in all
    assert 0.0 < snap["gen_lane_wait_s"] <= model.busy_s


def test_a_full_engine_makes_the_next_request_wait_for_admission(rng):
    clock = DeviceClock()
    eng, model = _engine(1, clock)
    prompts = [rng.integers(0, 97, (1, 6)).astype(np.int32) for _ in range(2)]
    try:
        snap = _run(eng, prompts)
    finally:
        eng.stop()
    assert snap["gen_first_tokens"] == snap["gen_joins"] == 2
    # the second request sat in the queue for the first one's whole life
    assert 0.0 < snap["gen_admit_wait_s"] <= model.busy_s
    assert snap["gen_lane_wait_s"] == pytest.approx(0.0, abs=1e-9)


def test_the_wait_counters_never_fall_and_reach_the_snapshot(rng):
    eng, _ = _engine(2)
    seen = []
    prompts = [rng.integers(0, 97, (1, 5 + 9 * i)).astype(np.int32)
               for i in range(5)]
    try:
        snap = _run(eng, prompts, watch=seen.append)
    finally:
        eng.stop()
    assert snap["gen_first_tokens"] == snap["gen_joins"] == 5
    for key in WAITS:
        values = [s[key] for s in seen]
        assert values == sorted(values) and values[-1] >= 0, key
    assert snap["gen_pump_host_s"] > 0 and snap["gen_admit_wait_s"] > 0


def test_a_resized_engine_keeps_the_wait_ledger(rng):
    eng, _ = _engine(1)
    try:
        _run(eng, [rng.integers(0, 97, (1, 6)).astype(np.int32)] * 2)
    finally:
        eng.stop()
    old = eng.snapshot()                 # the pump has stopped counting
    new, _ = _engine(2)
    new.adopt_ledger(eng)
    assert {k: new.snapshot()[k] for k in WAITS} == {k: old[k] for k in WAITS}


def test_a_traced_engine_names_every_phase_of_the_pump(session, rng):
    eng, _ = _engine(2)
    prompts = [rng.integers(0, 97, (1, n)).astype(np.int32) for n in (5, 40)]
    try:
        snap = _run(eng, prompts)
        time.sleep(0.12)                 # idle turns: the pump waits
    finally:
        eng.stop()
    recs = session["stop"]()
    got = set(names(recs))
    assert {"nns.slots.turn", "nns.slots.admit", "nns.slots.wait_request",
            "nns.slots.reset", "nns.slots.prefill", "nns.slots.decode",
            "nns.slots.decode.dispatch", "nns.slots.decode.sync",
            "nns.slots.emit", "nns.gen.admit_wait", "nns.gen.lane_wait"} <= got
    parents = {r.name: r.parent for r in recs if r.name.startswith("nns.slots")}
    assert parents["nns.slots.turn"] is None
    assert parents["nns.slots.decode.sync"] == "nns.slots.decode"
    assert parents["nns.slots.wait_request"] == "nns.slots.admit"
    assert {parents[k] for k in ("nns.slots.admit", "nns.slots.prefill",
                                 "nns.slots.decode", "nns.slots.emit")} == {
                                     "nns.slots.turn"}
    # the spans of one request share its stream id
    sids = {r.request for r in recs if r.name == "nns.gen.lane_wait"}
    assert len(sids) == snap["gen_first_tokens"] == 2
    for name in ("nns.gen.admit_wait", "nns.slots.reset", "nns.slots.prefill"):
        assert {r.request for r in recs if r.name == name} == sids
    chunks = [r.attrs for r in recs if r.name == "nns.slots.prefill"]
    assert sorted(c["n"] for c in chunks) == [5, 8, 32]
    assert len([r for r in recs if r.name == "nns.slots.decode"]) == (
        snap["gen_decode_steps"])
