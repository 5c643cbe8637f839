"""Fleet telemetry tests: metrics registry + Prometheus exposition,
wire-propagated trace spans (both transports), the flight recorder, and
the fused/unfused parity + schema-lint gates.

The module autouses ``module_leak_check`` (extended in conftest to count
open metrics-exposition servers), so every endpoint opened here must be
closed by ``Pipeline.stop()`` — the acceptance contract."""

import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.core.resilience import FAULTS
from nnstreamer_tpu.core.telemetry import (
    METRICS,
    REGISTRY,
    SPAN_META,
    Counter,
    FlightRecorder,
    Gauge,
    Histogram,
    MetricsRegistry,
    live_server_count,
)
from nnstreamer_tpu.pipeline import parse_pipeline


@pytest.fixture(scope="module", autouse=True)
def _leaks(module_leak_check):
    """Exposition servers/threads must never outlive their pipeline."""
    yield


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# ---------------------------------------------------------------------------
# Registry units
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_instruments_and_render(self):
        reg = MetricsRegistry()
        c = reg.counter("nns.query.delivered", {"pipeline": "t"})
        c.inc()
        c.inc(2)
        assert c.value == 3
        g = reg.gauge("nns.feed.window_occupancy", {"pipeline": "t"})
        g.set(4)
        h = reg.histogram("nns.query.rtt_seconds", {"pipeline": "t"})
        h.observe(0.004)
        h.observe(0.2)
        assert h.count == 2 and abs(h.sum - 0.204) < 1e-9
        text = reg.render_prometheus()
        assert "# TYPE nns_query_delivered counter" in text
        assert 'nns_query_delivered{pipeline="t"} 3' in text
        assert 'nns_feed_window_occupancy{pipeline="t"} 4' in text
        assert "# TYPE nns_query_rtt_seconds histogram" in text
        assert 'nns_query_rtt_seconds_count{pipeline="t"} 2' in text
        # bucket lines are cumulative and carry le=
        assert re.search(
            r'nns_query_rtt_seconds_bucket\{le="\+Inf",pipeline="t"\} 2',
            text)

    def test_unknown_name_refused(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="catalog"):
            reg.counter("nns.made.up_name")
        # the documented escape hatch: auto-mapped health keys
        reg.gauge("nns.health.some_key").set(1)

    def test_same_name_same_instrument(self):
        reg = MetricsRegistry()
        a = reg.counter("nns.query.retried", {"element": "q"})
        b = reg.counter("nns.query.retried", {"element": "q"})
        assert a is b
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("nns.query.retried", {"element": "q"})

    def test_remove_labeled(self):
        reg = MetricsRegistry()
        reg.counter("nns.query.delivered", {"pipeline": "p1", "element": "q"})
        reg.counter("nns.query.delivered", {"pipeline": "p2", "element": "q"})
        assert reg.remove_labeled(pipeline="p1") == 1
        names = {tuple(sorted(s.labels.items())) for s in reg.collect()}
        assert (("element", "q"), ("pipeline", "p2")) in names
        assert all(("pipeline", "p1") not in lb for lb in names)

    def test_default_name_pipelines_do_not_alias(self):
        """Both Pipeline() and parse_pipeline() default to
        name=\"pipeline\": two concurrent defaults must get DISTINCT
        registry labels, and one's stop() must not evict the other's
        instruments or merge its samples (regression: remove_labeled by
        bare name)."""
        a = parse_pipeline("appsrc name=src ! tensor_sink name=out")
        b = parse_pipeline("appsrc name=src ! tensor_sink name=out")
        a.start()
        b.start()
        try:
            assert a.telemetry_label != b.telemetry_label
            a["src"].push(np.float32([1.0]))
            a["src"].end_of_stream()
            a.wait(timeout=10)
            # a's snapshot sees only its own delivery, not b's series
            assert a.metrics_snapshot().get("nns.pipeline.delivered") == 1
            assert b.metrics_snapshot().get("nns.pipeline.delivered") == 0
        finally:
            a.stop()
            b.stop()
        # labels released: a fresh default pipeline gets the bare name
        c = parse_pipeline("appsrc name=src ! tensor_sink name=out")
        try:
            assert c.telemetry_label == "pipeline"
        finally:
            c.stop()

    def test_collector_failure_survives(self):
        reg = MetricsRegistry()

        def bad():
            raise RuntimeError("collector bug")

        reg.register_collector(bad)
        assert reg.collect() == []  # scrape survives, returns what it has
        reg.unregister_collector(bad)

    def test_catalog_kinds_are_sane(self):
        assert all(kind in ("counter", "gauge", "histogram")
                   for kind, _ in METRICS.values())
        # spot-check the names the issue pins
        assert "nns.filter.invoke_latency" in METRICS
        assert "nns.feed.window_occupancy" in METRICS
        assert "nns.query.inflight" in METRICS


# ---------------------------------------------------------------------------
# Pipeline snapshot + Prometheus endpoint under load
# ---------------------------------------------------------------------------
def _parse_prometheus(text: str) -> dict:
    """Minimal exposition-format parser: {metric{labels}: float}.
    Raises on any malformed line — the 'parseable' acceptance check."""
    out = {}
    line_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? "
        r"([-+]?[0-9.eE+-]+|NaN|[+-]Inf)$")
    for line in text.strip().splitlines():
        if not line or line.startswith("#"):
            continue
        m = line_re.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return out


class TestExposition:
    def test_metrics_endpoint_under_load_and_clean_shutdown(self):
        """Acceptance: /metrics serves parseable Prometheus text holding
        filter, feed, query, and lifecycle series while a query server
        is under load; Pipeline.stop() closes the endpoint (the module
        leak check additionally pins the thread + socket)."""
        sid = 9301
        server = parse_pipeline(
            f"tensor_query_serversrc name=ssrc id={sid} port=0 "
            "max-inflight=16 ! "
            "tensor_filter name=f framework=scaler custom=factor:2 "
            "max-batch=4 ! "
            f"tensor_query_serversink id={sid}",
            name="metsrv",
        )
        server.enable_tracing()
        mport = server.serve_metrics(0)
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            f"appsrc name=src ! tensor_query_client name=q port={port} "
            "max-in-flight=8 ! tensor_sink name=out",
            name="metcli",
        )
        client.start()
        servers_open = live_server_count()
        assert servers_open >= 1
        try:
            # load + scrape concurrently: push a stream, scrape mid-flight
            n = 60
            text_mid = None
            for i in range(n):
                client["src"].push(np.float32([i]))
                if i == n // 2:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{mport}/metrics",
                            timeout=5) as r:
                        assert r.headers["Content-Type"].startswith(
                            "text/plain")
                        text_mid = r.read().decode()
            client["src"].end_of_stream()
            client.wait(timeout=30)
            metrics = _parse_prometheus(text_mid)

            def series(prefix):
                return [k for k in metrics if k.startswith(prefix)]

            # filter, feed, query, lifecycle series all present
            assert series("nns_filter_invokes")
            assert series("nns_feed_window_occupancy")
            assert series("nns_query_inflight")
            assert series("nns_query_admitted")
            assert series("nns_lifecycle_state")
            assert series("nns_lifecycle_server_state")
            # tracer-fed per-element series (tracing enabled server-side)
            assert series("nns_element_frames")
            # and the snapshot view agrees with health()
            snap = server.metrics_snapshot()
            admitted = server.health()["ssrc"]["admitted"]
            assert snap.get("nns.query.admitted", element="ssrc") == admitted
            assert snap.get("nns.query.inflight", element="ssrc") is not None
            vals = [float(f.tensors[0][0]) for f in client["out"].frames]
            assert vals == [2.0 * i for i in range(n)]
        finally:
            client.stop()
            server.stop()
        # endpoint down: connection refused, server census back to baseline
        assert live_server_count() == servers_open - 1
        with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
            urllib.request.urlopen(
                f"http://127.0.0.1:{mport}/metrics", timeout=2)

    def test_snapshot_basics_without_tracer(self):
        pipe = parse_pipeline(
            "appsrc name=src ! identity ! tensor_sink name=out",
            name="snapbasic",
        )
        pipe.start()
        try:
            for i in range(7):
                pipe["src"].push(np.float32([i]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=15)
            snap = pipe.metrics_snapshot()
            assert snap.get("nns.pipeline.delivered") == 7
            assert snap.get("nns.sink.rendered", element="out") == 7
            assert snap.get("nns.source.pending", element="src") == 0
            # no tracer: the nns.element dataplane series are absent, the
            # supervision series still exported
            assert snap.get("nns.element.frames", element="out") is None
            assert snap.get("nns.element.dead_letters", element="out") == 0
            flat = pipe.telemetry_summary()
            assert flat["nns.pipeline.delivered"] == 7
        finally:
            pipe.stop()


# ---------------------------------------------------------------------------
# Wire-propagated trace spans (acceptance e2e, both transports)
# ---------------------------------------------------------------------------
class TestWireSpans:
    @pytest.mark.parametrize("ct,sid", [("tcp", 9311), ("grpc", 9312)])
    def test_roundtrip_span_decomposition(self, ct, sid):
        """Acceptance: one tensor_query round trip yields a trace whose
        client-queue + wire + server-queue + device segments sum to the
        measured end-to-end latency within tolerance, with the
        per-segment breakdown visible in client health() and the
        registry."""
        server = parse_pipeline(
            f"tensor_query_serversrc name=ssrc id={sid} port=0 "
            f"connect-type={ct} ! "
            "tensor_filter framework=scaler custom=factor:3 ! "
            f"tensor_query_serversink id={sid}",
            name=f"spansrv{ct}",
        )
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            f"appsrc name=src ! tensor_query_client name=q port={port} "
            f"connect-type={ct} ! tensor_sink name=out",
            name=f"spancli{ct}",
        )
        client.start()
        try:
            # warm the path (dials, jit-less here, but first-RPC costs)
            for i in range(4):
                client["src"].push(np.float32([i]))
            deadline = time.time() + 15
            while len(client["out"].frames) < 4 and time.time() < deadline:
                time.sleep(0.01)
            assert len(client["out"].frames) == 4
            # one measured lone round trip: wall e2e from push to sink
            t_push = time.perf_counter()
            client["src"].push(np.float32([41.0]))
            while len(client["out"].frames) < 5 and time.time() < deadline:
                time.sleep(0.0005)
            wall_e2e = time.perf_counter() - t_push
            ans = client["out"].frames[-1]
            assert float(ans.tensors[0][0]) == 123.0
            span = ans.meta[SPAN_META]
            segments = (
                span["client_queue"] + span["wire"] + span["server_queue"]
                + span["device_dispatch"] + span["device_compute"]
            )
            # additive by construction: segments sum EXACTLY to total
            assert segments == pytest.approx(span["total"], abs=1e-9)
            # and the externally measured e2e upper-bounds it (the wall
            # measurement additionally includes the appsrc->client and
            # client->sink mailbox hops + our 0.5ms poll)
            assert span["total"] <= wall_e2e + 1e-4
            assert span["trace_id"]
            assert span["remote"].endswith(f":{port}")
            # every segment is a real, finite duration
            for key in ("client_queue", "wire", "server_queue",
                        "device_dispatch", "device_compute"):
                assert 0.0 <= span[key] <= span["total"]
            # server actually decomposed (not the legacy wire==rtt path)
            assert span["device_compute"] > 0.0
            # breakdown visible in client health() ...
            remotes = client.health()["q"]["remotes"]
            agg = remotes[span["remote"]]
            assert agg["requests"] == 5
            for key in ("e2e_ms", "rtt_ms", "wire_ms", "server_ms",
                        "client_queue_ms"):
                assert agg[key] is not None and agg[key] >= 0.0
            # ... and in the registry, labeled by remote
            snap = client.metrics_snapshot()
            assert snap.get("nns.query.remote_requests",
                            remote=span["remote"]) == 5
            assert snap.get("nns.query.remote_e2e_ms",
                            remote=span["remote"]) == pytest.approx(
                                agg["e2e_ms"], rel=1e-6)
            # the client-observed rtt histogram recorded every exchange
            assert snap.sum("nns.query.rtt_seconds_count", element="q") == 5
        finally:
            client.stop()
            server.stop()

    def test_trace_local_stamps_never_cross_the_wire(self):
        """The _nns_tl_ prefix (and the tracer's source stamp) are
        host-local: encode strips them; the trace id and the server
        duration dict DO cross."""
        from nnstreamer_tpu.core.buffer import TensorFrame
        from nnstreamer_tpu.core.telemetry import (
            SRV_SPAN_META,
            TL_ENQ_META,
            TL_RX_META,
            TRACE_ID_META,
        )
        from nnstreamer_tpu.core.tracer import META_SRC_TS
        from nnstreamer_tpu.distributed.wire import decode_frame, encode_frame

        f = TensorFrame([np.float32([1.0])], meta={
            TRACE_ID_META: "abc-1",
            TL_ENQ_META: 123.0,
            TL_RX_META: 124.0,
            META_SRC_TS: 125.0,
            SRV_SPAN_META: {"queue": 0.1, "dispatch": 0.0,
                            "compute": 0.2, "total": 0.3},
            "client_id": 7,
        })
        g = decode_frame(encode_frame(f))
        assert g.meta[TRACE_ID_META] == "abc-1"
        assert g.meta["client_id"] == 7
        assert g.meta[SRV_SPAN_META]["total"] == 0.3
        assert TL_ENQ_META not in g.meta
        assert TL_RX_META not in g.meta
        assert META_SRC_TS not in g.meta


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_stall_dump_contains_stuck_span(self, tmp_path):
        """Acceptance: an injected watchdog stall (FaultInjector hang
        site) produces a dump containing the stalled frame's span
        timeline — the hung element shows as an OPEN span with its
        trace id; the pipeline then restarts the element and loses
        nothing."""
        pipe = parse_pipeline(
            "appsrc name=src ! identity name=work stall-timeout=0.3 "
            "stall-policy=restart ! tensor_sink name=out",
            name="frstall",
        )
        pipe.enable_flight_recorder(dump_dir=str(tmp_path))
        # exactly ONE hang (times=1): the watchdog escalation interrupts
        # it cooperatively; the retry then runs clean
        FAULTS.arm("element.work.handle_frame", hang=True, after=2, times=1)
        pipe.start()
        try:
            for i in range(4):
                pipe["src"].push(np.float32([i]))
            deadline = time.time() + 15
            files = []
            while not files and time.time() < deadline:
                files = list(tmp_path.glob("nns_flight_*.json"))
                time.sleep(0.05)
            assert files, "no flight dump on watchdog stall"
            FAULTS.reset()  # release the hang -> StallError -> restart
            pipe["src"].end_of_stream()
            pipe.wait(timeout=15)
            data = json.loads(files[0].read_text())
            assert data["reason"].startswith("watchdog_")
            assert data["source"] == "work"
            stuck = [
                (t["trace_id"], s) for t in data["traces"]
                for s in t["spans"] if s.get("open")
            ]
            assert stuck, "dump lacks the stalled frame's open span"
            tid, s = stuck[0]
            assert s["element"] == "work"
            assert s["stuck_for_ms"] >= 300.0 - 50.0
            assert tid, "stalled frame has no trace id"
            # the stalled frame's earlier history is in the same dump:
            # frames 0/1 completed 'work' spans before the hang
            done = [
                sp for t in data["traces"] for sp in t["spans"]
                if not sp.get("open") and sp["element"] == "work"
            ]
            assert len(done) >= 2
            # zero loss: the restart retried the hung frame
            assert len(pipe["out"].frames) == 4
            assert pipe.health()["work"]["restarts"] == 1
            snap = pipe.metrics_snapshot()
            assert snap.get("nns.element.stalls", element="work") >= 1
        finally:
            FAULTS.reset()
            pipe.stop()

    def test_dead_letter_and_rate_limit(self, tmp_path):
        """Dead-letters dump too, and the recorder rate-limits: a burst
        of incidents produces ONE file inside the interval."""
        pipe = parse_pipeline(
            "appsrc name=src ! identity name=work error-policy=skip ! "
            "tensor_sink name=out",
            name="frskip",
        )
        pipe.enable_flight_recorder(
            dump_dir=str(tmp_path), min_dump_interval_s=60.0)
        FAULTS.arm("element.work.handle_frame",
                   exc=ValueError("poison"), every=2)
        pipe.start()
        try:
            for i in range(8):
                pipe["src"].push(np.float32([i]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=15)
            files = list(tmp_path.glob("nns_flight_*.json"))
            assert len(files) == 1  # 4 dead-letters, one dump (limited)
            rec = pipe.flight_recorder
            assert rec.dumps == 1 and rec.suppressed >= 3
            assert pipe.health()["work"]["dead_letters"] == 4
        finally:
            FAULTS.reset()
            pipe.stop()

    def test_recorder_units(self, tmp_path):
        class F:
            def __init__(self, tid):
                self.meta = {"_nns_trace_id": tid}

        rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                             min_dump_interval_s=0.0)
        rec.begin("a", F("t1"))
        rec.end("a", F("t1"), 1.0, 2.0, 1)
        rec.begin("b", F("t1"))  # never ends: open span
        tl = rec.timelines()
        assert [s["element"] for s in tl["t1"]] == ["a", "b"]
        assert tl["t1"][1]["open"] is True
        path = rec.dump("unit", "test")
        assert path and json.load(open(path))["traces"]


# ---------------------------------------------------------------------------
# Fused/unfused parity: per-element stats and registry counts identical
# ---------------------------------------------------------------------------
class TestFusedParity:
    N = 24

    def _run(self, fuse: bool):
        FAULTS.reset()
        pipe = parse_pipeline(
            "appsrc name=src ! identity name=a error-policy=skip ! "
            "identity name=b ! tensor_sink name=out",
            name="parity",  # SAME name both runs: labels must match too
            fuse=fuse,
        )
        tracer = pipe.enable_tracing()
        # deterministic poison: every 4th supervised call on 'a' fails
        FAULTS.arm("element.a.handle_frame",
                   exc=ValueError("poison"), every=4)
        pipe.start()
        try:
            for i in range(self.N):
                pipe["src"].push(np.float32([i]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=20)
            report = {
                name: {"frames": r["frames"], "calls": r["calls"]}
                for name, r in tracer.report().items()
            }
            counters = {
                key: v
                for key, v in pipe.metrics_snapshot().counters().items()
                # process-global pools accumulate across runs — excluded
                # (everything else is per-pipeline deterministic).  The
                # log2 latency histograms' bucket/sum series hold TIMING
                # (nondeterministic by nature), and queue-wait exists
                # only where mailboxes exist — which fusion elides by
                # design; their deterministic subset (handle-latency
                # _count) stays in and is additionally pinned by
                # test_handle_histogram_counts_identical.
                if not key[0].startswith("nns.pool.")
                and not key[0].endswith(("_bucket", "_sum"))
                and not key[0].startswith("nns.element.queue_wait_seconds")
            }
            health = {
                el: {k: entry[k] for k in (
                    "state", "dead_letters", "deadline_drops", "restarts")}
                for el, entry in pipe.health().items()
            }
            return report, counters, health
        finally:
            FAULTS.reset()
            pipe.stop()

    def test_stats_and_registry_counts_identical(self):
        """The supervision truth-table pipeline (skip policy + periodic
        poison) produces BYTE-IDENTICAL per-element tracer stats and
        registry counter values fused vs unfused."""
        rep_f, cnt_f, health_f = self._run(True)
        rep_u, cnt_u, health_u = self._run(False)
        assert rep_f == rep_u
        assert cnt_f == cnt_u
        assert health_f == health_u
        # and the truth table itself held: every 4th of 24 dead-letters
        assert health_f["a"]["dead_letters"] == 6
        assert dict(cnt_f)[
            ("nns.pipeline.delivered", (("pipeline", "parity"),))
        ] == self.N - 6

    def _run_hists(self, fuse: bool):
        """Handle-latency log2 histograms after the supervision
        truth-table pipeline: {element: (count, bucket_count_sum)}."""
        FAULTS.reset()
        pipe = parse_pipeline(
            "appsrc name=src ! identity name=a error-policy=skip ! "
            "identity name=b ! tensor_sink name=out",
            name="hparity", fuse=fuse,
        )
        tracer = pipe.enable_tracing()
        FAULTS.arm("element.a.handle_frame",
                   exc=ValueError("poison"), every=4)
        pipe.start()
        try:
            for i in range(self.N):
                pipe["src"].push(np.float32([i]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=20)
            out = {}
            for el, mname, h in tracer.latency_histograms():
                if mname != "nns.element.handle_seconds":
                    continue
                out[el] = (h.count, sum(h.state()))
            return out
        finally:
            FAULTS.reset()
            pipe.stop()

    def test_handle_histogram_counts_identical(self):
        """PR-11 satellite (PR-7 registry-parity discipline): on the
        supervision truth-table pipeline, each element's handle-latency
        histogram records BYTE-IDENTICAL observation counts fused vs
        unfused, and the per-bucket counts sum exactly to the total in
        both modes (no observation is lost or double-bucketed by the
        lock-free record path).  Bucket PLACEMENT is timing and is
        deliberately not compared."""
        hf = self._run_hists(True)
        hu = self._run_hists(False)
        assert set(hf) == set(hu) == {"a", "b", "out"}
        assert hf == hu
        for el, (count, bucket_sum) in hf.items():
            assert count == bucket_sum, (
                f"{el}: bucket counts do not sum to the total")
        # the truth table's exact shape: 'a' is called once per frame,
        # poison included (the handler raised INSIDE the call — it still
        # began and ended); b/out see only the 18 survivors
        assert hf["a"][0] == self.N
        assert hf["b"][0] == self.N - 6
        assert hf["out"][0] == self.N - 6


# ---------------------------------------------------------------------------
# Profilers: jax trace-session refcount hygiene + the incident-time
# thread sampler
# ---------------------------------------------------------------------------
class _FakeJaxProfiler:
    """Scripted stand-in for the jax.profiler singleton."""

    def __init__(self, fail_starts=0):
        self.fail_starts = fail_starts
        self.starts = []
        self.stops = 0

    def start_trace(self, d):
        if self.fail_starts > 0:
            self.fail_starts -= 1
            raise RuntimeError("injected start_trace failure")
        self.starts.append(d)

    def stop_trace(self):
        self.stops += 1


@pytest.fixture
def _clean_profiler():
    """Snapshot/restore the profiler module's global session state."""
    from nnstreamer_tpu.core import profiler

    refs, d = profiler._refs, profiler._dir
    yield profiler
    profiler._refs, profiler._dir = refs, d


class TestJaxTraceSession:
    def test_failed_start_leaves_state_fully_reset(self, monkeypatch,
                                                   _clean_profiler):
        """Satellite bugfix pin: a trace_start whose start_trace raises
        returns False with refs==0 and dir==None AND resets the jax
        singleton (stop_trace called best-effort) — so a later
        successful start from ANOTHER element enters the clean refs==0
        path instead of refcounting on top of stale state."""
        import jax

        profiler = _clean_profiler
        profiler._refs, profiler._dir = 0, None
        fake = _FakeJaxProfiler(fail_starts=1)
        monkeypatch.setattr(jax, "profiler", fake)
        assert profiler.trace_start("/tmp/t1") is False
        assert profiler._refs == 0 and profiler._dir is None
        assert fake.stops == 1  # the half-armed singleton was reset
        assert profiler.trace_active() is False
        # a subsequent start (different element, different dir) succeeds
        # through the clean refs==0 path
        assert profiler.trace_start("/tmp/t2") is True
        assert profiler._refs == 1 and profiler._dir == "/tmp/t2"
        assert fake.starts == ["/tmp/t2"]
        assert profiler.trace_active() is True
        # join + full teardown refcounts exactly
        assert profiler.trace_start("/tmp/t2") is True
        assert profiler._refs == 2
        profiler.trace_stop()
        assert profiler._refs == 1 and fake.stops == 1
        profiler.trace_stop()
        assert profiler._refs == 0 and profiler._dir is None
        assert fake.stops == 2

    def test_foreign_active_session_is_not_reset(self, monkeypatch,
                                                 _clean_profiler):
        """A start that fails because the jax singleton is ALREADY
        active (someone else's TensorBoard capture) must NOT be reset —
        the failure-path stop_trace would kill their trace mid-run."""
        import jax

        profiler = _clean_profiler
        profiler._refs, profiler._dir = 0, None

        class Busy(_FakeJaxProfiler):
            def start_trace(self, d):
                raise RuntimeError("profiler session already active")

        fake = Busy()
        monkeypatch.setattr(jax, "profiler", fake)
        assert profiler.trace_start("/tmp/t3") is False
        assert profiler._refs == 0 and profiler._dir is None
        assert fake.stops == 0  # the foreign session survives

    def test_profiler_active_gauge_via_health_collector(self, monkeypatch,
                                                        _clean_profiler):
        """Satellite pin: the filter's trace session surfaces as the
        `profiler_active` health key -> nns.profiler.active gauge via
        the ONE health-collector path (no duplicate series)."""
        import jax

        profiler = _clean_profiler
        profiler._refs, profiler._dir = 0, None
        monkeypatch.setattr(jax, "profiler", _FakeJaxProfiler())
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter name=f framework=scaler "
            "custom=factor:2 trace=1 trace-dir=/tmp/nns_t ! "
            "tensor_sink name=out",
            name="profgauge",
        )
        pipe.start()
        try:
            assert pipe.health()["f"]["profiler_active"] == 1
            snap = pipe.metrics_snapshot()
            assert snap.get("nns.profiler.active", element="f") == 1.0
            samples = [
                s for s in snap.samples if s.name == "nns.profiler.active"
                and s.labels.get("element") == "f"
            ]
            assert len(samples) == 1  # one export path, one series
        finally:
            pipe.stop()
        assert profiler._refs == 0  # stop() released the session


class TestThreadProfiler:
    def test_samples_named_framework_thread(self):
        """A named framework thread parked in a known function shows up
        with that function in its collapsed top stack; ignored-prefix
        threads (Thread-N etc.) do not."""
        import threading
        import time as _time

        from nnstreamer_tpu.core.profiler import profile_threads

        release = threading.Event()

        def distinctive_parked_fn():
            release.wait(10)

        t = threading.Thread(target=distinctive_parked_fn,
                             name="tprof-seg", daemon=True)
        anon = threading.Thread(target=lambda: release.wait(10),
                                daemon=True)  # "Thread-N": ignored
        t.start()
        anon.start()
        try:
            prof = profile_threads(duration_s=0.15, hz=50)
        finally:
            release.set()
            t.join(timeout=5)
            anon.join(timeout=5)
        assert prof["samples"] >= 1
        assert "tprof-seg" in prof["threads"]
        top = prof["threads"]["tprof-seg"]["top_stacks"]
        assert top and top[0]["count"] >= 1
        assert "distinctive_parked_fn" in top[0]["stack"]
        assert not any(n.startswith("Thread-") for n in prof["threads"])

    def test_stall_dump_contains_stalled_threads_stack(self, tmp_path):
        """Acceptance: a watchdog-stall incident dump carries collapsed
        thread stacks NAMING the stalled element's streaming thread,
        with the hang site visible in its top stack — "where did the
        time go" from the dump file alone."""
        pipe = parse_pipeline(
            "appsrc name=src ! identity name=work stall-timeout=0.3 "
            "stall-policy=restart ! tensor_sink name=out",
            name="profstall", fuse=False,  # thread named after 'work'
        )
        pipe.enable_flight_recorder(dump_dir=str(tmp_path))
        FAULTS.arm("element.work.handle_frame", hang=True, after=2, times=1)
        pipe.start()
        try:
            for i in range(4):
                pipe["src"].push(np.float32([i]))
            deadline = time.time() + 15
            files = []
            while not files and time.time() < deadline:
                files = list(tmp_path.glob("nns_flight_*.json"))
                time.sleep(0.05)
            assert files, "no flight dump on watchdog stall"
            FAULTS.reset()  # release the hang -> restart, zero loss
            pipe["src"].end_of_stream()
            pipe.wait(timeout=15)
            data = json.loads(files[0].read_text())
            prof = data["thread_profile"]
            assert prof and prof["samples"] >= 1
            assert "work" in prof["threads"], sorted(prof["threads"])
            stacks = [
                s["stack"]
                for s in prof["threads"]["work"]["top_stacks"]
            ]
            # the hung thread is parked inside the injected fault's
            # cooperative hang (resilience.py) under the supervised
            # handler — its collapsed stack says so
            assert any("resilience.py" in s for s in stacks), stacks
            assert any("pipeline.py" in s for s in stacks), stacks
            assert len(pipe["out"].frames) == 4  # zero loss after restart
        finally:
            FAULTS.reset()
            pipe.stop()
        from nnstreamer_tpu.core.telemetry import REGISTRY

        caps = [
            s for s in REGISTRY.collect()
            if s.name == "nns.profiler.captures"
        ]
        assert caps and caps[0].value >= 1


# ---------------------------------------------------------------------------
# Always-on latency histograms (tentpole 2): snapshot + exposition
# ---------------------------------------------------------------------------
class TestLatencyHistograms:
    def test_log2_histogram_units(self):
        from nnstreamer_tpu.core.telemetry import (
            LOG2_NBUCKETS,
            Log2Histogram,
        )

        h = Log2Histogram()
        assert h.quantile(0.5) is None and h.percentiles_us() == {}
        for v in (2e-6, 2e-6, 2e-6, 1e-3, 1e-3, 0.25, 100.0):
            h.record(v)
        assert h.count == 7
        assert sum(h.state()) == 7
        assert h.sum == pytest.approx(100.252006, rel=1e-6)
        # overflow lands in the +Inf tail, never out of range
        assert h.state()[LOG2_NBUCKETS] == 1
        # quantile estimates respect bucket edges (log2 resolution)
        assert 1e-6 <= h.quantile(0.25) <= 4e-6
        assert 5e-4 <= h.quantile(0.65) <= 2e-3
        p = h.percentiles_us()
        assert p["p50"] <= p["p95"] <= p["p99"]
        # sub-resolution values land in bucket 0, not a crash
        h.record(1e-9)
        assert h.state()[0] >= 1

    def test_quantiles_in_summary_and_prometheus(self):
        """Acceptance: per-element p50/p95/p99 are visible in
        telemetry_summary() and on /metrics (via the registry's
        exposition render) with a tracer armed, window dwell included."""
        from nnstreamer_tpu.core.telemetry import REGISTRY

        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter name=f framework=async-sim "
            "custom=compute_ms:1 max-batch=4 dispatch-depth=4 ! "
            "tensor_sink name=out",
            name="histvis",
        )
        pipe.enable_tracing()
        pipe.start()
        try:
            for i in range(32):
                pipe["src"].push(np.float32([i]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=20)
            flat = pipe.telemetry_summary()
            for key in ("nns.element.handle_p50_us",
                        "nns.element.handle_p95_us",
                        "nns.element.handle_p99_us",
                        "nns.feed.window_dwell_p50_us",
                        "nns.feed.window_dwell_p99_us"):
                assert flat.get(key, 0) > 0, key
            # the compact summary never carries raw bucket series
            assert not any(k.endswith("_bucket") for k in flat)
            snap = pipe.metrics_snapshot()
            assert snap.get("nns.element.handle_p99_us",
                            element="f") > 0
            assert snap.sum("nns.feed.window_dwell_seconds_count",
                            element="f") >= 1
            text = REGISTRY.render_prometheus()
            assert "# TYPE nns_element_handle_seconds histogram" in text
            assert re.search(
                r'nns_element_handle_seconds_bucket\{[^}]*le="\+Inf"', text)
            assert "nns_feed_window_dwell_seconds_count" in text
            assert "nns_element_handle_p99_us" in text
            _parse_prometheus(text)  # parseable end to end
        finally:
            pipe.stop()

    def test_queue_wait_recorded_at_thread_boundaries(self):
        """Unfused (every element owns a mailbox): each consuming
        element records one queue-wait observation per frame; the
        stamps are host-local and never reach the wire."""
        from nnstreamer_tpu.core.telemetry import TL_QPUT_META

        pipe = parse_pipeline(
            "appsrc name=src ! identity name=a ! tensor_sink name=out",
            name="qwait", fuse=False,
        )
        pipe.enable_tracing()
        pipe.start()
        try:
            for i in range(10):
                pipe["src"].push(np.float32([i]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=15)
            snap = pipe.metrics_snapshot()
            for el in ("a", "out"):
                assert snap.sum("nns.element.queue_wait_seconds_count",
                                element=el) == 10, el
                assert snap.get("nns.element.queue_wait_p50_us",
                                element=el) >= 0
            # the dequeue popped the stamp off every delivered frame
            for f in pipe["out"].frames:
                assert TL_QPUT_META not in f.meta
        finally:
            pipe.stop()


# ---------------------------------------------------------------------------
# lint gate: health/metric schema stability (tier-1, like the other two)
# ---------------------------------------------------------------------------
def test_health_schema_lint_clean():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    try:
        import check_health_schema
    finally:
        sys.path.pop(0)
    bad = check_health_schema.scan()
    assert not bad, "health/metric schema problems:\n" + "\n".join(bad)


def test_train_health_keys_map_to_explicit_train_metrics():
    """The one-health-collector-path contract for continuous learning:
    every tensor_trainer / model_validator health key has an EXPLICIT
    ``nns.train.*`` mapping in HEALTH_KEY_METRICS backed by a registered
    metric — none may leak into the generic ``nns.health.*`` fallback
    namespace where dashboards would never find it."""
    from nnstreamer_tpu.core.telemetry import HEALTH_KEY_METRICS
    from nnstreamer_tpu.pipeline.element import make_element

    for factory, name in (("tensor_trainer", "train"),
                          ("model_validator", "gate")):
        el = make_element(factory, name)
        keys = el.health_info().keys()
        assert keys, f"{factory} reports no health keys"
        for key in keys:
            mname = HEALTH_KEY_METRICS.get(key)
            assert mname is not None, (
                f"{factory} health key {key!r} has no explicit metric "
                "mapping (would fall back to nns.health.*)")
            assert mname.startswith("nns.train."), (key, mname)
            assert mname in METRICS, f"{mname} not registered in METRICS"
