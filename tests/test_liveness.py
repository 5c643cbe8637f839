"""Liveness layer: stall watchdog (fake clock), deadline QoS truth
table, overload admission control, BUSY client backpressure, latency
fault injection, and the chaos acceptance runs.

All tier-1 fast: fake clocks for the watchdog/deadline units, real
timeouts capped at fractions of a second for the e2e chaos runs.
"""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.liveness import (
    DEADLINE_META,
    AdmissionController,
    ServerBusyError,
    StallError,
    Watchdog,
    deadline_remaining,
    is_expired,
    stamp_deadline,
)
from nnstreamer_tpu.core.resilience import (
    FAULTS,
    is_remote_application_error,
    is_transient,
)
from nnstreamer_tpu.elements.basic import AppSrc, TensorSink
from nnstreamer_tpu.pipeline import parse_pipeline
from nnstreamer_tpu.pipeline.element import TransformElement
from nnstreamer_tpu.pipeline.pipeline import Pipeline


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def frame(v=0.0, pts=None):
    return TensorFrame([np.float32([v])], pts=pts)


# ---------------------------------------------------------------------------
# deadline helpers (fake clock) — the drop-vs-deliver truth table
# ---------------------------------------------------------------------------
class TestDeadlineTruthTable:
    def test_wall_anchored_stamp_and_remaining(self):
        clk = FakeClock(100.0)
        f = stamp_deadline(frame(), 0.5, clock=clk)
        assert f.meta[DEADLINE_META] == 100.5
        assert deadline_remaining(f, clock=clk) == 0.5
        clk.t = 100.4
        assert deadline_remaining(f, clock=clk) == pytest.approx(0.1)

    def test_pts_anchored_stamp(self):
        clk = FakeClock(100.0)
        f = stamp_deadline(frame(pts=2.0), 0.5, clock=clk, anchor=90.0)
        assert f.meta[DEADLINE_META] == 92.5  # anchor + pts + budget

    def test_no_deadline_never_expires(self):
        f = frame()
        assert deadline_remaining(f) is None
        assert not is_expired(f, now=1e12)

    def test_boundary_drop_vs_deliver(self):
        # the pinned boundary contract: delivered strictly BEFORE the
        # deadline; dropped from the instant now >= deadline (zero
        # remaining budget cannot pay for any downstream work)
        clk = FakeClock(0.0)
        f = stamp_deadline(frame(), 1.0, clock=clk)
        assert not is_expired(f, now=0.999999)   # deliver
        assert is_expired(f, now=1.0)            # drop AT the boundary
        assert is_expired(f, now=1.5)            # drop past it

    def test_scheduler_drops_expired_with_accounting(self):
        # a frame whose budget died while queued is dropped before the
        # element runs, counted exactly, and warned on the bus
        pipe = Pipeline("dl")
        src, sink = AppSrc("src"), TensorSink("out")
        pipe.chain(src, sink)
        warnings = []
        pipe.add_bus_watcher(
            lambda m: warnings.append(m) if m.kind == "warning" else None)
        pipe.start()
        expired = stamp_deadline(frame(1.0), -0.1)   # already dead
        alive = stamp_deadline(frame(2.0), 60.0)
        src.push(expired)
        src.push(alive)
        src.push(frame(3.0))                          # no deadline
        src.end_of_stream()
        pipe.wait(timeout=20)
        vals = [float(f.tensors[0][0]) for f in sink.frames]
        assert vals == [2.0, 3.0]
        assert pipe.health()["out"]["deadline_drops"] == 1
        assert [m for m in warnings if m.data.get("qos") == "deadline"]
        pipe.stop()

    def test_late_policy_deliver_processes_expired(self):
        pipe = Pipeline("dl2")
        src, sink = AppSrc("src"), TensorSink("out")
        sink.set_property("late-policy", "deliver")
        pipe.chain(src, sink)
        pipe.start()
        src.push(stamp_deadline(frame(1.0), -0.1))
        src.end_of_stream()
        pipe.wait(timeout=20)
        assert len(sink.frames) == 1
        assert pipe.health()["out"]["deadline_drops"] == 0
        pipe.stop()

    def test_source_deadline_s_stamps_frames(self):
        pipe = parse_pipeline(
            "appsrc name=src deadline-s=60 ! tensor_sink name=out")
        pipe.start()
        pipe["src"].push(np.float32([1]))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=20)
        out = pipe["out"].frames[0]
        rem = deadline_remaining(out)
        assert rem is not None and 0 < rem <= 60
        pipe.stop()


# ---------------------------------------------------------------------------
# tensor_rate QoS feedback
# ---------------------------------------------------------------------------
class TestTensorRateQos:
    def test_note_qos_sheds_up_to_late_pts(self):
        from nnstreamer_tpu.elements.flow import TensorRate

        r = TensorRate("r")
        r.start()
        r.note_qos(pts=0.5, lateness=0.25)  # shed everything <= 0.75
        assert r.transform(frame(1.0, pts=0.6)) is None
        assert r.transform(frame(2.0, pts=0.75)) is None
        out = r.transform(frame(3.0, pts=0.76))
        assert out is not None
        assert r.qos_dropped == 2 and r.dropped == 2
        assert r.get_property("qos-dropped") == 2

    def test_qos_false_ignores_feedback(self):
        from nnstreamer_tpu.elements.flow import TensorRate

        r = TensorRate("r")
        r.set_property("qos", False)
        r.start()
        r.note_qos(pts=0.5, lateness=0.25)
        assert r.transform(frame(1.0, pts=0.6)) is not None
        assert r.qos_dropped == 0

    def test_pipeline_routes_deadline_miss_to_upstream_rate(self):
        # a frame that expires DOWNSTREAM of tensor_rate (between the
        # slow element and the sink) must feed back to the rate sitting
        # upstream (≙ GStreamer QoS events travelling upstream)
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_rate name=rate ! "
            "identity sleep=0.1 ! tensor_sink name=out")
        pipe.start()
        f = stamp_deadline(frame(1.0, pts=0.0), 0.05)  # dies mid-pipeline
        pipe["src"].push(f)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=20)
        assert pipe.health()["out"]["deadline_drops"] == 1
        # the miss reached the throttle: it now sheds around pts 0
        assert pipe["rate"]._qos_until > 0.0
        pipe.stop()


# ---------------------------------------------------------------------------
# FaultInjector delay= / hang=
# ---------------------------------------------------------------------------
class TestLatencyFaults:
    def test_delay_fault_injects_latency_then_proceeds(self):
        FAULTS.arm("t.delay", delay=0.08, times=1)
        t0 = time.monotonic()
        FAULTS.check("t.delay")  # must NOT raise
        assert time.monotonic() - t0 >= 0.07
        FAULTS.check("t.delay")  # times=1: the second call fires nothing
        assert FAULTS.stats("t.delay") == {"calls": 2, "fired": 1}

    def test_hang_fault_interrupted_raises_stall(self):
        FAULTS.arm("t.hang", hang=True, times=1)
        flag = threading.Event()
        t = threading.Timer(0.05, flag.set)
        t.start()
        t0 = time.monotonic()
        with pytest.raises(StallError):
            FAULTS.check("t.hang", interrupt=flag.is_set)
        assert time.monotonic() - t0 >= 0.04
        t.cancel()

    def test_reset_releases_a_hanging_check(self):
        FAULTS.arm("t.hang2", hang=True)
        errs = []

        def hung():
            try:
                FAULTS.check("t.hang2")
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        th = threading.Thread(target=hung, daemon=True)
        th.start()
        time.sleep(0.05)
        assert th.is_alive()  # wedged, as designed
        FAULTS.reset()        # teardown valve
        th.join(timeout=2)
        assert not th.is_alive()
        assert len(errs) == 1 and isinstance(errs[0], StallError)

    def test_stall_error_is_transient(self):
        assert is_transient(StallError("x"))  # restart can cure a stall


# ---------------------------------------------------------------------------
# Watchdog (fake clock)
# ---------------------------------------------------------------------------
class TestWatchdogUnit:
    def test_overrun_flagged_once_per_episode(self):
        clk = FakeClock()
        wd = Watchdog(clock=clk)
        events = []
        w = wd.register("f", frame_deadline=1.0,
                        on_event=lambda w, k, e: events.append((k, e)))
        wd.begin(w)
        clk.t = 0.5
        assert wd.check() == []          # inside the budget
        clk.t = 1.2
        assert wd.check() == [("f", "overrun", pytest.approx(1.2))]
        assert wd.check() == []          # same episode: no re-flag
        wd.done(w)
        assert w.overruns == 1 and w.frames_done == 1
        wd.begin(w)                      # new episode
        clk.t = 2.5
        assert len(wd.check()) == 1
        assert events and events[0][0] == "overrun"

    def test_stall_needs_queued_input_and_no_progress(self):
        clk = FakeClock()
        wd = Watchdog(clock=clk)
        depth = [0]
        w = wd.register("f", stall_timeout=2.0, qsize=lambda: depth[0])
        clk.t = 3.0
        assert wd.check() == []          # idle + empty queue: healthy
        depth[0] = 4
        assert wd.check() == [("f", "stall", pytest.approx(3.0))]
        assert w.stalls == 1
        clk.t = 4.0
        assert wd.check() == []          # re-flag only every stall_timeout
        clk.t = 5.0
        assert len(wd.check()) == 1
        wd.begin(w)
        wd.done(w)                       # progress resets the clock
        clk.t = 6.0
        assert wd.check() == []

    def test_stall_timeout_alone_detects_in_call_hang(self):
        # an element hung INSIDE handle_frame must be detectable with
        # only stall-timeout armed (frame-deadline is the per-call
        # refinement, not a prerequisite) — the in-flight call counts
        # as pending work even with an empty mailbox
        clk = FakeClock()
        wd = Watchdog(clock=clk)
        w = wd.register("f", stall_timeout=1.0, qsize=lambda: 0)
        wd.begin(w)
        clk.t = 0.5
        assert wd.check() == []
        clk.t = 1.5
        assert wd.check() == [("f", "stall", pytest.approx(1.5))]
        assert w.stalls == 1

    def test_overrun_wins_the_tie_over_stall(self):
        # both armed, hung in-call: the first sweep reports the overrun;
        # the stall only fires on LATER sweeps (once per stall_timeout)
        clk = FakeClock()
        wd = Watchdog(clock=clk)
        w = wd.register("f", stall_timeout=1.0, frame_deadline=1.0,
                        qsize=lambda: 0)
        wd.begin(w)
        clk.t = 1.5
        assert wd.check() == [("f", "overrun", pytest.approx(1.5))]
        clk.t = 2.5
        assert wd.check() == [("f", "stall", pytest.approx(2.5))]

    def test_policy_validated(self):
        wd = Watchdog()
        with pytest.raises(ValueError):
            wd.register("f", policy="reboot")

    def test_min_interval_quarter_of_tightest_bound(self):
        wd = Watchdog()
        assert wd.min_interval() == 0.5  # nothing armed
        wd.register("a", frame_deadline=0.4)
        wd.register("b", stall_timeout=2.0)
        assert wd.min_interval() == pytest.approx(0.1)

    def test_snapshot(self):
        wd = Watchdog(clock=FakeClock())
        w = wd.register("f", frame_deadline=1.0)
        wd.begin(w)
        snap = wd.snapshot()["f"]
        assert snap["busy"] and snap["frames_done"] == 0


# ---------------------------------------------------------------------------
# AdmissionController
# ---------------------------------------------------------------------------
class TestAdmissionController:
    def test_high_watermark_sheds(self):
        a = AdmissionController(high=2, low=1)
        assert a.try_admit() and a.try_admit()
        assert not a.try_admit()           # at high: shed
        snap = a.snapshot()
        assert snap["shed"] == 1 and snap["admitted"] == 2

    def test_hysteresis_holds_until_low_watermark(self):
        a = AdmissionController(high=4, low=2)
        for _ in range(4):
            assert a.try_admit()
        assert not a.try_admit()           # shedding begins
        a.release()                        # inflight 3 — still > low
        assert not a.try_admit()
        a.release()                        # inflight 2 == low: band clears
        assert a.try_admit()

    def test_unlimited_when_high_zero(self):
        a = AdmissionController(0)
        for _ in range(1000):
            assert a.try_admit()
        assert a.snapshot()["shed"] == 0

    def test_low_must_be_below_high(self):
        with pytest.raises(ValueError):
            AdmissionController(high=4, low=4)

    def test_negative_low_rejected(self):
        # a negative low could never clear the shedding band — the first
        # overload would brick the server into BUSY forever
        with pytest.raises(ValueError):
            AdmissionController(high=2, low=-1)

    def test_default_low_is_half(self):
        a = AdmissionController(high=8)
        assert a.low == 4

    def test_high_of_one_is_legal(self):
        # low defaults to 0: drain fully before re-admitting
        a = AdmissionController(high=1)
        assert a.low == 0
        assert a.try_admit() and not a.try_admit()
        a.release()
        assert a.try_admit()

    def test_explicit_low_of_zero_honored(self):
        # an explicit 0 means drain FULLY — it must not silently coerce
        # to the high//2 default
        a = AdmissionController(high=4, low=0)
        assert a.low == 0
        for _ in range(4):
            assert a.try_admit()
        assert not a.try_admit()
        for _ in range(3):
            a.release()
            assert not a.try_admit()  # still draining (inflight > 0)
        a.release()
        assert a.try_admit()

    def test_busy_error_is_backpressure_not_ill_health(self):
        e = ServerBusyError(retry_after=0.2)
        assert is_remote_application_error(e)  # never trips a breaker
        assert is_transient(e)                 # retry may succeed
        assert e.retry_after == 0.2


def test_tcp_pipeline_timeout_is_a_health_signal():
    # transport parity: a server pipeline that produces no answer in
    # time must surface as TimeoutError on raw TCP (≙ gRPC
    # DEADLINE_EXCEEDED) — NOT as a RemoteApplicationError, which would
    # immunize a wedged server against breakers/cooldowns
    from nnstreamer_tpu.distributed.tcp_query import (
        TcpQueryConnection,
        TcpQueryServer,
    )

    class StuckCore:
        def check_caps(self, caps):
            return caps

        def process(self, frames, timeout):
            raise TimeoutError("server pipeline produced no answer in time")

    srv = TcpQueryServer(StuckCore(), port=0)
    srv.start()
    conn = TcpQueryConnection("localhost", srv.port, timeout=5.0)
    try:
        with pytest.raises(TimeoutError) as ei:
            conn.invoke(frame())
        assert not is_remote_application_error(ei.value)
        assert is_transient(ei.value)  # retries/failover still apply
    finally:
        conn.close()
        srv.stop()


# ---------------------------------------------------------------------------
# BUSY-reply client behavior (unit: fake connection)
# ---------------------------------------------------------------------------
class TestBusyClient:
    def make_client(self, busy_retries=3, breaker_threshold=2):
        from nnstreamer_tpu.elements.query import TensorQueryClient, _PoolState

        q = TensorQueryClient("q")
        q.set_property("busy-retries", busy_retries)
        q.set_property("breaker-threshold", breaker_threshold)
        q.set_property("retries", 0)
        q.set_property("retry-backoff", 0.0)
        return q, _PoolState

    def test_busy_retried_on_own_budget_without_breaker_trip(self):
        q, _PoolState = self.make_client(busy_retries=3)

        class BusyTwice:
            addr = "fake:1"
            calls = 0

            def invoke(self, frame, timeout):
                type(self).calls += 1
                if type(self).calls <= 2:
                    raise ServerBusyError(retry_after=0.0)
                return frame

        q._pstate = _PoolState((BusyTwice(),), (("fake", 1),), 0)
        q._stopped = False
        f = frame(7.0)
        # retries=0 (single failover attempt) — yet BUSY gets its own
        # paced budget and the request ultimately succeeds
        assert q._invoke_failover(f, 0) is f
        assert BusyTwice.calls == 3
        info = q.health_info()
        assert info["busy_replies"] == 2
        snap = info["breakers"]["fake:1"]
        assert snap["state"] == "closed" and snap["trips"] == 0

    def test_busy_budget_exhausted_surfaces_error(self):
        q, _PoolState = self.make_client(busy_retries=1)

        class AlwaysBusy:
            addr = "fake:1"

            def invoke(self, frame, timeout):
                raise ServerBusyError(retry_after=0.0)

        q._pstate = _PoolState((AlwaysBusy(),), (("fake", 1),), 0)
        q._stopped = False
        with pytest.raises(ServerBusyError):
            q._invoke_failover(frame(), 0)
        snap = q.health_info()["breakers"]["fake:1"]
        assert snap["state"] == "closed" and snap["trips"] == 0

    def test_expired_request_counts_once_even_in_topic_mode_shape(self):
        # deadline expiry is TERMINAL: no rediscovery, no recursive
        # re-invoke, exactly one deadline_expired count per request
        q, _PoolState = self.make_client()
        q.set_property("retries", 3)  # would make resends "safe"

        class Slow:
            addr = "fake:1"

            def invoke(self, frame, timeout):
                raise ConnectionResetError("down")

        q._pstate = _PoolState((Slow(),), (("fake", 1),), 0)
        q._stopped = False
        f = stamp_deadline(frame(), -1.0)
        with pytest.raises(TimeoutError):
            q._invoke_failover(f, 0)
        assert q.health_info()["deadline_expired"] == 1

    def test_expired_request_stops_retrying(self):
        q, _PoolState = self.make_client()

        class NeverReached:
            addr = "fake:1"
            calls = 0

            def invoke(self, frame, timeout):
                type(self).calls += 1
                return frame

        q._pstate = _PoolState((NeverReached(),), (("fake", 1),), 0)
        q._stopped = False
        f = stamp_deadline(frame(), -1.0)  # budget already dead
        with pytest.raises(TimeoutError):
            q._invoke_failover(f, 0)
        assert NeverReached.calls == 0  # never even sent
        assert q.health_info()["deadline_expired"] == 1

    def test_request_timeout_propagates_remaining_budget(self):
        q, _ = self.make_client()
        f = stamp_deadline(frame(), 0.5)
        t, expired = q._request_timeout(f, 10.0)
        assert not expired and 0 < t <= 0.5
        t2, _ = q._request_timeout(frame(), 10.0)
        assert t2 == 10.0  # no deadline: configured timeout

    def test_answers_inherit_request_deadline(self):
        from nnstreamer_tpu.elements.query import TensorQueryClient

        req = stamp_deadline(frame(1.0), 9.0)
        ans = frame(2.0)
        TensorQueryClient._carry_deadline(req, ans)
        assert ans.meta[DEADLINE_META] == req.meta[DEADLINE_META]
        reqs = [stamp_deadline(frame(), 1.0), stamp_deadline(frame(), 2.0)]
        answers = [frame(), frame()]
        TensorQueryClient._carry_deadline(reqs, answers)
        assert [a.meta[DEADLINE_META] for a in answers] == [
            r.meta[DEADLINE_META] for r in reqs]


# ---------------------------------------------------------------------------
# watchdog in a live pipeline
# ---------------------------------------------------------------------------
class Pass(TransformElement):
    FACTORY_NAME = "pass"

    def transform(self, frame):
        return frame


class TestWatchdogPipeline:
    def test_overrun_warn_policy_counts_without_restart(self):
        # delay= fault overruns the frame-deadline; warn policy observes
        # (bus + health) but never interferes with the stream
        FAULTS.arm("element.mid.handle_frame", delay=0.3, times=1)
        pipe = Pipeline("wwarn")
        src, mid, sink = AppSrc("src"), Pass("mid"), TensorSink("out")
        mid.set_property("frame-deadline", 0.1)
        mid.set_property("stall-policy", "warn")
        pipe.chain(src, mid, sink)
        warnings = []
        pipe.add_bus_watcher(
            lambda m: warnings.append(m) if m.kind == "warning" else None)
        pipe.start()
        for i in range(4):
            src.push(np.float32([i]))
        src.end_of_stream()
        pipe.wait(timeout=20)
        h = pipe.health()["mid"]
        assert len(sink.frames) == 4           # nothing lost
        assert h["overruns"] == 1 and h["restarts"] == 0
        assert [m for m in warnings if m.data.get("liveness") == "overrun"]
        pipe.stop()

    def test_hung_element_restarted_zero_loss(self):
        # the acceptance core: a hang is detected as an overrun, the
        # watchdog interrupts it, restart machinery retries the frame
        FAULTS.arm("element.mid.handle_frame", every=3, times=1, hang=True)
        pipe = Pipeline("wrestart")
        src, mid, sink = AppSrc("src"), Pass("mid"), TensorSink("out")
        mid.set_property("frame-deadline", 0.12)
        mid.set_property("stall-policy", "restart")
        mid.set_property("restart-backoff", 0.01)
        pipe.chain(src, mid, sink)
        pipe.start()
        n = 8
        for i in range(n):
            src.push(np.float32([i]))
        src.end_of_stream()
        pipe.wait(timeout=30)
        h = pipe.health()["mid"]
        vals = [float(f.tensors[0][0]) for f in sink.frames]
        assert vals == [float(i) for i in range(n)]   # zero loss, in order
        assert h["restarts"] == 1 and h["overruns"] == 1
        assert h["state"] == "finished"
        pipe.stop()

    def test_stale_interrupt_does_not_spuriously_restart(self):
        # an escalation whose flagged call completes on its own leaves
        # the interrupt flag set; the NEXT healthy call must consume it
        # silently instead of raising a spurious StallError
        pipe = Pipeline("wstale")
        src, mid, sink = AppSrc("src"), Pass("mid"), TensorSink("out")
        mid.set_property("stall-policy", "restart")
        mid.set_property("frame-deadline", 5.0)  # watchdog armed, quiet
        pipe.chain(src, mid, sink)
        pipe.start()
        mid._interrupted.set()  # simulate the race: flag set, call done
        FAULTS.arm("element.mid.handle_frame", hang=True, times=1)
        # were the stale flag leaked into the fault's interrupt predicate,
        # this frame would insta-StallError and burn a restart
        src.push(np.float32([1]))
        time.sleep(0.2)
        FAULTS.reset()  # release the (expected, genuine) hang
        src.push(np.float32([2]))
        src.end_of_stream()
        pipe.wait(timeout=20)
        h = pipe.health()["mid"]
        assert len(sink.frames) == 2
        assert h["restarts"] <= 1  # at most the genuine-hang recovery
        pipe.stop()

    def test_stall_policy_fail_tears_down(self):
        FAULTS.arm("element.mid.handle_frame", hang=True, times=1)
        pipe = Pipeline("wfail")
        src, mid, sink = AppSrc("src"), Pass("mid"), TensorSink("out")
        mid.set_property("frame-deadline", 0.1)
        mid.set_property("stall-policy", "fail")
        pipe.chain(src, mid, sink)
        pipe.start()
        src.push(np.float32([1]))
        with pytest.raises(StallError):
            pipe.wait(timeout=20)
        pipe.stop()

    def test_hung_source_detected_and_restarted(self):
        # sources are monitored too: the busy window wraps each next()
        # on frames(), so a stalled producer (camera/publisher) is
        # flagged and stall-policy=restart re-opens it
        FAULTS.arm("element.cam.frames", every=4, times=1, hang=True)
        pipe = parse_pipeline(
            "videotestsrc name=cam num-buffers=10 width=4 height=4 "
            "frame-deadline=0.15 stall-policy=restart "
            "restart-backoff=0.01 ! tensor_sink name=out")
        pipe.start()
        pipe.wait(timeout=30)
        h = pipe.health()["cam"]
        assert h["restarts"] == 1 and h["overruns"] == 1
        assert len(pipe["out"].frames) == 10
        pipe.stop()

    def test_restart_budget_still_applies_to_stalls(self):
        # a permanently hanging element degrades after max-restarts
        # instead of restart-looping forever
        FAULTS.arm("element.mid.handle_frame", hang=True)
        pipe = Pipeline("wbudget")
        src, mid, sink = AppSrc("src"), Pass("mid"), TensorSink("out")
        mid.set_property("frame-deadline", 0.08)
        mid.set_property("stall-policy", "restart")
        mid.set_property("restart-backoff", 0.0)
        mid.set_property("max-restarts", 2)
        mid.set_property("restart-window", 0.0)
        pipe.chain(src, mid, sink)
        pipe.start()
        src.push(np.float32([1]))
        with pytest.raises(StallError):
            pipe.wait(timeout=30)
        assert pipe.health()["mid"]["restarts"] == 2
        pipe.stop()
        FAULTS.reset()


# ---------------------------------------------------------------------------
# chaos acceptance: hang + deadline + overload, exact accounting
# ---------------------------------------------------------------------------
def _live_named_threads(names):
    return [t.name for t in threading.enumerate()
            if t.is_alive() and t.name in names]


@pytest.mark.chaos
class TestChaosLiveness:
    def test_hung_filter_detected_restarted_stream_completes(self):
        """Acceptance: a filter hung via an injected hang= fault is
        detected by the watchdog within its deadline, restarted under
        stall-policy=restart, and the stream reaches EOS with every
        frame accounted for (delivered + dead-lettered + deadline-
        dropped == pushed) and no leaked worker threads."""
        FAULTS.arm("filter.invoke", every=11, times=1, hang=True)
        pipe = parse_pipeline(
            "appsrc name=src deadline-s=20 ! "
            "tensor_filter name=f framework=scaler custom=factor:2 "
            "frame-deadline=0.15 stall-policy=restart restart-backoff=0.01 ! "
            "tensor_sink name=out")
        pipe.start()
        n = 30
        for i in range(n):
            pipe["src"].push(np.float32([i]))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=60)
        h = pipe.health()
        hf = h["f"]
        vals = sorted(float(f.tensors[0][0]) for f in pipe["out"].frames)
        delivered = len(vals)
        dead_lettered = hf["dead_letters"]
        deadline_dropped = sum(e["deadline_drops"] for e in h.values())
        # exact accounting: no frame unaccounted, no dupes
        assert delivered + dead_lettered + deadline_dropped == n
        assert len(set(vals)) == delivered
        assert set(vals) <= {i * 2.0 for i in range(n)}
        # the hang was detected and cured by a restart
        assert hf["overruns"] == 1 and hf["restarts"] == 1
        assert hf["state"] == "finished"
        pipe.stop()
        deadline = time.monotonic() + 3
        while (_live_named_threads({"src", "f", "out"})
               and time.monotonic() < deadline):
            time.sleep(0.01)
        leaked = _live_named_threads({"src", "f", "out"})
        assert not leaked, f"leaked worker threads: {leaked}"

    def test_overloaded_server_sheds_busy_client_completes(self):
        """Acceptance: an overloaded query server sheds with BUSY instead
        of timing out; the client treats BUSY as paced backpressure and
        completes (degrade accounts any residue), the breaker stays
        closed, and shed counts are visible in Pipeline.health()."""
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=981 port=0 "
            "connect-type=tcp max-inflight=2 low-watermark=1 "
            "retry-after=0.02 ! "
            "identity sleep=0.03 ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=981")
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            f"appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={port} retries=0 busy-retries=10 "
            "retry-backoff=0.01 breaker-threshold=3 degrade=skip timeout=5 "
            "max-in-flight=8 ! tensor_sink name=out")
        client.start()
        try:
            n = 20
            for i in range(n):
                client["src"].push(np.float32([i]))
            client["src"].end_of_stream()
            client.wait(timeout=60)
            hq = client.health()["q"]
            hs = server.health()["ssrc"]
            vals = sorted(
                float(f.tensors[0][0]) for f in client["out"].frames)
            # the stream completed, degraded at worst — exact accounting
            assert len(vals) + hq["degraded_frames"] == n
            assert len(set(vals)) == len(vals)
            # shedding actually happened and is visible in health()
            assert hq["busy_replies"] > 0
            assert hs["load_shed"] > 0 and hs["admitted"] >= len(vals)
            # BUSY is backpressure, not ill-health: breaker stays closed
            for snap in hq["breakers"].values():
                assert snap["state"] == "closed" and snap["trips"] == 0
        finally:
            client.stop()
            server.stop()

    def test_server_side_expiry_before_invoke(self):
        """The wire deadline is honored END-TO-END: the server re-stamps
        each request with the client's remaining budget, and a frame
        whose budget dies inside the server pipeline is expired BEFORE
        the expensive invoke (visible in the server's health)."""
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=982 port=0 "
            "connect-type=tcp ! "
            "identity name=slow sleep=0.3 ! "
            "tensor_filter name=sf framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=982")
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            f"appsrc name=src ! tensor_query_client name=q connect-type=tcp "
            f"host=localhost port={port} retries=0 busy-retries=0 "
            "retry-backoff=0 breaker-threshold=0 degrade=skip timeout=0.2 ! "
            "tensor_sink name=out")
        client.start()
        try:
            for i in range(2):
                client["src"].push(np.float32([i]))
            client["src"].end_of_stream()
            client.wait(timeout=30)
            assert len(client["out"].frames) == 0  # all too slow
            # the filter never invoked those frames: each expired at the
            # door of whichever server element it had reached (the budget
            # re-stamped from the wire deadline_s governs them all)
            def server_drops():
                return sum(
                    e["deadline_drops"] for e in server.health().values())

            deadline = time.monotonic() + 5
            while server_drops() < 2 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert server_drops() >= 2
            assert server.health()["sf"]["state"] == "running"
        finally:
            client.stop()
            server.stop()


# ---------------------------------------------------------------------------
# lint gate: no unbounded blocking calls in the I/O layers
# ---------------------------------------------------------------------------
def test_no_unbounded_blocking():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    try:
        import check_blocking_timeouts
    finally:
        sys.path.pop(0)
    bad = check_blocking_timeouts.scan()
    assert not bad, f"unbounded blocking calls: {bad}"


def test_both_lint_gates_run_clean():
    # CI contract: BOTH failure-handling gates run inside tier-1
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
    try:
        import check_blocking_timeouts
        import check_no_bare_except
    finally:
        sys.path.pop(0)
    assert not check_no_bare_except.scan()
    assert not check_blocking_timeouts.scan()
