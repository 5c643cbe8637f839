"""Device-resource resilience: HBM pressure and device loss (degrade,
don't die — Documentation/resilience.md "Resource pressure & device
loss").

Covers the four seams of the ladder:

1. **Typed taxonomy** — ``classify_device_error`` maps raw XLA runtime
   errors to :class:`DeviceOomError` / :class:`DeviceLostError`; both
   are transient (a shrink/re-mesh cures them, never a restart burn).
2. **Adaptive OOM recovery on the hot path** — the filter retries once
   at the next-smaller batch bucket with exact ``oom_retries`` /
   ``oom_shrinks`` / ``oom_evictions`` accounting (fused/unfused
   parity), and the slot engine sheds its lowest-priority slot as a
   RESUMABLE continuity chunk.
3. **Memory watermarks** — ``MemoryPressureMonitor`` hysteresis, trim
   hooks, rate-limited incidents, and the admission coupling that sheds
   BUSY (reason="memory") *before* the chip OOMs.
4. **Degraded-mesh re-shard** — a jax-xla mesh backend that loses a
   device rebuilds on the survivors via the ``shrink_axes`` ladder,
   the slot engine hands live streams off with resume state, and the
   serving plane announces degraded.

Every path runs chip-free: deterministic injection via the ``device.*``
fault sites, the AsyncSim ``oom_at``/``lost_at`` knobs, and the
SimSlotModel ``fail_next`` twin.
"""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core.buffer import (
    BatchFrame,
    DeviceBufferPool,
    FramePool,
    TensorFrame,
)
from nnstreamer_tpu.core.continuity import GOAWAY_META, RESUME_META
from nnstreamer_tpu.core.liveness import (
    MemoryPressureMonitor,
    ServerBusyError,
    TenantAdmissionController,
)
from nnstreamer_tpu.core.resilience import (
    FAULTS,
    DeviceLostError,
    DeviceOomError,
    classify_device_error,
    is_transient,
)
from nnstreamer_tpu.core.slots import SimSlotModel, SlotEngine
from nnstreamer_tpu.parallel.mesh import remesh_after_loss, shrink_axes
from nnstreamer_tpu.pipeline import parse_pipeline


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    FAULTS.reset()


# ---------------------------------------------------------------------------
# 1. Typed device-error classification
# ---------------------------------------------------------------------------
# a stand-in whose TYPE NAME matches the jax runtime's (classification
# keys on name/module, never on an import of jaxlib)
XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})


class TestClassification:
    def test_resource_exhausted_maps_to_oom(self):
        raw = XlaRuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "4096 bytes")
        typed = classify_device_error(raw)
        assert isinstance(typed, DeviceOomError)
        assert typed.__cause__ is raw

    def test_device_death_maps_to_lost(self):
        typed = classify_device_error(
            XlaRuntimeError("INTERNAL: device is lost (chip reset?)"))
        assert isinstance(typed, DeviceLostError)

    def test_unrelated_runtime_error_is_not_classified(self):
        assert classify_device_error(
            XlaRuntimeError("INVALID_ARGUMENT: shapes differ")) is None
        assert classify_device_error(ValueError("nope")) is None

    def test_already_typed_pass_through(self):
        e = DeviceOomError("x")
        assert classify_device_error(e) is e
        lost = DeviceLostError("y", device_ids=(3,))
        assert classify_device_error(lost) is lost
        assert lost.device_ids == (3,)

    def test_both_are_transient(self):
        # the recovery ladders cure them; supervision must never treat
        # them as poison frames (restart-budget burn / dead-letter)
        assert is_transient(DeviceOomError("o"))
        assert is_transient(DeviceLostError("l"))


class TestShrinkLadder:
    """parallel/mesh.shrink_axes: dp gives way first, then tp halves,
    then unsharded."""

    @pytest.mark.parametrize("axes,n,want", [
        ({"dp": 2, "tp": 2}, 3, {"dp": 1, "tp": 2}),
        ({"dp": 4, "tp": 2}, 6, {"dp": 3, "tp": 2}),
        ({"dp": 4}, 2, {"dp": 2}),
        ({"tp": 4}, 2, {"tp": 2}),
        ({"tp": 2}, 1, {}),
        ({"dp": 2, "tp": 2}, 1, {}),
        ({}, 4, {}),
    ])
    def test_ladder(self, axes, n, want):
        assert shrink_axes(axes, n) == want


class TestRemeshAfterLoss:
    """parallel/mesh.remesh_after_loss: dead-member identification
    order (reported > probed > guessed-last), the probe's
    cannot-probe (``None``) vs all-alive (``()``) disambiguation, and
    the exclusion contract — shared by the jax-xla backend and the
    slotted generator so both re-shard identically."""

    def test_reported_ids_win_and_probe_is_skipped(self):
        probed = []
        dead, axes, spec = remesh_after_loss(
            [0, 1, 2, 3], {"dp": 2, "tp": 2}, (1,),
            probe=lambda ids: probed.append(ids) or (0,))
        assert dead == (1,) and probed == []
        assert axes == {"dp": 1, "tp": 2} and spec == "dp:1,tp:2"

    def test_unnamed_loss_probes_for_the_dead_member(self):
        """Real XLA status strings rarely name the chip: with empty
        ``lost_ids`` the ladder PROBES instead of guessing, so
        ordinal-first claiming cannot hand the rebuilt backend the
        dead chip back (chip 0 dead + a last-member guess would have
        re-placed tp:2 on devices[:2] = {0, 1})."""
        dead, axes, spec = remesh_after_loss(
            [0, 1, 2, 3], {"tp": 4}, (), probe=lambda ids: (0,))
        assert dead == (0,)
        assert axes == {"tp": 2} and spec == "tp:2"

    def test_all_alive_probe_condemns_nobody(self):
        """A probe that reaches EVERY member means the loss did not
        reproduce: dead comes back empty with axes UNCHANGED — callers
        escalate to supervision (a plain retry may cure a transient)
        instead of shrinking the mesh around a healthy chip."""
        dead, axes, spec = remesh_after_loss(
            [0, 1], {"tp": 2}, (), probe=lambda ids: ())
        assert dead == ()
        assert axes == {"tp": 2} and spec == "tp:2"

    def test_unavailable_probe_falls_back_to_last_member_guess(self):
        """``None`` from the probe = could not even enumerate devices
        (a wedged runtime): only THEN does the conservative last-member
        guess apply."""
        dead, axes, spec = remesh_after_loss(
            [0, 1], {"tp": 2}, (), probe=lambda ids: None)
        assert dead == (1,)
        assert axes == {} and spec == ""

    def test_no_probe_falls_back_to_last_member_guess(self):
        dead, axes, spec = remesh_after_loss([0, 1], {"tp": 2}, ())
        assert dead == (1,)
        assert axes == {} and spec == ""


class TestUnshardedSurvivorPlacement:
    """The BOTTOM rung of the re-mesh ladder (spec ``""`` = rebuild
    unsharded) must still avoid the dead ordinals: the default device
    pick would otherwise hand the rebuilt backend the very chip that
    died, crash-looping a server with a healthy survivor."""

    def test_unsharded_open_avoids_excluded_ordinal(self):
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 proxy devices")
        from nnstreamer_tpu.backends.jax_xla import (
            JaxXla,
            register_jax_model,
            unregister_jax_model,
        )

        register_jax_model("excl_ident", lambda p, xs: list(xs))
        try:
            first = int(jax.devices()[0].id)
            be = JaxXla()
            be.open("excl_ident", {
                "accelerators": ["cpu"], "mesh": "",
                "mesh_remesh_override": True,
                "mesh_exclude_ids": [first],
            })
            try:
                assert int(be._device.id) != first
            finally:
                be.close()
        finally:
            unregister_jax_model("excl_ident")

    def test_override_replaces_legacy_mesh_custom_props(self):
        """A survivor spec must REPLACE legacy ``mesh_*`` custom props,
        not merge over them — re-merged axes the survivors cannot
        satisfy would refuse every restart."""
        from nnstreamer_tpu.backends.jax_xla import JaxXla

        be = JaxXla()
        be.custom_props = {"mesh_dp": "2"}
        assert be._mesh_axes_from_props({"mesh": ""}) == {"dp": 2}
        assert be._mesh_axes_from_props(
            {"mesh": "", "mesh_remesh_override": True}) == {}
        assert be._mesh_axes_from_props(
            {"mesh": "tp:2", "mesh_remesh_override": True}) == {"tp": 2}


# ---------------------------------------------------------------------------
# Satellite: DeviceBufferPool key-space LRU + trims
# ---------------------------------------------------------------------------
class TestPoolBounds:
    def test_key_space_is_lru_bounded(self):
        pool = DeviceBufferPool(max_per_key=2)
        sweep = pool.MAX_KEYS + 8
        for i in range(sweep):
            # a (shape, dtype, placement) sweep used to grow rings
            # forever — the jit-cache leak class
            pool.release(np.empty((i + 1, 4), np.float32))
        assert len(pool._free) <= pool.MAX_KEYS
        assert pool.rings_evicted >= 8

    def test_lru_keeps_the_hot_ring(self):
        pool = DeviceBufferPool(max_per_key=2)
        hot = pool._key((2, 2), np.float32, None)
        pool.release(np.empty((2, 2), np.float32))
        for i in range(pool.MAX_KEYS + 4):
            pool.release(np.empty((i + 3, 3), np.float32))
            pool.acquire((2, 2), np.float32)  # touch = keep
        assert hot in pool._free

    def test_trim_frees_everything_but_keeps_pooling(self):
        pool = DeviceBufferPool(max_per_key=4)
        for _ in range(3):
            pool.release(np.empty((4, 4), np.float32))
        assert pool.trim() == 3
        assert not pool._free and pool.trims == 1
        buf = pool.acquire((4, 4), np.float32)
        assert pool.release(buf)  # ring rebuilt on demand

    def test_frame_pool_trim(self):
        fp = FramePool(maxsize=16)
        f = fp.acquire([np.zeros(2)])
        fp.recycle(f)
        assert fp.trim() >= 1
        assert fp.acquire([np.zeros(2)]) is not None


# ---------------------------------------------------------------------------
# 3. Memory watermarks
# ---------------------------------------------------------------------------
class FakeMem:
    def __init__(self, frac=0.0, limit=1000):
        self.frac = frac
        self.limit = limit

    def __call__(self):
        return int(self.frac * self.limit), self.limit, 123


class TestMemoryPressureMonitor:
    def _mon(self, mem, clk, **kw):
        kw.setdefault("high", 0.9)
        kw.setdefault("low", 0.7)
        kw.setdefault("min_poll_s", 0.0)
        return MemoryPressureMonitor(
            sample=mem, clock=lambda: clk["t"], **kw)

    def test_hysteresis_and_trim_on_entry(self):
        mem, clk = FakeMem(0.5), {"t": 0.0}
        trims = {"n": 0}
        mon = self._mon(mem, clk)
        mon.add_trim_hook(lambda: trims.__setitem__("n", trims["n"] + 1) or 7)
        assert mon.poll() is False
        mem.frac = 0.95
        clk["t"] = 1.0
        assert mon.poll() is True and trims["n"] == 1
        assert mon.trimmed_entries == 7
        # inside the hysteresis band: still pressured, no re-trim
        mem.frac = 0.8
        clk["t"] = 1.1
        assert mon.poll() is True and trims["n"] == 1
        mem.frac = 0.6
        clk["t"] = 1.2
        assert mon.poll() is False
        snap = mon.snapshot()
        assert snap["mem_pressure"] == 0 and snap["mem_trims"] == 1
        assert snap["mem_host_rss"] == 123

    def test_sustained_pressure_incident_is_rate_limited(self):
        mem, clk = FakeMem(0.95), {"t": 0.0}
        hits = []
        mon = self._mon(mem, clk, sustain_s=1.0, incident_interval_s=10.0,
                        on_pressure=hits.append)
        mon.poll()
        assert not hits  # entered, not yet sustained
        clk["t"] = 1.5
        mon.poll()
        assert len(hits) == 1 and hits[0]["mem_pressure"] == 1
        clk["t"] = 2.0
        mon.poll()
        assert len(hits) == 1  # rate-limited
        clk["t"] = 12.0
        mon.poll()
        assert len(hits) == 2

    def test_poll_rate_limit(self):
        mem, clk = FakeMem(0.0), {"t": 0.0}
        mon = self._mon(mem, clk, min_poll_s=0.25)
        mon.poll()
        clk["t"] = 0.1
        mon.poll()  # inside the window: no sample
        assert mon.polls == 1
        clk["t"] = 0.3
        mon.poll()
        assert mon.polls == 2

    def test_host_rss_watermark_fallback(self):
        # no device stats: the host-RSS/host-limit fraction drives it
        clk = {"t": 0.0}
        mon = MemoryPressureMonitor(
            high=0.9, low=0.5, min_poll_s=0.0, host_limit_bytes=100,
            sample=lambda: (0, 0, 95), clock=lambda: clk["t"])
        assert mon.poll() is True

    def test_armed_monitor_never_inert_without_limits(self):
        """Stats-less platform + no explicit host limit: the fraction
        defaults to RSS over physical RAM — an armed watermark must
        watch SOMETHING, never sit at 0.0 while the process OOMs."""
        mon = MemoryPressureMonitor(
            high=0.9, low=0.5, min_poll_s=0.0,
            sample=lambda: (0, 0, 123 << 20), clock=lambda: 0.0)
        mon.poll()
        assert mon.fraction > 0.0

    def test_bad_watermarks_refused(self):
        with pytest.raises(ValueError):
            MemoryPressureMonitor(high=0.5, low=0.8)


class TestAdmissionMemoryCoupling:
    def test_pressure_sheds_with_memory_reason(self):
        flag = {"on": False}
        adm = TenantAdmissionController(high=8)
        adm.pressure = lambda: flag["on"]
        adm.admit(tenant="a")
        flag["on"] = True
        with pytest.raises(ServerBusyError) as ei:
            adm.admit(tenant="a")
        assert ei.value.reason == "memory"
        assert adm.memory_shed == 1
        assert adm.snapshot()["memory_shed"] == 1
        flag["on"] = False
        adm.admit(tenant="a")  # clears with the watermark
        adm.release(tenant="a")
        adm.release(tenant="a")

    def test_memory_shed_covers_every_priority_class(self):
        adm = TenantAdmissionController(high=8)
        adm.pressure = lambda: True
        # HBM exhaustion takes the whole chip down: even priority-3
        # traffic sheds while the watermark is crossed
        for prio in (0, 3):
            with pytest.raises(ServerBusyError) as ei:
                adm.admit(tenant="x", priority=prio)
            assert ei.value.reason == "memory"
        assert adm.memory_shed == 2


# ---------------------------------------------------------------------------
# 2a. Slot engine: OOM shed + device-loss handoff
# ---------------------------------------------------------------------------
def _engine(model, resume_sig="testsig", **kw):
    eng = SlotEngine(model, None, max_seq=1 << 20, chunk=4,
                     prefill_chunk=32, resume_sig=resume_sig, **kw)
    eng.start()
    return eng


def _submit(eng, base=1, priority=3):
    prompt = (np.arange(4, dtype=np.int32)[None] + base)
    frame = TensorFrame([prompt])
    return eng.submit(frame, prompt, max_new=24, chunk=4,
                      priority=priority), prompt


def _drain_until(eng, pred, timeout=20.0, out=None):
    out = [] if out is None else out
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out.extend(eng.pop_ready())
        if pred(out):
            return out
        time.sleep(0.005)
    raise TimeoutError(f"engine never satisfied predicate; got {len(out)}")


def _oracle(model, prompt, n):
    t = int(prompt.sum()) % model.vocab
    toks = [t]
    for _ in range(n - 1):
        t = model.step_token(t)
        toks.append(t)
    return toks


def _stream_tokens(frames, sid):
    toks = []
    for _, f in frames:
        if f.meta.get("stream_seq") == sid and f.tensors:
            toks.extend(int(t) for t in np.asarray(f.tensors[0])[0])
    return toks


class TestSlotEngineOom:
    def test_oom_sheds_lowest_priority_resumably(self):
        model = SimSlotModel(2, step_base_ms=0.5)
        eng = _engine(model)
        try:
            s_lo, p_lo = _submit(eng, base=1, priority=0)
            s_hi, p_hi = _submit(eng, base=9, priority=3)
            # both decoding: wait for tokens from each, then blow HBM
            out = _drain_until(eng, lambda o: (
                _stream_tokens(o, s_lo.frame.seq)
                and _stream_tokens(o, s_hi.frame.seq)))
            model.fail_next("oom")
            out = _drain_until(
                eng, lambda o: any(
                    f.meta.get("evicted") == "oom" for _, f in o),
                out=out)
            shed = [f for _, f in out if f.meta.get("evicted") == "oom"]
            assert len(shed) == 1
            # the LOWEST priority stream was chosen, and its chunk is a
            # resumable migration (goaway marker + resume state), never
            # a deadline-style failure
            assert shed[0].meta.get("stream_seq") == s_lo.frame.seq
            assert shed[0].meta.get(GOAWAY_META) is True
            assert RESUME_META in shed[0].meta
            assert "deadline_expired" not in shed[0].meta
            assert eng.oom_retries == 1 and eng.oom_sheds == 1
            # the survivor finishes bit-exact: the retried step lost no
            # tokens and duplicated none
            out = _drain_until(
                eng, lambda o: any(
                    f.meta.get("final") and not f.meta.get("evicted")
                    and f.meta.get("stream_seq") == s_hi.frame.seq
                    for _, f in o),
                out=out)
            assert _stream_tokens(out, s_hi.frame.seq) == _oracle(
                model, p_hi, 24)
            assert eng.snapshot()["gen_oom_sheds"] == 1
        finally:
            eng.stop()

    def test_donated_cache_death_hands_off_all_streams_resumably(self):
        """Real-chip donation semantics: the decode/prefill jits donate
        the KV cache, and donation invalidates at DISPATCH, not at
        success — a step that OOMs after dispatch leaves ``_cache``
        deleted.  Retrying on the deleted buffers would raise an
        UNTYPED "Array has been deleted" and kill the pump; instead the
        engine hands EVERY live stream off as a resumable continuity
        chunk and re-inits the cache clean (streams re-prefill on
        resume — bit-exact), then keeps serving."""

        class _DeadLeaf:
            def is_deleted(self):
                return True

        model = SimSlotModel(2, step_base_ms=0.5)
        eng = _engine(model)
        try:
            s1, _p1 = _submit(eng, base=1, priority=0)
            s2, _p2 = _submit(eng, base=9, priority=3)
            out = _drain_until(eng, lambda o: (
                _stream_tokens(o, s1.frame.seq)
                and _stream_tokens(o, s2.frame.seq)))
            # the OOMing step also consumed the donated cache
            orig = eng._handle_oom

            def oom_and_kill_cache():
                orig()
                eng._cache = {"k": _DeadLeaf()}

            eng._handle_oom = oom_and_kill_cache
            model.fail_next("oom")
            out = _drain_until(eng, lambda o: sum(
                1 for _, f in o
                if f.meta.get("evicted") == "oom") >= 2, out=out)
            shed = [f for _, f in out if f.meta.get("evicted") == "oom"]
            # the priority victim AND the survivor whose KV died: both
            # resumable migrations, never a poisoned frame
            assert len(shed) == 2
            for f in shed:
                assert f.meta.get(GOAWAY_META) is True
                assert RESUME_META in f.meta
            assert eng.oom_retries == 1 and eng.oom_sheds == 2
            # the pump SURVIVED with a fresh cache: a new stream
            # decodes to the exact oracle
            s3, p3 = _submit(eng, base=42)
            out = _drain_until(eng, lambda o: any(
                f.meta.get("final") and not f.meta.get("evicted")
                and f.meta.get("stream_seq") == s3.frame.seq
                for _, f in o))
            assert _stream_tokens(out, s3.frame.seq) == _oracle(
                model, p3, 24)
        finally:
            eng.stop()

    def test_single_occupant_oom_is_shed_resumably(self):
        model = SimSlotModel(1, step_base_ms=0.5, oom_at_step=0)
        eng = _engine(model)
        try:
            s, _ = _submit(eng)
            out = _drain_until(
                eng, lambda o: any(f.meta.get("final") for _, f in o))
            # single occupant: it IS the lowest-priority slot, so it is
            # shed resumably (token 1 from the prefill survives in the
            # handoff chunk) — never silently dropped, never restarted
            shed = [f for _, f in out if f.meta.get("evicted") == "oom"]
            assert len(shed) == 1
            assert shed[0].meta.get(GOAWAY_META) is True
            assert shed[0].meta.get("tokens_done") == 1
            assert eng.oom_retries == 1 and eng.oom_sheds == 1
        finally:
            eng.stop()


class TestSlotEngineDeviceLost:
    def test_loss_hands_off_all_streams_and_remeshes(self):
        model = SimSlotModel(4, step_base_ms=0.5)
        calls = []

        def hook(err):
            calls.append(err)
            return None  # sim twin: recovered in place

        eng = _engine(model, on_device_lost=hook)
        try:
            streams = [_submit(eng, base=i * 7 + 1) for i in range(3)]
            _drain_until(eng, lambda out: all(
                _stream_tokens(out, s.frame.seq) for s, _ in streams))
            model.fail_next("lost")
            out = _drain_until(eng, lambda o: sum(
                1 for _, f in o
                if f.meta.get("evicted") == "device_lost") >= 3)
            handed = [f for _, f in out
                      if f.meta.get("evicted") == "device_lost"]
            assert len(handed) == 3
            for f in handed:
                assert f.meta.get(GOAWAY_META) is True  # resumable
                assert RESUME_META in f.meta
            assert len(calls) == 1
            assert isinstance(calls[0], DeviceLostError)
            snap = eng.snapshot()
            assert snap["gen_device_lost"] == 1
            assert snap["gen_device_lost_evicted"] == 3
            assert snap["gen_remeshes"] == 1
            # the engine keeps serving on the "survivors": a fresh
            # stream decodes to the exact oracle
            s2, p2 = _submit(eng, base=42)
            out = _drain_until(eng, lambda o: any(
                f.meta.get("final")
                and f.meta.get("stream_seq") == s2.frame.seq
                for _, f in o))
            assert _stream_tokens(out, s2.frame.seq) == _oracle(
                model, p2, 24)
        finally:
            eng.stop()

    def test_loss_without_hook_is_sticky(self):
        model = SimSlotModel(1, step_base_ms=0.5)
        eng = _engine(model, on_device_lost=None)
        try:
            _submit(eng)
            model.fail_next("lost")
            deadline = time.monotonic() + 10
            with pytest.raises(DeviceLostError):
                while time.monotonic() < deadline:
                    eng.pop_ready()
                    time.sleep(0.01)
                raise TimeoutError("engine error never surfaced")
        finally:
            eng.stop()

    def test_legacy_engine_handoff_is_typed_but_not_resumable(self):
        model = SimSlotModel(1, step_base_ms=0.5)
        eng = _engine(model, resume_sig=None, on_device_lost=lambda e: None)
        try:
            s, _ = _submit(eng)
            _drain_until(eng, lambda out: _stream_tokens(out, s.frame.seq))
            model.fail_next("lost")
            out = _drain_until(eng, lambda o: any(
                f.meta.get("evicted") == "device_lost" for _, f in o))
            f = next(f for _, f in out
                     if f.meta.get("evicted") == "device_lost")
            # no resume state to offer: the truncation is LOUD (typed
            # final chunk), never a goaway a client would wait on
            assert GOAWAY_META not in f.meta
            assert RESUME_META not in f.meta
            assert f.meta.get("final") is True
        finally:
            eng.stop()


class TestSlotEngineFaultWithAScanInFlight:
    @pytest.mark.parametrize("site", ["device.oom", "device.lost"])
    def test_a_fault_with_a_scan_in_flight_recovers_as_before(self, site):
        """The fault site fires at the pump's fourth device call: the two
        prompts' chunks and scan 1 are the first three, so it is scan 2's
        dispatch, made while scan 1 is in flight.  That scan is read back
        first, so the recovery sees what it saw when every scan was read
        back in its own turn: each stream holds its first token and scan
        1's four.  An OOM sheds the lower-priority stream with those five
        as a resumable chunk and the other finishes exactly; a lost device
        hands both off with them."""
        model = SimSlotModel(2, step_base_ms=0.5)
        eng = SlotEngine(model, None, max_seq=1 << 20, chunk=4,
                         prefill_chunk=32, resume_sig="testsig",
                         on_device_lost=lambda err: None)
        lo, p_lo = _submit(eng, base=1, priority=0)
        hi, p_hi = _submit(eng, base=9, priority=3)
        oom = site == "device.oom"
        FAULTS.arm(site, exc=DeviceOomError if oom else DeviceLostError,
                   times=1, after=3)
        eng.start()
        reason = "oom" if oom else "device_lost"
        try:
            out = _drain_until(eng, lambda o: sum(
                1 for _, f in o if f.meta.get("final")) >= 2)
            snap = eng.snapshot()
        finally:
            eng.stop()
        handed = {f.meta["stream_seq"]: f for _, f in out
                  if f.meta.get("evicted") == reason}
        assert sorted(handed) == sorted(
            [lo.frame.seq] if oom else [lo.frame.seq, hi.frame.seq])
        for seq, f in handed.items():
            assert f.meta[GOAWAY_META] is True and RESUME_META in f.meta
            assert f.meta["tokens_done"] == 5
            p = p_lo if seq == lo.frame.seq else p_hi
            assert _stream_tokens(out, seq) == _oracle(model, p, 5)
        if oom:
            assert _stream_tokens(out, hi.frame.seq) == _oracle(model, p_hi, 24)
            assert (snap["gen_oom_retries"], snap["gen_oom_sheds"]) == (1, 1)
        else:
            assert (snap["gen_device_lost"], snap["gen_device_lost_evicted"],
                    snap["gen_remeshes"]) == (1, 2, 1)


class TestGeneratorDeviceLost:
    """The slotted generator's ``on_device_lost`` hook for REAL
    (non-sim) models: an unsharded model escalates to supervision
    instead of "recovering" onto the dead device forever, and a
    re-shard leaves a survivor config that later restarts keep."""

    CUSTOM = ("dtype:float32,vocab:61,d_model:32,heads:2,layers:1,"
              "d_ff:64,seq:32,seed:11")

    def test_unsharded_real_model_loss_escalates(self):
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_generator name=g slots=2 "
            f"custom={self.CUSTOM} max-new=4 ! tensor_sink name=out",
            name="genloss", fuse=False)
        pipe.start()
        try:
            with pytest.raises(DeviceLostError):
                pipe["g"]._rebuild_on_device_loss(
                    DeviceLostError("chip gone"))
        finally:
            pipe.stop()

    def test_restart_keeps_the_survivor_config(self):
        """A supervision restart after a re-shard must claim the SHRUNK
        config: re-claiming the original spec against the exclusion
        list would refuse to start once the survivors no longer fit
        it (the dead stay dead across restarts)."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 proxy devices")
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_generator name=g slots=2 "
            f"custom={self.CUSTOM} mesh=tp:2 max-new=4 ! "
            "tensor_sink name=out", name="genremesh", fuse=False)
        pipe.start()
        g = pipe["g"]
        assert g._mesh is not None
        pipe.stop()
        # the state a device-loss rebuild leaves behind: the FIRST
        # member (the default pick!) dead, survivor config "" (unsharded)
        dead = int(jax.devices()[0].id)
        g._mesh_exclude = (dead,)
        g._mesh_override = ""
        g.start()  # the supervision-restart path re-enters start()
        try:
            assert g._mesh is None  # serving unsharded on the survivor
            leaf = jax.tree_util.tree_leaves(g._params)[0]
            assert dead not in {int(d.id) for d in leaf.devices()}
        finally:
            g.stop()


# ---------------------------------------------------------------------------
# 2b. Filter hot path: OOM shrink-retry (fused/unfused parity) + loss
# ---------------------------------------------------------------------------
def _run_block_through_filter(fuse: bool, custom: str,
                              n: int = 8) -> tuple:
    pipe = parse_pipeline(
        "appsrc name=src ! "
        f"tensor_filter name=f framework=async-sim custom={custom} "
        "max-batch=8 ! tensor_sink name=out max-stored=64",
        name=f"oomf-{fuse}", fuse=fuse)
    pipe.start()
    got = []
    pipe["out"].connect_new_data(
        lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
    block = np.arange(n * 1, dtype=np.float32).reshape(n, 1)
    pipe["src"].push_block(block)
    pipe["src"].end_of_stream()
    pipe.wait(timeout=30)
    health = pipe.health()["f"]
    pipe.stop()
    return got, health


class TestFilterOomShrinkRetry:
    @pytest.mark.parametrize("fuse", [True, False])
    def test_injected_oom_burst_delivers_every_frame(self, fuse):
        """Acceptance pin: an OOM on a full 8-row micro-batch delivers
        ALL frames via two half-bucket invokes — zero dead-letters,
        zero restart-budget burn, exact counters; identical fused and
        unfused (the parity satellite)."""
        got, health = _run_block_through_filter(fuse, "oom_at:0")
        assert sorted(got) == [v * 2.0 + 1.0 for v in range(8)]
        assert health["oom_retries"] == 1
        assert health["oom_shrinks"] == 1
        assert health["device_lost"] == 0
        assert health["dead_letters"] == 0
        assert health["restarts"] == 0
        assert health["degraded"] == 0

    def test_unrecovered_second_oom_escalates(self):
        """The retry is ONCE: a second OOM on the shrunken halves
        surfaces to supervision (typed, transient) instead of looping."""
        # every attempt from 0 on faults: attempt 0 (full batch) and
        # attempt 1 (first half) both OOM
        pipe = parse_pipeline(
            "appsrc name=src ! "
            "tensor_filter name=f framework=async-sim "
            "custom=oom_at:0,oom_every:0 max-batch=8 ! "
            "tensor_sink name=out", name="oomhard", fuse=False)
        # arm the process-wide site as well: the half-batch retry hits it
        FAULTS.arm("device.oom", exc=DeviceOomError, times=2, after=1)
        pipe.start()
        pipe["src"].push_block(np.ones((8, 1), np.float32))
        pipe["src"].end_of_stream()
        with pytest.raises(DeviceOomError):
            pipe.wait(timeout=20)
        pipe.stop()

    def test_per_frame_oom_trims_and_retries_once(self):
        """max-batch=1 path (nothing to split): trim + one bare retry."""
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model,
            unregister_jax_model,
        )

        register_jax_model("oom_ident", lambda p, xs: list(xs))
        try:
            pipe = parse_pipeline(
                "appsrc name=src ! "
                "tensor_filter name=f framework=jax-xla model=oom_ident ! "
                "tensor_sink name=out max-stored=8",
                name="oomframe", fuse=False)
            pipe.start()
            got = []
            pipe["out"].connect_new_data(
                lambda f: got.append(np.asarray(f.tensors[0]).copy()))
            FAULTS.arm("device.oom", exc=DeviceOomError, times=1, after=1)
            for i in range(4):
                pipe["src"].push(np.full((3,), float(i), np.float32))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=60)
            health = pipe.health()["f"]
            pipe.stop()
            assert len(got) == 4
            assert health["oom_retries"] == 1
            assert health["oom_shrinks"] == 0
            assert health["dead_letters"] == 0
        finally:
            unregister_jax_model("oom_ident")

    def test_donated_inputs_deleted_by_the_failed_attempt_escalate(self):
        """A donated invoke invalidates its inputs at DISPATCH, not at
        success: when the OOM lands after donation there is nothing
        left to slice — the typed transient error must surface to
        supervision, never a crash on a deleted array (and never a
        phantom ``oom_retries`` count for a retry that cannot run)."""
        pipe = parse_pipeline(
            "appsrc name=src ! "
            "tensor_filter name=f framework=async-sim max-batch=8 ! "
            "tensor_sink name=out", name="oomdel", fuse=False)
        pipe.start()
        try:
            f = pipe["f"]

            def _oom(inputs, private=False):
                raise DeviceOomError("post-donation OOM")

            f._backend_invoke_batch = _oom

            class DeletedArray:
                shape = (8, 1)

                def is_deleted(self):
                    return True

                def __getitem__(self, s):
                    raise RuntimeError("Array has been deleted.")

            with pytest.raises(DeviceOomError):
                f._resilient_invoke_batch([DeletedArray()], private=True)
            assert f._oom_retries == 0
            assert f._oom_shrinks == 0
        finally:
            pipe.stop()


# ---------------------------------------------------------------------------
# Satellite: RESOURCE_EXHAUSTED inside staged-reload warmup
# ---------------------------------------------------------------------------
class TestWarmupOom:
    def test_warmup_oom_counts_swap_failure_and_keeps_serving(self):
        """An OOM-typed error raised inside the staged-reload WARMUP
        (the new model's probe invoke blowing HBM) must land as a
        ``swap_failures`` with the old backend serving — never a
        restart, never a half-swapped backend."""
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model,
            unregister_jax_model,
        )

        register_jax_model("warm_a", lambda p, xs: [xs[0] * 2.0])
        register_jax_model("warm_b", lambda p, xs: [xs[0] * 3.0])
        try:
            pipe = parse_pipeline(
                "appsrc name=src ! "
                "tensor_filter name=f framework=jax-xla model=warm_a "
                "is-updatable=true ! tensor_sink name=out max-stored=8",
                name="warmoom", fuse=False)
            pipe.start()
            got = []
            pipe["out"].connect_new_data(
                lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
            pipe["src"].push(np.float32([1.0]))
            _wait(lambda: len(got) == 1)
            FAULTS.arm(
                "filter.reload.warmup",
                exc=DeviceOomError("RESOURCE_EXHAUSTED in staged warmup"),
                times=1)
            ticket = pipe["f"].request_reload("warm_b")
            assert ticket.wait_staged(timeout=20)
            assert not ticket.ok
            assert isinstance(ticket.error, DeviceOomError)
            # the OLD model keeps serving, accounted as a swap failure
            pipe["src"].push(np.float32([2.0]))
            _wait(lambda: len(got) == 2)
            assert got == [2.0, 4.0]  # still *2, never *3
            health = pipe.health()["f"]
            assert health["swap_failures"] == 1
            assert health["swaps"] == 0
            assert health["restarts"] == 0
            pipe["src"].end_of_stream()
            pipe.wait(timeout=20)
            pipe.stop()
        finally:
            unregister_jax_model("warm_a")
            unregister_jax_model("warm_b")


def _wait(pred, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise TimeoutError("condition never held")


# ---------------------------------------------------------------------------
# 4. Degraded-mesh re-shard (real jax-xla CPU proxy mesh)
# ---------------------------------------------------------------------------
class TestFilterDeviceLostRemesh:
    def test_mesh_member_loss_reshards_and_redelivers(self):
        """dp:2,tp:2 filter loses device 3 mid-serving: the element
        stages a dp:1,tp:2 backend on the survivors, swaps atomically,
        retries the failed batch (zero frame loss), reports exact
        ``device_lost``/``remeshes`` counters, and marks itself
        degraded."""
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 proxy devices")
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model,
            unregister_jax_model,
        )

        register_jax_model("remesh_ident", lambda p, xs: [xs[0] * 2.0])
        try:
            pipe = parse_pipeline(
                "appsrc name=src ! "
                "tensor_filter name=f framework=jax-xla "
                "model=remesh_ident mesh=dp:2,tp:2 max-batch=4 ! "
                "tensor_sink name=out max-stored=64",
                name="remesh", fuse=False)
            pipe.start()
            got = []
            pipe["out"].connect_new_data(
                lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
            pipe["src"].push_block(
                np.arange(4, dtype=np.float32).reshape(4, 1))
            _wait(lambda: len(got) == 4)
            assert pipe.health()["f"]["mesh_devices"] == 4
            # device 3 dies under the NEXT batch (exactly once)
            FAULTS.arm("device.lost", callback=lambda i: (
                DeviceLostError("injected chip death", device_ids=(3,))
                if i == 0 else None))
            pipe["src"].push_block(
                np.arange(4, 8, dtype=np.float32).reshape(4, 1))
            _wait(lambda: len(got) == 8)
            pipe["src"].push_block(
                np.arange(8, 12, dtype=np.float32).reshape(4, 1))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=60)
            health = pipe.health()["f"]
            pipe.stop()
            # zero frame loss, bit-exact through the re-shard
            assert sorted(got) == [v * 2.0 for v in range(12)]
            assert health["device_lost"] == 1
            assert health["remeshes"] == 1
            assert health["degraded"] == 1
            # the survivors' mesh: dp halved, tp kept, dead chip excluded
            assert health["mesh_devices"] == 2
            assert health["mesh_dp"] == 1 and health["mesh_tp"] == 2
            assert health["dead_letters"] == 0
            assert health["restarts"] == 0
        finally:
            unregister_jax_model("remesh_ident")

    def test_unsharded_loss_falls_through_to_supervision(self):
        """No mesh = no re-mesh story: the typed loss reaches the
        supervisor (error-policy owns it), pinned so the ladder never
        silently swallows a loss it cannot cure."""
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model,
            unregister_jax_model,
        )

        register_jax_model("flat_ident", lambda p, xs: list(xs))
        try:
            pipe = parse_pipeline(
                "appsrc name=src ! "
                "tensor_filter name=f framework=jax-xla model=flat_ident ! "
                "tensor_sink name=out", name="flatloss", fuse=False)
            pipe.start()
            FAULTS.arm("device.lost", exc=DeviceLostError, times=1)
            pipe["src"].push(np.float32([1.0]))
            pipe["src"].end_of_stream()
            with pytest.raises(DeviceLostError):
                pipe.wait(timeout=20)
            pipe.stop()
        finally:
            unregister_jax_model("flat_ident")

    def test_unsharded_loss_excludes_dead_chip_for_restart(self):
        """An UNSHARDED loss has no re-mesh story, but the reported
        dead ordinal must still land on the exclusion list — without
        it the supervision restart deterministically re-picks the very
        chip that died (pick_device is ordinal-first) and crash-loops
        until the restart budget burns.  With it, open()'s survivor
        placement moves serving to a live device and every frame
        delivers."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 proxy devices")
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model,
            unregister_jax_model,
        )

        register_jax_model("uloss_double", lambda p, xs: [xs[0] * 2.0])
        try:
            pipe = parse_pipeline(
                "appsrc name=src ! "
                "tensor_filter name=f framework=jax-xla "
                "model=uloss_double error-policy=restart "
                "max-restarts=2 ! tensor_sink name=out max-stored=64",
                name="ulossex", fuse=False)
            pipe.start()
            got = []
            pipe["out"].connect_new_data(
                lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
            own = int(pipe["f"].backend._device.id)
            FAULTS.arm("device.lost", callback=lambda i: (
                DeviceLostError("chip reset", device_ids=(own,))
                if i == 0 else None))
            pipe["src"].push(np.float32([1.0]))
            pipe["src"].push(np.float32([2.0]))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=30)
            health = pipe.health()["f"]
            moved_to = int(pipe["f"].backend._device.id)
            pipe.stop()
            assert sorted(got) == [2.0, 4.0]
            assert health["restarts"] == 1
            assert health["device_lost"] == 1
            assert health["dead_letters"] == 0
            assert moved_to != own  # restarted on a SURVIVOR
        finally:
            unregister_jax_model("uloss_double")


# ---------------------------------------------------------------------------
# 3b. Watermark -> BUSY coupling, end to end over the query wire
# ---------------------------------------------------------------------------
class TestWatermarkProps:
    def test_serversrc_prop_arms_the_pipeline_monitor(self):
        """Pipeline-text configuration parity: ``mem-high-watermark=``
        on the serversrc arms the same pipeline monitor as
        ``enable_memory_monitor()`` (sweeper-polled, admission-coupled,
        default real sampler)."""
        pipe = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=9472 port=0 "
            "connect-type=tcp mem-high-watermark=0.9 ! "
            "tensor_filter framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=9472", name="memprop")
        pipe.start()
        try:
            mon = pipe.memory_monitor
            assert mon is not None
            assert mon.high == 0.9 and abs(mon.low - 0.72) < 1e-9
            _wait(lambda: mon.polls > 0)  # the sweeper picked it up
            assert pipe.health()["ssrc"]["mem_polls"] >= 1
        finally:
            pipe.stop()


class TestWatermarkShedsBeforeOom:
    def test_server_sheds_busy_at_the_watermark_then_recovers(self):
        """Acceptance pin: sustained watermark pressure sheds BUSY at
        admission (reason=memory, exact ``memory_shed`` count, breaker-
        immune) and serving resumes once pressure clears — every frame
        delivered exactly once."""
        mem = FakeMem(0.1)
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=9471 port=0 "
            "connect-type=tcp max-inflight=8 ! "
            "tensor_filter name=f framework=scaler custom=factor:2 ! "
            "tensor_query_serversink id=9471", name="memsrv")
        mon = server.enable_memory_monitor(
            high=0.9, low=0.5, sustain_s=0.05, min_poll_s=0.01,
            sample=mem)
        server.start()
        port = server["ssrc"].props["port"]
        client = parse_pipeline(
            "appsrc name=src ! "
            f"tensor_query_client name=q hosts=localhost:{port} "
            "connect-type=tcp busy-retries=200 retry-backoff=0.02 "
            "timeout=30 ! tensor_sink name=out max-stored=64",
            name="memcli")
        client.start()
        got = []
        client["out"].connect_new_data(
            lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
        try:
            client["src"].push(np.float32([1.0]))
            _wait(lambda: len(got) == 1)
            # cross the watermark; the sweeper poll cadence picks it up
            mem.frac = 0.95
            _wait(lambda: mon.pressured)
            client["src"].push(np.float32([2.0]))
            client["src"].push(np.float32([3.0]))
            # the server provably refused at admission while pressured
            _wait(lambda: server.health()["ssrc"]["memory_shed"] >= 1)
            assert server.health()["ssrc"]["mem_pressure"] == 1
            # pressure clears -> the paced client retries land
            mem.frac = 0.1
            _wait(lambda: not mon.pressured)
            _wait(lambda: len(got) == 3, timeout=30)
            assert sorted(got) == [2.0, 4.0, 6.0]
            h = server.health()["ssrc"]
            assert h["memory_shed"] >= 1
            assert h["mem_polls"] > 0
            # the shed PREEMPTED the OOM: pressure was relieved at
            # admission, so the invoke path never hit the threshold
            assert server.health()["f"]["oom_retries"] == 0
            # BUSY sheds are health, never breaker food
            q = client.health()["q"]
            assert int(q.get("busy_replies", 0)) >= 1
            assert all(b["trips"] == 0
                       for b in q.get("breakers", {}).values())
            client["src"].end_of_stream()
            client.wait(timeout=30)
        finally:
            client.stop()
            server.stop()
