"""Continuous batching: slot scheduler, paged KV cache, multiplexed
token streams (core/slots.py + models/transformer.py SlotModel +
tensor_generator slots=N).

Oracles:

* REAL model — the slotted path must be BIT-IDENTICAL per stream to the
  seed ``generate:<N>`` one-shot path and to the unslotted streaming
  path (same params seed, same sampling seed, same per-step key
  folding): continuous batching is a scheduling change, never a
  sampling change.
* SIM model — token 1 = ``sum(prompt) % vocab``, token j+1 =
  ``(31 t_j + 17) % vocab``: exact per-stream accounting and
  cross-slot-contamination checks without model cost.
"""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.slots import SimSlotModel, SlotEngine
from nnstreamer_tpu.models import build
from nnstreamer_tpu.pipeline import parse_pipeline

PROPS = {
    "dtype": "float32", "vocab": 61, "d_model": 32, "heads": 2,
    "layers": 2, "d_ff": 64, "seq": 64, "seed": 11,
}
CUSTOM = ",".join(f"{k}:{v}" for k, v in PROPS.items())
SAMPLING = "temperature:0.8,top_k:7,gen_seed:3"


def _oneshot(prompt, n, extra=None):
    props = {**{k: str(v) for k, v in PROPS.items()}, "generate": str(n)}
    if extra:
        props.update(extra)
    fn, params, _, _ = build("transformer", props)
    return np.asarray(fn(params, [prompt])[0])[:, prompt.shape[1]:]


def sim_oracle(model: SimSlotModel, prompt, n):
    t = int(prompt.sum()) % model.vocab
    out = [t]
    for _ in range(n - 1):
        t = model.step_token(t)
        out.append(t)
    return np.asarray([out], np.int32)


def _stream_tokens(frames):
    """Concatenate one stream's chunk frames (tensor-less typed-expiry
    frames contribute nothing) after asserting chunk-meta coherence."""
    frames = sorted(frames, key=lambda f: f.meta["chunk_index"])
    assert [f.meta["chunk_index"] for f in frames] == list(
        range(len(frames)))
    assert frames[-1].meta["final"] is True
    assert all(f.meta["final"] is False for f in frames[:-1])
    parts = [np.asarray(f.tensors[0]) for f in frames if f.tensors]
    toks = (np.concatenate(parts, axis=1) if parts
            else np.zeros((1, 0), np.int32))
    assert frames[-1].meta["tokens_done"] == toks.shape[1]
    return toks


def _group_by_stream(frames):
    by_seq = {}
    for f in frames:
        by_seq.setdefault(f.meta["stream_seq"], []).append(f)
    return by_seq


# ---------------------------------------------------------------------------
# Model-level: per-slot paged cache parity (bit-identical single occupant)
# ---------------------------------------------------------------------------
class TestSlotModelParity:
    @pytest.mark.parametrize("extra", [None, {
        "temperature": "0.8", "top_k": "7", "gen_seed": "3"}],
        ids=["greedy", "sampling"])
    def test_single_occupant_bit_parity(self, rng, extra):
        """An occupant in the MIDDLE slot of a 4-wide batch, decoded in
        mixed-length scans, is bit-equal to the one-shot generate:<N>
        tokens — and the decode step compiles once per scan length."""
        import jax.numpy as jnp

        from nnstreamer_tpu.models.transformer import build_slot_stream

        props = {k: str(v) for k, v in PROPS.items()}
        if extra:
            props.update(extra)
        prompt = rng.integers(0, 61, (1, 7)).astype(np.int32)
        n = 13
        want = _oneshot(prompt, n, extra)
        model, params, _ = build_slot_stream(props, 4)
        cache = model.init_cache()
        slot = np.int32(2)
        cache = model.reset_slot(cache, slot)
        cache, logits = model.prefill_fn(7)(params, cache, prompt, slot)
        t1 = model.pick_first(logits)
        got = [np.asarray(t1)[:, None]]
        tok = jnp.zeros((4,), jnp.int32).at[2].set(t1[0])
        gen = jnp.zeros((4,), jnp.int32).at[2].set(1)
        active = jnp.zeros((4,), jnp.int32).at[2].set(1)
        for k in (5, 4, 3):  # mixed scan buckets, 12 decode tokens
            cache, tok, gen, toks, _counts = model.decode_fn(k)(
                params, cache, tok, gen, active)
            got.append(np.asarray(toks)[2:3, :])
        np.testing.assert_array_equal(
            np.concatenate(got, axis=1), want)
        assert model.decode_compiles == 3  # one per distinct k, no churn

    def test_chunked_prefill_token_parity(self, rng):
        """A prompt prefilled in PIECES (interleaved-join path) yields
        the same tokens as the one-pass prefill oracle."""
        import jax.numpy as jnp

        from nnstreamer_tpu.models.transformer import build_slot_stream

        props = {k: str(v) for k, v in PROPS.items()}
        prompt = rng.integers(0, 61, (1, 20)).astype(np.int32)
        n = 8
        want = _oneshot(prompt, n)
        model, params, _ = build_slot_stream(props, 2)
        cache = model.reset_slot(model.init_cache(), np.int32(0))
        logits = None
        for lo in range(0, 20, 6):  # chunks 6,6,6,2
            piece = prompt[:, lo:lo + 6]
            cache, logits = model.prefill_fn(piece.shape[1])(
                params, cache, piece, np.int32(0))
        t1 = model.pick_first(logits)
        got = [np.asarray(t1)[:, None]]
        tok = jnp.zeros((2,), jnp.int32).at[0].set(t1[0])
        gen = jnp.zeros((2,), jnp.int32).at[0].set(1)
        active = jnp.zeros((2,), jnp.int32).at[0].set(1)
        cache, tok, gen, toks, _counts = model.decode_fn(n - 1)(
            params, cache, tok, gen, active)
        got.append(np.asarray(toks)[0:1])
        np.testing.assert_array_equal(np.concatenate(got, axis=1), want)

    def test_join_touches_only_its_slot(self, rng):
        """A joining stream's reset+prefill leaves every NEIGHBOR page
        bit-untouched (the leave/join page-reuse contract)."""
        import jax

        from nnstreamer_tpu.models.transformer import build_slot_stream

        props = {k: str(v) for k, v in PROPS.items()}
        model, params, _ = build_slot_stream(props, 3)
        cache = model.init_cache()
        # occupy slot 0 with a stream so its pages are non-trivial
        p0 = rng.integers(0, 61, (1, 9)).astype(np.int32)
        cache = model.reset_slot(cache, np.int32(0))
        cache, _ = model.prefill_fn(9)(params, cache, p0, np.int32(0))
        before = [np.array(leaf)[0] for leaf in jax.tree.leaves(cache)]
        # join slot 2: reset + prefill a different prompt
        p2 = rng.integers(0, 61, (1, 5)).astype(np.int32)
        cache = model.reset_slot(cache, np.int32(2))
        cache, _ = model.prefill_fn(5)(params, cache, p2, np.int32(2))
        after = [np.array(leaf)[0] for leaf in jax.tree.leaves(cache)]
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)


# ---------------------------------------------------------------------------
# Engine-level: scheduling, accounting, eviction (sim model — fast)
# ---------------------------------------------------------------------------
def _mk_engine(slots=2, vocab=97, chunk=4, step_ms=0.2, **kw):
    model = SimSlotModel(slots, vocab=vocab, step_base_ms=step_ms,
                         step_per_slot_ms=0.01, prefill_ms_per_token=0.01)
    eng = SlotEngine(model, None, max_seq=1 << 30, chunk=chunk,
                     name="test", **kw)
    eng.start()
    return eng, model


def _frame(prompt, **meta):
    return TensorFrame([prompt], meta=dict(meta))


def _drain(eng, until, timeout=20.0):
    out = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out.extend(eng.pop_ready())
        if until(out):
            return out
        eng.wait_progress(0.02)
    raise TimeoutError(f"engine drain timed out with {len(out)} chunks")


class TestSlotEngine:
    def test_concurrent_streams_exact_accounting(self, rng):
        """5 streams through 2 slots: every stream's tokens equal its
        oracle (zero cross-slot contamination), exact counters."""
        eng, model = _mk_engine(slots=2)
        try:
            prompts = [
                rng.integers(0, 97, (1, 5 + i)).astype(np.int32)
                for i in range(5)
            ]
            for p in prompts:
                eng.submit(_frame(p), p, max_new=11, chunk=4)
            outs = _drain(
                eng, lambda o: sum(
                    1 for _p, f in o if f.meta["final"]) >= 5)
            by_seq = _group_by_stream([f for _pad, f in outs])
            assert len(by_seq) == 5
            matched = 0
            for frames in by_seq.values():
                toks = _stream_tokens(frames)
                assert toks.shape == (1, 11)
                for p in prompts:
                    if np.array_equal(toks, sim_oracle(model, p, 11)):
                        matched += 1
                        break
            assert matched == 5
            snap = eng.snapshot()
            assert snap["gen_joins"] == 5
            assert snap["gen_completed"] == 5
            assert snap["gen_occupied"] == 0
            assert snap["gen_tokens"] == 55
        finally:
            eng.stop()

    def test_priority_wins_free_slot(self, rng):
        """With every slot busy, a later high-priority prompt beats an
        earlier low-priority one to the next free slot (PR-8 classes
        extend to slot admission)."""
        eng, model = _mk_engine(slots=1, step_ms=1.0)
        try:
            p0 = rng.integers(0, 97, (1, 4)).astype(np.int32)
            lo = rng.integers(0, 97, (1, 4)).astype(np.int32)
            hi = rng.integers(0, 97, (1, 4)).astype(np.int32)
            eng.submit(_frame(p0), p0, max_new=24, chunk=4)
            time.sleep(0.01)
            s_lo = eng.submit(_frame(lo), lo, max_new=4, chunk=4,
                              priority=0)
            s_hi = eng.submit(_frame(hi), hi, max_new=4, chunk=4,
                              priority=3)
            _drain(eng, lambda o: sum(
                1 for _p, f in o if f.meta["final"]) >= 3)
            assert s_hi.joined_ts is not None
            assert s_lo.joined_ts is not None
            assert s_hi.joined_ts <= s_lo.joined_ts
        finally:
            eng.stop()

    def test_cancel_frees_slot_immediately(self, rng):
        eng, model = _mk_engine(slots=1, step_ms=1.0)
        try:
            p = rng.integers(0, 97, (1, 4)).astype(np.int32)
            s = eng.submit(_frame(p, client_id=42), p,
                           max_new=10_000, chunk=4)
            _drain(eng, lambda o: len(o) >= 2)  # mid-decode
            assert eng.cancel(client_id=42)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if eng.snapshot()["gen_occupied"] == 0:
                    break
                time.sleep(0.01)
            snap = eng.snapshot()
            assert snap["gen_occupied"] == 0
            assert snap["gen_cancelled"] == 1
            assert s.state == "cancelled"
            # cancellation emits nothing further; engine drains clean
            assert eng.idle() or eng.pop_ready() is not None
        finally:
            eng.stop()

    def test_deadline_eviction_typed_expiry(self, rng):
        """A stream whose PR-2 deadline passes mid-decode is EVICTED:
        final chunk carries the typed-expiry meta, partial tokens are
        preserved and exactly oracle-prefix, the slot frees."""
        eng, model = _mk_engine(slots=1, step_ms=1.0)
        try:
            p = rng.integers(0, 97, (1, 4)).astype(np.int32)
            eng.submit(_frame(p), p, max_new=10_000, chunk=4,
                       deadline_ts=eng.clock() + 0.3)
            outs = _drain(
                eng, lambda o: any(f.meta["final"] for _p, f in o))
            frames = [f for _pad, f in outs]
            toks = _stream_tokens(frames)
            last = frames[-1].meta
            assert last["evicted"] == "deadline"
            assert last["deadline_expired"] is True
            assert 0 < toks.shape[1] < 10_000
            np.testing.assert_array_equal(
                toks, sim_oracle(model, p, toks.shape[1]))
            snap = eng.snapshot()
            assert snap["gen_evicted"] == 1
            assert snap["gen_occupied"] == 0
        finally:
            eng.stop()

    def test_token_budget_pace_eviction(self, rng):
        """token-budget-s: a stream slower than its per-token pace is
        evicted with the typed expiry (reason=token_budget)."""
        eng, model = _mk_engine(slots=1, step_ms=30.0,
                                token_budget_s=0.01)
        try:
            p = rng.integers(0, 97, (1, 4)).astype(np.int32)
            eng.submit(_frame(p), p, max_new=10_000, chunk=4)
            outs = _drain(
                eng, lambda o: any(f.meta["final"] for _p, f in o),
                timeout=30.0)
            last = [f for _p, f in outs][-1].meta
            assert last["evicted"] == "token_budget"
            assert eng.snapshot()["gen_evicted"] == 1
        finally:
            eng.stop()

    def test_zero_retrace_across_churn(self, rng):
        """Streams joining and leaving NEVER retrace the decode step:
        with chunk-aligned lengths there is exactly one decode bucket,
        however many streams churn through the slots."""
        eng, model = _mk_engine(slots=3, chunk=4)
        try:
            compiles_after_first = None
            for wave in range(3):
                prompts = [
                    rng.integers(0, 97, (1, 6)).astype(np.int32)
                    for _ in range(4)
                ]
                for p in prompts:
                    eng.submit(_frame(p), p, max_new=8, chunk=4)
                _drain(eng, lambda o: sum(
                    1 for _p, f in o if f.meta["final"]) >= 4)
                if compiles_after_first is None:
                    compiles_after_first = (
                        eng.snapshot()["gen_decode_compiles"])
            snap = eng.snapshot()
            assert snap["gen_completed"] == 12
            # the k-bucket set is fixed by (chunk, max_new); churn after
            # the first wave compiles NOTHING new
            assert snap["gen_decode_compiles"] == compiles_after_first <= 2
        finally:
            eng.stop()

    def test_jit_buckets_lru_bounded(self, rng):
        """Distinct prefill chunk lengths churn past the cap: live
        buckets stay bounded (gen_jit_buckets), work stays correct."""
        eng, model = _mk_engine(slots=1, chunk=4, jit_bucket_max=3)
        try:
            lens = [3, 5, 7, 9, 11, 13]
            for ln in lens:
                p = rng.integers(0, 97, (1, ln)).astype(np.int32)
                eng.submit(_frame(p), p, max_new=4, chunk=4)
            _drain(eng, lambda o: sum(
                1 for _p, f in o if f.meta["final"]) >= len(lens))
            assert eng.snapshot()["gen_jit_buckets"] <= 2 * 3
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# Element-level: single-occupant parity through the pipeline (satellite)
# ---------------------------------------------------------------------------
def _run_pipeline_stream(prompts, n, chunk, slots, fuse=True,
                         extra_custom=""):
    custom = CUSTOM + ("," + extra_custom if extra_custom else "")
    pipe = parse_pipeline(
        f"appsrc name=src ! tensor_generator slots={slots} "
        f"custom={custom} max-new={n} chunk={chunk} ! "
        "tensor_sink name=out", fuse=fuse,
    )
    pipe.start()
    for p in prompts:
        pipe["src"].push(p)
    pipe["src"].end_of_stream()
    pipe.wait(timeout=180)
    frames = pipe["out"].frames
    health = pipe.health()
    pipe.stop()
    gen_key = next(k for k in health if k.startswith("tensor_generator"))
    return frames, health[gen_key]


class TestSlottedElementParity:
    @pytest.mark.parametrize("fuse", [
        pytest.param(True, id="fused"),
        # tier-1 budget: ~18s second full compile; unfused slotted
        # bit-parity stays tier-1 via the prefix element-wiring [unfused]
        # pin, which drives the same unfused slotted dataplane
        pytest.param(False, marks=pytest.mark.slow, id="unfused"),
    ])
    def test_slotted_bit_identical_to_seed_paths(self, rng, fuse):
        """Slotted decode vs seed generate:<N> AND vs the unslotted
        streaming path: tokens and chunk meta bit-identical per stream,
        fused and unfused."""
        prompts = [rng.integers(0, 61, (1, 7)).astype(np.int32),
                   rng.integers(0, 61, (1, 5)).astype(np.int32)]
        n, chunk = 13, 4
        slotted, health = _run_pipeline_stream(prompts, n, chunk, slots=3,
                                               fuse=fuse)
        unslotted, _ = _run_pipeline_stream(prompts, n, chunk, slots=0,
                                            fuse=fuse)
        by_stream = _group_by_stream(slotted)
        assert len(by_stream) == 2
        want = [_oneshot(p, n) for p in prompts]
        got = []
        for frames in by_stream.values():
            toks = _stream_tokens(frames)
            # chunk sizing matches the unslotted path: chunk-aligned
            # with one tail
            sizes = [np.asarray(f.tensors[0]).shape[1]
                     for f in sorted(frames,
                                     key=lambda f: f.meta["chunk_index"])]
            assert sizes == [4, 4, 4, 1]
            got.append(toks)
        for w in want:
            assert any(np.array_equal(g, w) for g in got)
        # the unslotted frames agree too (transitive, but pin it)
        un_by = _group_by_stream(unslotted)
        un_toks = sorted(
            (_stream_tokens(f).tolist() for f in un_by.values()))
        assert un_toks == sorted(g.tolist() for g in got)
        assert health["gen_completed"] == 2
        assert health["gen_occupied"] == 0

    def test_sampling_parity_slotted(self, rng):
        """temperature/top-k sampling through shared slots stays
        bit-equal per stream to the one-shot path (per-slot key
        folding == per-step folding)."""
        prompts = [rng.integers(0, 61, (1, 4)).astype(np.int32),
                   rng.integers(0, 61, (1, 6)).astype(np.int32)]
        n = 9
        frames, _ = _run_pipeline_stream(
            prompts, n, 4, slots=2, extra_custom=SAMPLING)
        by_stream = _group_by_stream(frames)
        want = [
            _oneshot(p, n, {"temperature": "0.8", "top_k": "7",
                            "gen_seed": "3"})
            for p in prompts
        ]
        got = [_stream_tokens(f) for f in by_stream.values()]
        for w in want:
            assert any(np.array_equal(g, w) for g in got)

    def test_block_of_prompts_splits_into_streams(self, rng):
        """A pushed BLOCK of prompts becomes one slot stream per row."""
        prompts = rng.integers(0, 61, (2, 5)).astype(np.int32)
        pipe = parse_pipeline(
            f"appsrc name=src ! tensor_generator slots=2 custom={CUSTOM} "
            "max-new=6 chunk=4 ! tensor_sink name=out")
        pipe.start()
        pipe["src"].push_block(prompts)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=120)
        frames = pipe["out"].frames
        pipe.stop()
        by_stream = _group_by_stream(frames)
        assert len(by_stream) == 2
        want = [_oneshot(prompts[j:j + 1], 6) for j in range(2)]
        got = [_stream_tokens(f) for f in by_stream.values()]
        for w in want:
            assert any(np.array_equal(g, w) for g in got)

    def test_overrun_fails_loud_slotted(self, rng):
        prompt = rng.integers(0, 61, (1, 60)).astype(np.int32)
        pipe = parse_pipeline(
            f"appsrc name=src ! tensor_generator slots=2 custom={CUSTOM} "
            "max-new=32 chunk=8 ! tensor_sink name=out")
        pipe.start()
        pipe["src"].push(prompt)
        pipe["src"].end_of_stream()
        with pytest.raises(Exception, match="exceeds the model's seq"):
            pipe.wait(timeout=60)
        pipe.stop()


# ---------------------------------------------------------------------------
# Serving-level: many concurrent wire streams share the slots
# ---------------------------------------------------------------------------
def _stream_client(port, ct, prompt, results, key, timeout=120,
                   name=None):
    pipe = parse_pipeline(
        f"appsrc name=src ! tensor_query_client port={port} "
        f"connect-type={ct} stream=true timeout={timeout} ! "
        "tensor_sink name=out", name=name or f"cli{key}")
    pipe.start()
    pipe["src"].push(prompt)
    pipe["src"].end_of_stream()
    try:
        pipe.wait(timeout=timeout + 30)
        results[key] = list(pipe["out"].frames)
    finally:
        pipe.stop()


class TestMultiplexedServing:
    @pytest.mark.parametrize("ct", [
        # tier-1 budget: ~15s; same multiplex contract over a second
        # transport — grpc framing stays tier-1 via the remote-stream
        # roundtrip test, so only the tcp variant runs in tier-1
        pytest.param("grpc", marks=pytest.mark.slow),
        "tcp",
    ])
    def test_concurrent_streams_share_slots_exact(self, rng, ct,
                                                  module_leak_check):
        """N concurrent InvokeStream/tcp-stream clients multiplex into
        shared slots: per-stream tokens bit-equal to the seed one-shot
        path (zero cross-slot contamination), slots provably SHARED
        (tokens-per-step EWMA > 1), zero retraces."""
        n = 10
        sid = 761 if ct == "grpc" else 762
        server = parse_pipeline(
            f"tensor_query_serversrc name=ssrc id={sid} port=0 "
            f"connect-type={ct} ! "
            f"tensor_generator name=gen slots=3 custom={CUSTOM} "
            f"max-new={n} chunk=3 ! "
            f"tensor_query_serversink id={sid}")
        server.start()
        port = server["ssrc"].props["port"]
        try:
            prompts = [
                rng.integers(0, 61, (1, 4 + i)).astype(np.int32)
                for i in range(3)
            ]
            results = {}
            ts = [
                threading.Thread(
                    target=_stream_client,
                    args=(port, ct, p, results, i),
                    kwargs={"name": f"{ct}cli{i}"})
                for i, p in enumerate(prompts)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=180)
            gen_health = server.health()["gen"]
        finally:
            server.stop()
        assert sorted(results) == [0, 1, 2]
        for i, p in enumerate(prompts):
            toks = _stream_tokens(results[i])
            np.testing.assert_array_equal(toks, _oneshot(p, n))
        assert gen_health["gen_joins"] == 3
        assert gen_health["gen_completed"] == 3
        assert gen_health["gen_occupied"] == 0
        # slots were genuinely SHARED, not serialized
        assert gen_health["gen_tokens_per_step"] > 1.0
        assert gen_health["gen_decode_compiles"] <= 4

    def test_tcp_stream_single_answer_graph(self, rng, module_leak_check):
        """A non-streaming server graph under the raw-TCP 'S' message:
        exactly one answer per request (absent final closes), parity
        with the gRPC InvokeStream contract."""
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model, unregister_jax_model)

        register_jax_model("tstream_cb", lambda p, xs: [xs[0] * 3.0], None)
        try:
            server = parse_pipeline(
                "tensor_query_serversrc name=ssrc id=763 port=0 "
                "connect-type=tcp ! "
                "tensor_filter framework=jax-xla model=tstream_cb ! "
                "tensor_query_serversink id=763")
            server.start()
            port = server["ssrc"].props["port"]
            try:
                client = parse_pipeline(
                    f"appsrc name=src ! tensor_query_client port={port} "
                    "connect-type=tcp stream=true ! tensor_sink name=out")
                client.start()
                for i in range(4):
                    client["src"].push(np.float32([i]))
                client["src"].end_of_stream()
                client.wait(timeout=60)
                vals = [float(f.tensors[0][0])
                        for f in client["out"].frames]
                client.stop()
                assert vals == [0.0, 3.0, 6.0, 9.0]
            finally:
                server.stop()
        finally:
            unregister_jax_model("tstream_cb")


# ---------------------------------------------------------------------------
# Acceptance: chaos-tolerant e2e — join, finish, kill, deadline-evict
# ---------------------------------------------------------------------------
@pytest.mark.chaos
class TestContinuousBatchingChaos:
    def test_join_kill_evict_exact_accounting(self, rng,
                                              module_leak_check):
        """The tentpole verdict: concurrent streams join shared slots,
        one finishes, one is KILLED mid-decode (client vanishes), one is
        DEADLINE-EVICTED (typed expiry with partial tokens) — exact
        per-stream token accounting against the sim oracle, zero
        cross-slot contamination, every slot freed, counters exact."""
        sim = SimSlotModel(2, vocab=997)  # oracle twin of the server's
        # ~2ms/token: a full stream takes ~8s+ — longer than BOTH the
        # 0.5s eviction budget AND the ~5s a hard client stop takes to
        # close its held stream socket (the kill must land mid-decode)
        n = 4000
        custom = ("sim:1,sim_step_ms:2.0,sim_per_slot_ms:0.05,"
                  "sim_prefill_ms:0.02,vocab:997")
        server = parse_pipeline(
            "tensor_query_serversrc name=ssrc id=764 port=0 "
            "connect-type=tcp ! "
            f"tensor_generator name=gen slots=2 custom={custom} "
            f"max-new={n} chunk=4 ! "
            "tensor_query_serversink id=764")
        server.start()
        port = server["ssrc"].props["port"]
        try:
            p_fin = rng.integers(0, 997, (1, 5)).astype(np.int32)
            p_kill = rng.integers(0, 997, (1, 6)).astype(np.int32)
            p_evict = rng.integers(0, 997, (1, 7)).astype(np.int32)
            results = {}

            # finisher: normal stream, completes its 40 tokens
            t_fin = threading.Thread(
                target=_stream_client,
                args=(port, "tcp", p_fin, results, "fin"),
                kwargs={"name": "chaos-fin"})
            t_fin.start()

            # victim: killed after >= 2 chunks (hard client stop)
            victim = parse_pipeline(
                f"appsrc name=src ! tensor_query_client port={port} "
                "connect-type=tcp stream=true timeout=60 ! "
                "tensor_sink name=out", name="chaos-victim")
            victim.start()
            victim["src"].push(p_kill)
            deadline = time.monotonic() + 30
            while (len(victim["out"].frames) < 2
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            kill_chunks = len(victim["out"].frames)
            assert kill_chunks >= 2
            victim_frames = list(victim["out"].frames)
            victim.stop()  # mid-decode kill

            # deadline victim (started AFTER the kill so a freed slot is
            # coming): budget far below the full generation's decode time
            evict = parse_pipeline(
                f"appsrc name=src ! tensor_query_client name=q "
                f"port={port} connect-type=tcp stream=true timeout=0.5 "
                "retries=0 ! tensor_sink name=out", name="chaos-evict")
            evict.start()
            evict["src"].push(p_evict)
            evict["src"].end_of_stream()
            try:
                evict.wait(timeout=30)
            except Exception:
                pass  # a lost eviction race surfaces as client timeout
            evict_frames = list(evict["out"].frames)
            evict_health = evict.health()["q"]
            evict.stop()

            t_fin.join(timeout=120)

            # engine settles: kill-cancel feedback frees the slot
            deadline = time.monotonic() + 20
            gen_health = server.health()["gen"]
            while time.monotonic() < deadline:
                gen_health = server.health()["gen"]
                if (gen_health["gen_occupied"] == 0
                        and gen_health["gen_waiting"] == 0):
                    break
                time.sleep(0.02)
        finally:
            server.stop()

        # finisher: exact full completion
        toks = _stream_tokens(results["fin"])
        np.testing.assert_array_equal(toks, sim_oracle(sim, p_fin, n))

        # killed stream: the chunks that DID arrive are an exact oracle
        # prefix (no contamination before the kill)
        got = np.concatenate(
            [np.asarray(f.tensors[0]) for f in victim_frames
             if f.tensors], axis=1)
        np.testing.assert_array_equal(
            got, sim_oracle(sim, p_kill, got.shape[1]))

        # evicted stream: typed expiry, partial tokens exact.  How many
        # tokens land before the budget blows depends on when the killed
        # victim's slot frees (cancel-feedback detection is ~0.1s but
        # races the 0.5s budget on a slow box) — zero tokens is a LEGAL
        # outcome of that race (the engine logs "evicted after 0
        # token(s)" and the final marker is tensor-less; the chaos
        # harness's check_exact tolerates it the same way).  What is
        # deterministic: the typed-expiry answer, exact tokens_done
        # accounting, and oracle-prefix integrity of whatever DID land.
        assert evict_frames, "eviction must ANSWER the stream"
        last = evict_frames[-1].meta
        assert last["final"] is True
        assert last["evicted"] == "deadline"
        assert last["deadline_expired"] is True
        etok_arrays = [
            np.asarray(f.tensors[0]) for f in evict_frames if f.tensors
        ]
        n_etoks = sum(a.shape[1] for a in etok_arrays)
        assert n_etoks < n  # the budget really cut the stream short
        if etok_arrays:
            etoks = np.concatenate(etok_arrays, axis=1)
            np.testing.assert_array_equal(
                etoks, sim_oracle(sim, p_evict, etoks.shape[1]))
        assert n_etoks == last["tokens_done"]
        assert evict_health["deadline_expired"] >= 1

        # server-side verdict: every slot freed, counters exact.  The
        # evict stream JOINS only when a slot freed inside its budget:
        # delivered tokens imply a join; a waiting-queue eviction
        # legally leaves joins at 2 (same race as above).
        assert gen_health["gen_occupied"] == 0
        assert gen_health["gen_joins"] in ((3,) if n_etoks else (2, 3))
        assert gen_health["gen_completed"] == 1
        assert gen_health["gen_evicted"] == 1
        assert gen_health["gen_cancelled"] == 1
        assert gen_health["gen_decode_compiles"] <= 4
