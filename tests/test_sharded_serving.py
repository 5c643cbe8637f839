"""Sharded serving: one logical tensor_filter spread across a device mesh
via the first-class ``mesh=`` prop (legacy ``mesh_*`` custom props still
accepted): params sharded by parallel/sharding.py rules and staged across
the whole mesh, ``invoke``/``invoke_batch`` compiled under NamedSharding
in/out specs, micro-batches scattered over dp, XLA SPMD collectives.

Reference analog: none — the reference fans *streams* out over
nnstreamer-edge (SURVEY §2.3); intra-model sharding of serving is
TPU-native net-new.  Runs on the conftest 8-device CPU mesh.
"""

import time

import jax
import numpy as np
import pytest

from nnstreamer_tpu.backends.base import find_backend
from nnstreamer_tpu.backends.jax_xla import (
    register_jax_model,
    unregister_jax_model,
)
from nnstreamer_tpu.core.buffer import DeviceBufferPool
from nnstreamer_tpu.core.resilience import FAULTS
from nnstreamer_tpu.elements.filter import SingleShot
from nnstreamer_tpu.parallel.mesh import mesh_spec_str, parse_mesh_spec
from nnstreamer_tpu.pipeline import parse_pipeline

TRANSFORMER = "arch:transformer,dtype:float32,vocab:64,d_model:32,heads:2,layers:2,d_ff:64,seq:16,seed:7"


def _tokens(rng, n, t=16):
    return rng.integers(0, 64, (n, t)).astype(np.int32)


def test_sharded_matches_unsharded(rng):
    toks = _tokens(rng, 8)
    with SingleShot(
        framework="jax-xla", model="zoo", custom=TRANSFORMER
    ) as plain:
        want = np.asarray(plain.invoke_batch([toks])[0])
    with SingleShot(
        framework="jax-xla",
        model="zoo",
        custom=TRANSFORMER + ",mesh_dp:2,mesh_tp:2",
    ) as sharded:
        be = sharded.backend
        assert be._mesh is not None and be._mesh.shape["dp"] == 2
        # params actually landed sharded: at least one leaf spans >1 device
        spans = [
            len(leaf.sharding.device_set)
            for leaf in jax.tree.leaves(be._params)
        ]
        assert max(spans) > 1, "no parameter is sharded across devices"
        got = np.asarray(sharded.invoke_batch([toks])[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_sharded_odd_batch_bucketing(rng):
    """Batch not divisible by dp: bucket pads to an even scatter and
    slices back."""
    toks = _tokens(rng, 5)
    with SingleShot(
        framework="jax-xla",
        model="zoo",
        custom=TRANSFORMER + ",mesh_dp:4",
    ) as s:
        out = np.asarray(s.invoke_batch([toks])[0])
    assert out.shape[0] == 5


def test_sharded_single_invoke_replicates(rng):
    toks = _tokens(rng, 1)[0]
    with SingleShot(
        framework="jax-xla",
        model="zoo",
        custom=TRANSFORMER + ",mesh_dp:2,mesh_tp:2",
    ) as s:
        out = np.asarray(s.invoke([toks])[0])
    assert out.shape == (16, 64)


def test_sharded_pipeline_end_to_end(rng):
    """Full streaming pipeline over a sharded filter: appsrc -> filter
    (mesh dp×tp, micro-batched) -> sink; outputs match the unsharded
    pipeline frame-for-frame."""
    frames = [_tokens(rng, 1)[0] for _ in range(8)]

    def run(custom):
        pipe = parse_pipeline(
            "appsrc name=src ! "
            f"tensor_filter framework=jax-xla model=zoo custom={custom} "
            "max-batch=4 batch-timeout=50 ! "
            "tensor_sink name=out",
            name="sharded-serve",
        )
        pipe.start()
        for f in frames:
            pipe["src"].push(f)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=120)
        outs = [np.asarray(f.tensors[0]) for f in pipe["out"].frames]
        pipe.stop()
        return outs

    plain = run(TRANSFORMER)
    sharded = run(TRANSFORMER + ",mesh_dp:2,mesh_tp:2")
    assert len(plain) == len(sharded) == 8
    for a, b in zip(plain, sharded):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)


def test_sharded_generation_matches_unsharded(rng):
    """mesh_* props compose with generate:<N>: the KV-cache decode loop
    runs under GSPMD with tp-sharded params; tokens must be identical."""
    toks = _tokens(rng, 4, t=8)
    with SingleShot(
        framework="jax-xla", model="zoo", custom=TRANSFORMER + ",generate:3"
    ) as plain:
        want = np.asarray(plain.invoke_batch([toks])[0])
    with SingleShot(
        framework="jax-xla",
        model="zoo",
        custom=TRANSFORMER + ",generate:3,mesh_dp:2,mesh_tp:2",
    ) as sharded:
        got = np.asarray(sharded.invoke_batch([toks])[0])
    assert want.shape == (4, 11)
    np.testing.assert_array_equal(got, want)


def _setup_module_guard():
    # fail fast if the zoo alias used above ever changes
    assert find_backend("jax-xla") is not None


_setup_module_guard()


# ---------------------------------------------------------------------------
# mesh= config grammar (parallel/mesh.py — the ONE grammar every surface
# shares: filter/generator props, jax-xla backend)
# ---------------------------------------------------------------------------
class TestMeshSpecGrammar:
    def test_parse_valid(self):
        assert parse_mesh_spec("tp:4") == {"tp": 4}
        assert parse_mesh_spec("dp:2,tp:2") == {"dp": 2, "tp": 2}
        assert parse_mesh_spec(" DP:2 , tp:-1 ") == {"dp": 2, "tp": -1}
        for empty in ("", "0", "off", "none"):
            assert parse_mesh_spec(empty) == {}

    @pytest.mark.parametrize("bad", [
        "xp:2",          # unknown axis
        "tp",            # no size
        "tp:two",        # non-integer
        "tp:0",          # zero
        "tp:-2",         # below -1
        "tp:2,tp:4",     # duplicate
        "dp:-1,tp:-1",   # two wildcards
    ])
    def test_parse_invalid_is_loud(self, bad):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)

    def test_canonical_string(self):
        assert mesh_spec_str({}) == "0"
        assert mesh_spec_str({"tp": 2, "dp": 4}) == "dp:4,tp:2"

    def test_filter_refuses_bad_spec_at_start(self):
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=passthrough "
            "mesh=xp:2 ! tensor_sink name=out")
        with pytest.raises(Exception, match="unknown axis"):
            pipe.start()
        pipe.stop()

    def test_filter_refuses_meshless_backend(self):
        """A backend that would silently ignore mesh= is refused loudly
        (passthrough has no mesh support)."""
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=passthrough "
            "mesh=tp:2 ! tensor_sink name=out")
        with pytest.raises(Exception, match="does not support mesh"):
            pipe.start()
        pipe.stop()


# ---------------------------------------------------------------------------
# 1-device-mesh bit parity: the full sharded machinery (NamedSharding
# in/out compile, scatter path, replicate-on-invoke) with zero parallelism
# to hide behind — outputs must be BIT-identical to the unsharded backend
# ---------------------------------------------------------------------------
class TestOneDeviceMeshBitParity:
    def test_invoke_and_batch_bit_identical(self, rng):
        toks_b = _tokens(rng, 4)
        toks_1 = _tokens(rng, 1)[0]
        with SingleShot(framework="jax-xla", model="zoo",
                        custom=TRANSFORMER) as plain:
            want_b = np.asarray(plain.invoke_batch([toks_b])[0])
            want_1 = np.asarray(plain.invoke([toks_1])[0])
        with SingleShot(framework="jax-xla", model="zoo",
                        custom=TRANSFORMER, mesh="dp:1") as sharded:
            assert sharded.backend._mesh is not None
            got_b = np.asarray(sharded.invoke_batch([toks_b])[0])
            got_1 = np.asarray(sharded.invoke([toks_1])[0])
        np.testing.assert_array_equal(got_b, want_b)
        np.testing.assert_array_equal(got_1, want_1)

    def test_generation_bit_identical(self, rng):
        toks = _tokens(rng, 2, t=8)
        with SingleShot(framework="jax-xla", model="zoo",
                        custom=TRANSFORMER + ",generate:3") as plain:
            want = np.asarray(plain.invoke_batch([toks])[0])
        with SingleShot(framework="jax-xla", model="zoo",
                        custom=TRANSFORMER + ",generate:3",
                        mesh="tp:1") as sharded:
            got = np.asarray(sharded.invoke_batch([toks])[0])
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("fuse", [True, False])
    def test_pipeline_bit_identical_fused_and_unfused(self, rng, fuse):
        """Streaming parity in BOTH dataplanes: micro-batched serving
        over a 1-device mesh is bit-identical to unsharded, and the
        sharded outputs really ride the async dispatch window."""
        frames = [_tokens(rng, 1)[0] for _ in range(6)]

        def run(mesh_tok):
            pipe = parse_pipeline(
                "appsrc name=src ! "
                f"tensor_filter name=f framework=jax-xla model=zoo "
                f"custom={TRANSFORMER} {mesh_tok}"
                "max-batch=3 batch-timeout=50 ! tensor_sink name=out",
                name="mesh1p",
                fuse=fuse,
            )
            pipe.start()
            for f in frames:
                pipe["src"].push(f)
            pipe["src"].end_of_stream()
            pipe.wait(timeout=120)
            outs = [np.asarray(f.tensors[0]) for f in pipe["out"].frames]
            win_async = pipe["f"]._win_async
            health = pipe.health()["f"]
            pipe.stop()
            return outs, win_async, health

        plain, _, _ = run("")
        sharded, win_async, health = run("mesh=dp:1 ")
        assert len(plain) == len(sharded) == 6
        for a, b in zip(plain, sharded):
            np.testing.assert_array_equal(b, a)
        # sharded jax outputs keep the async-window capability
        assert win_async is True
        # mesh facts are in health() (exported as nns.mesh.* by the
        # telemetry collector)
        assert health["mesh_devices"] == 1
        assert health["mesh_dp"] == 1 and health["mesh_axes"] == "dp:1"


# ---------------------------------------------------------------------------
# tensor_query e2e (acceptance): a tp-/dp-sharded model serves through
# BOTH transports; tokens bit-identical to the unsharded server
# ---------------------------------------------------------------------------
class TestShardedQueryServing:
    @pytest.mark.parametrize("transport", ["tcp", "grpc"])
    def test_sharded_generation_served_bit_identical(self, rng, transport):
        gen = TRANSFORMER + ",generate:3"
        prompts = [_tokens(rng, 1, t=8)[0] for _ in range(4)]

        def serve(mesh_tok, sid):
            server = parse_pipeline(
                f"tensor_query_serversrc name=ssrc id={sid} port=0 "
                f"connect-type={transport} ! "
                f"tensor_filter framework=jax-xla model=zoo "
                f"custom={gen} {mesh_tok}max-batch=2 batch-timeout=30 ! "
                f"tensor_query_serversink id={sid}",
                name=f"shq{sid}",
            )
            server.start()
            port = server["ssrc"].props["port"]
            client = parse_pipeline(
                f"appsrc name=src ! tensor_query_client port={port} "
                f"connect-type={transport} ! tensor_sink name=out",
                name=f"shqc{sid}",
            )
            client.start()
            try:
                for p in prompts:
                    client["src"].push(p)
                client["src"].end_of_stream()
                client.wait(timeout=120)
                outs = [np.asarray(f.tensors[0])
                        for f in client["out"].frames]
                mesh_health = {
                    k: v for k, v in server.health().get(
                        "tensor_filter0", server.health().get("f", {})
                    ).items() if k.startswith("mesh_")
                } if mesh_tok else {}
            finally:
                client.stop()
                server.stop()
            return outs, mesh_health

        plain, _ = serve("", 571 if transport == "tcp" else 573)
        sharded, _ = serve(
            "mesh=dp:2,tp:2 ", 572 if transport == "tcp" else 574)
        assert len(plain) == len(sharded) == 4
        for a, b in zip(plain, sharded):
            # greedy token generation: the served completions must be
            # the SAME tokens (proven stable on this mesh/model size by
            # test_sharded_generation_matches_unsharded)
            np.testing.assert_array_equal(b, a)


# ---------------------------------------------------------------------------
# Atomic sharded hot swap: staging covers the WHOLE mesh before the
# pointer exchange; every failure mode keeps the old mesh serving
# ---------------------------------------------------------------------------
#: two versions of a tiny REAL-params model whose kernel path matches the
#: transformer tp rules (mlp/up/kernel -> sharded on dim 1 over tp)
def _mesh_swap_model(scale: float):
    kernel = np.full((4, 8), scale, np.float32)

    def fn(p, xs):
        return [xs[0] @ p["mlp"]["up"]["kernel"]]

    return fn, {"mlp": {"up": {"kernel": kernel}}}


@pytest.fixture
def _swap_models():
    FAULTS.reset()
    for name, scale in (("shard_m1", 0.5), ("shard_m2", 1.25)):
        fn, params = _mesh_swap_model(scale)
        register_jax_model(name, fn, params)
    yield
    FAULTS.reset()
    unregister_jax_model("shard_m1")
    unregister_jax_model("shard_m2")


def _swap_pipe(extra: str = ""):
    pipe = parse_pipeline(
        "appsrc name=src ! tensor_filter name=f framework=jax-xla "
        "model=shard_m1 mesh=dp:2,tp:2 is-updatable=true "
        f"max-batch=2 batch-timeout=20 {extra}! tensor_sink name=out",
        name="meshswap",
    )
    pipe.start()
    return pipe


def _wait_outs(pipe, n, timeout=30.0):
    t0 = time.time()
    while len(pipe["out"].frames) < n and time.time() - t0 < timeout:
        time.sleep(0.01)
    assert len(pipe["out"].frames) >= n, (
        f"{len(pipe['out'].frames)}/{n} outputs")


def _vals(pipe):
    return [float(np.asarray(f.tensors[0])[0]) for f in pipe["out"].frames]


class TestShardedHotSwap:
    OLD = 4 * 0.5   # x @ K with x = ones(4): each out elem = sum * scale
    NEW = 4 * 1.25

    def test_staged_swap_is_atomic_across_the_mesh(self, _swap_models):
        """The swap is ONE pointer exchange after the new params landed
        on every mesh device: outputs are bit-exactly the old model's
        before it and the new model's after — never a torn mix."""
        pipe = _swap_pipe()
        try:
            for _ in range(4):
                pipe["src"].push(np.ones((4,), np.float32))
            _wait_outs(pipe, 4)
            ticket = pipe.reload_model("f", "shard_m2")
            assert ticket.wait_staged(30) and ticket.ok, ticket.error
            for _ in range(4):
                pipe["src"].push(np.ones((4,), np.float32))
            assert ticket.wait_applied(10)
            pipe["src"].end_of_stream()
            pipe.wait(30)
            h = pipe.health()["f"]
            assert h["swaps"] == 1 and h["swap_failures"] == 0
            assert h["restarts"] == 0
            assert h["mesh_devices"] == 4  # still the same serving mesh
            vals = _vals(pipe)
            assert vals[:4] == [self.OLD] * 4
            assert vals[4:] == [self.NEW] * 4
            # no torn half-mesh state: every output is exactly one
            # model's — a partially-staged mesh would produce neither
            assert all(v in (self.OLD, self.NEW) for v in vals)
            # the ACTIVE backend's params are genuinely sharded across
            # the mesh (the staged instance inherited the mesh config)
            spans = [
                len(leaf.sharding.device_set)
                for leaf in jax.tree.leaves(pipe["f"].backend._params)
            ]
            assert max(spans) > 1
        finally:
            pipe.stop()

    def test_staging_failure_keeps_old_mesh_serving(self, _swap_models):
        pipe = _swap_pipe()
        try:
            FAULTS.arm("filter.reload.load",
                       exc=RuntimeError("injected sharded staging fault"))
            pipe["src"].push(np.ones((4,), np.float32))
            ticket = pipe.reload_model("f", "shard_m2")
            assert ticket.wait_staged(30)
            assert not ticket.ok and ticket.state == "failed"
            pipe["src"].push(np.ones((4,), np.float32))
            pipe["src"].end_of_stream()
            pipe.wait(30)
            h = pipe.health()["f"]
            assert h["swap_failures"] == 1 and h["swaps"] == 0
            assert h["restarts"] == 0
            assert _vals(pipe) == [self.OLD] * 2  # old mesh, zero loss
        finally:
            pipe.stop()

    def test_post_swap_burst_rolls_back_to_old_mesh(self, _swap_models):
        """Observation-window rollback restores the RETAINED old sharded
        backend: the faulted frames are served by it (zero loss), the
        failed mesh backend is discarded."""
        pipe = _swap_pipe(
            extra="observation-window=60 rollback-error-burst=2 ")
        try:
            pipe["src"].push(np.ones((4,), np.float32))
            _wait_outs(pipe, 1)
            ticket = pipe.reload_model("f", "shard_m2")
            assert ticket.wait_staged(30) and ticket.ok, ticket.error
            FAULTS.arm("filter.reload.post",
                       exc=RuntimeError("new sharded model is broken"))
            for _ in range(4):
                pipe["src"].push(np.ones((4,), np.float32))
            pipe["src"].end_of_stream()
            pipe.wait(30)
            h = pipe.health()["f"]
            assert h["swaps"] == 1 and h["rollbacks"] == 1
            assert h["model_version"] == 0 and h["restarts"] == 0
            assert ticket.state == "rolled-back"
            # zero frame loss: every post-swap frame was served by the
            # retained OLD sharded backend
            assert _vals(pipe) == [self.OLD] * 5
        finally:
            pipe.stop()


# ---------------------------------------------------------------------------
# Sharded-aware feed & pooling
# ---------------------------------------------------------------------------
class TestShardedFeedAndPool:
    def test_device_pool_placement_domains_never_cross(self):
        """Regression pin (satellite bugfix): two placements cycling the
        SAME (shape, dtype) never exchange buffers — a replicated
        carcass is never handed to a dp-sharded caller."""
        pool = DeviceBufferPool(max_per_key=4)
        a = pool.acquire((8,), np.float32, placement=("mesh", "dp:2"))
        pool.release(a, placement=("mesh", "dp:2"))
        b = pool.acquire((8,), np.float32, placement=("dev", "cpu", 0))
        assert b is not a, "buffer crossed placement domains"
        pool.release(b, placement=("dev", "cpu", 0))
        # same-domain reuse still works, per domain
        a2 = pool.acquire((8,), np.float32, placement=("mesh", "dp:2"))
        b2 = pool.acquire((8,), np.float32, placement=("dev", "cpu", 0))
        assert a2 is a and b2 is b
        assert pool.reused == 2 and pool.allocated == 2
        # release must key on the SAME token (derived per call)
        pool.release(a2, placement=("mesh", "dp:2"))
        assert pool.acquire((8,), np.float32) is not a2  # no-placement ring

    def test_staging_placement_tokens_distinguish_mesh_from_device(self):
        with SingleShot(framework="jax-xla", model="zoo",
                        custom=TRANSFORMER) as plain, \
                SingleShot(framework="jax-xla", model="zoo",
                           custom=TRANSFORMER, mesh="dp:2") as sharded:
            t_plain = plain.backend.staging_placement()
            t_shard = sharded.backend.staging_placement()
        assert t_plain is not None and t_shard is not None
        assert t_plain != t_shard
        assert t_shard[0] == "mesh" and "dp:2" in t_shard[1]

    def test_ingest_lane_stages_to_sharded_layout(self, rng):
        """Host frames through the staging lane land DIRECTLY in the dp
        NamedSharding (one scatter on the lane thread, none on
        dispatch), odd tail batches pad to the dp-divisible bucket, and
        outputs stay bit-identical to unsharded serving."""
        frames = [_tokens(rng, 1)[0] for _ in range(6)]

        def run(mesh_tok):
            pipe = parse_pipeline(
                "appsrc name=src ! "
                f"tensor_filter name=f framework=jax-xla model=zoo "
                f"custom={TRANSFORMER} {mesh_tok}ingest-lane=on "
                "max-batch=4 batch-timeout=30 ! tensor_sink name=out",
                name="meshlane",
            )
            pipe.start()
            for f in frames:
                pipe["src"].push(np.asarray(f))  # host frames: lane path
            pipe["src"].end_of_stream()
            pipe.wait(timeout=120)
            outs = [np.asarray(f.tensors[0]) for f in pipe["out"].frames]
            be = pipe["f"].backend
            scatters = getattr(be, "mesh_scatters", 0)
            pipe.stop()
            return outs, scatters

        plain, _ = run("")
        sharded, scatters = run("mesh=dp:4 ")
        assert len(plain) == len(sharded) == 6
        for a, b in zip(plain, sharded):
            np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-4)
        # the lane really scattered host batches onto the mesh (incl.
        # the padded 2->4 tail)
        assert scatters >= 2

    def test_window_readiness_means_all_shards_not_shard_zero(self):
        """CompletionWindow contract on a mesh: a parked batch whose
        shard 0 completed but shard 1 did not is NOT ready — no output
        may emit until EVERY shard landed."""
        pipe = parse_pipeline(
            "appsrc name=src max-buffers=64 ! tensor_filter name=f "
            "framework=async-sim custom=manual:1,mesh_dp:2 "
            "max-batch=2 batch-timeout=10 dispatch-depth=4 ! "
            "tensor_sink name=out",
            name="meshwin",
        )
        pipe.start()
        try:
            be = pipe["f"].backend
            pipe["src"].push(np.float32([1.0]))
            pipe["src"].push(np.float32([2.0]))
            # wait for the batch to be dispatched to both shard servers
            t0 = time.time()
            while time.time() - t0 < 10:
                with be._cv:
                    if (len(be._pending) >= 2 and be._pending[0]
                            and be._pending[1]):
                        break
                time.sleep(0.01)
            assert be.release_one(0)   # shard 0 completes...
            time.sleep(0.4)
            assert len(pipe["out"].frames) == 0, (
                "output emitted with only shard 0 ready")
            assert be.release_one(1)   # ...now ALL shards are ready
            _wait_outs(pipe, 2)
            vals = sorted(
                float(np.asarray(f.tensors[0])[0])
                for f in pipe["out"].frames)
            assert vals == [3.0, 5.0]  # y = 2x + 1
        finally:
            pipe["src"].end_of_stream()
            pipe.stop()


# ---------------------------------------------------------------------------
# Sharded continuous batching (slot engine under the mesh)
# ---------------------------------------------------------------------------
class TestShardedSlotEngine:
    def test_single_occupant_parity_vs_generate(self, rng):
        """A tp-sharded slot engine's single occupant is bit-identical
        to seed ``generate:<N>`` one-shot serving."""
        prompt = _tokens(rng, 1, t=8)
        with SingleShot(framework="jax-xla", model="zoo",
                        custom=TRANSFORMER + ",generate:5") as ss:
            want = np.asarray(ss.invoke_batch([prompt])[0])  # (1, 13)
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_generator name=gen slots=2 "
            f"mesh=tp:2 custom={TRANSFORMER} max-new=5 chunk=2 ! "
            "tensor_sink name=out",
            name="meshslot",
        )
        pipe.start()
        try:
            pipe["src"].push(prompt)
            pipe["src"].end_of_stream()
            pipe.wait(timeout=120)
            toks = np.concatenate(
                [np.asarray(f.tensors[0]) for f in pipe["out"].frames
                 if f.tensors], axis=1)
            h = pipe.health()["gen"]
        finally:
            pipe.stop()
        np.testing.assert_array_equal(toks, want[:, 8:])
        assert h["gen_completed"] == 1
        assert h["mesh_tp"] == 2 and h["mesh_devices"] == 2

    def test_generator_mesh_requires_slots_and_tp_only(self):
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_generator slots=0 mesh=tp:2 "
            f"custom={TRANSFORMER} ! tensor_sink name=out")
        with pytest.raises(Exception, match="slots >= 1"):
            pipe.start()
        pipe.stop()
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_generator slots=2 mesh=dp:2 "
            f"custom={TRANSFORMER} ! tensor_sink name=out")
        with pytest.raises(Exception, match="tp only"):
            pipe.start()
        pipe.stop()


# ---------------------------------------------------------------------------
# JIT-cache hygiene: the backend compile cache is LRU-bounded (shared
# core/slots.lru_bucket discipline) so a mesh-/flex-shape sweep cannot
# grow tracing caches unbounded
# ---------------------------------------------------------------------------
def test_sharded_jit_cache_bounded_under_shape_sweep():
    register_jax_model("shard_sweep", lambda p, xs: [xs[0] * 2.0], None)
    try:
        with SingleShot(framework="jax-xla", model="shard_sweep",
                        mesh="dp:2") as s:
            be = s.backend
            cap = be.JIT_CACHE_MAX
            for n in range(1, cap + 20):
                out = s.invoke([np.full((n,), 1.0, np.float32)])
                assert float(np.asarray(out[0])[0]) == 2.0
            assert len(be._jit_cache) <= cap, (
                f"compile cache grew to {len(be._jit_cache)} > {cap}")
            # evicted shapes retrace transparently
            out = s.invoke([np.full((1,), 3.0, np.float32)])
            assert float(np.asarray(out[0])[0]) == 6.0
    finally:
        unregister_jax_model("shard_sweep")
