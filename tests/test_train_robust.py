"""Crash-safe in-pipeline training (ISSUE 19): the kill/resume truth
table, trainer-thread supervision, the gated-promotion loop, memory-
pressure pause, the truncated-repo-prefix e2e, the co-hosted serving
perf floor, and the `--mode train` chaos acceptance smoke."""

import json
import os
import time

import numpy as np
import pytest

from nnstreamer_tpu.core import checkpoint as ckpt
from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.resilience import FAULTS, TransientError
from nnstreamer_tpu.pipeline import parse_pipeline
from nnstreamer_tpu.pipeline.element import ElementError

N, B, CLASSES = 16, 8, 4           # 2 optimizer steps per epoch
STEPS_PER_EPOCH = N // B
CFG = {
    "arch": "mnist_cnn", "arch_props": {"classes": str(CLASSES)},
    "optimizer": "adam", "learning_rate": 3e-3,
    "batch_size": B, "loss": "softmax_ce",
}


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    FAULTS.reset()


def _make_frames(n=N, seed=0):
    """Deterministic learnable banded images (class = bright band)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        label = i % CLASSES
        img = rng.normal(0.2, 0.05, (28, 28, 1)).astype(np.float32)
        img[label * 5 : label * 5 + 4, :, :] += 0.8
        out.append((img, np.int32([label])))
    return out


def _write_repo(dirpath, frames, claim=None, truncate_bytes=0):
    """Flat-binary datarepo + meta (the datareposink layout), directly."""
    data_path = os.path.join(dirpath, "data.bin")
    json_path = os.path.join(dirpath, "data.json")
    blob = b"".join(img.tobytes() + lab.tobytes() for img, lab in frames)
    if truncate_bytes:
        blob = blob[:-truncate_bytes]
    with open(data_path, "wb") as f:
        f.write(blob)
    sample_size = frames[0][0].nbytes + frames[0][1].nbytes
    with open(json_path, "w") as f:
        json.dump({
            "tensors": ["float32:1:28:28", "int32:1"],  # innermost-first dims
            "total_samples": claim or len(frames),
            "sample_size": sample_size,
        }, f)
    return data_path, json_path


def _templates():
    import jax
    import optax

    from nnstreamer_tpu import models as zoo

    fn, params, _, _ = zoo.build("mnist_cnn", {"classes": str(CLASSES)})
    opt = jax.jit(optax.adam(CFG["learning_rate"]).init)(params)
    return fn, params, opt


# ---------------------------------------------------------------------------
# Kill/resume truth table (backend grain): fault BEFORE the checkpoint
# write, INSIDE the torn-save gap, and on a train step AFTER a durable
# checkpoint — resume must land on the newest durable step, retrain
# nothing, and end bit-identical to an uninterrupted control run.
# ---------------------------------------------------------------------------
class TestKillResumeTruthTable:
    EPOCHS = 2

    def _run(self, ck_dir, frames, resume=False):
        from nnstreamer_tpu.trainer.jax_trainer import JaxTrainer

        tr = JaxTrainer()
        tr.create({
            "model-config": json.dumps(CFG), "num-inputs": 1,
            "num-labels": 1, "num-training-samples": N,
            "num-validation-samples": 0, "epochs": self.EPOCHS,
            "checkpoint-path": ck_dir, "checkpoint-interval": 1,
            "checkpoint-keep": 0, "resume": resume,
        })
        tr.start()
        for ep in range(self.EPOCHS):
            for i in range(N):
                fr = TensorFrame([frames[i][0], frames[i][1]])
                fr.meta["epoch"] = ep
                fr.meta["sample_index"] = i
                tr.push_data(fr)
        tr.end_of_data()
        tr._thread.join(timeout=300)
        return tr

    @pytest.fixture(scope="class")
    def control(self, tmp_path_factory):
        import jax

        frames = _make_frames()
        ck_dir = str(tmp_path_factory.mktemp("ctl") / "ck")
        tr = self._run(ck_dir, frames)
        assert tr.error is None and ckpt.latest_step(ck_dir) == self.EPOCHS
        _, params, opt = _templates()
        tpl = {"params": params, "opt_state": opt}
        leaves = jax.tree_util.tree_leaves(
            ckpt.restore_state(ck_dir, self.EPOCHS, tpl))
        return frames, tpl, leaves

    # (site, arm kwargs, durable step after the kill, samples skipped on
    # the resume replay)
    ROWS = [
        ("trainer.step", {"after": STEPS_PER_EPOCH}, 1, N),
        ("trainer.checkpoint", {}, None, 0),
        ("trainer.checkpoint.commit", {}, None, 0),
    ]

    @pytest.mark.parametrize("site,arm,durable,skipped",
                             ROWS, ids=[r[0] for r in ROWS])
    def test_kill_then_resume_bit_identical(
            self, tmp_path, control, site, arm, durable, skipped):
        import jax

        frames, tpl, control_leaves = control
        ck_dir = str(tmp_path / "ck")
        FAULTS.arm(site, exc=RuntimeError(f"injected kill at {site}"),
                   times=1, **arm)
        killed = self._run(ck_dir, frames)
        FAULTS.reset()
        assert killed.error is not None
        assert ckpt.latest_step(ck_dir) == durable
        if site == "trainer.checkpoint.commit":
            # the torn-save gap: orbax data exists, marker doesn't —
            # invisible to latest_step, overwritten by the resume run
            assert os.path.isdir(os.path.join(ck_dir, "step_1"))

        resumed = self._run(ck_dir, frames, resume=True)
        assert resumed.error is None
        assert resumed.status.epoch_count == self.EPOCHS
        assert resumed.resumes == (1 if durable is not None else 0)
        assert resumed.replay_skipped == skipped
        assert resumed.gap_samples == 0
        # the (epoch, sample_index) ledger holds no duplicates: zero
        # samples retrained
        assert len(resumed.trained_log) == len(set(resumed.trained_log))
        assert ckpt.latest_step(ck_dir) == self.EPOCHS
        leaves = jax.tree_util.tree_leaves(
            ckpt.restore_state(ck_dir, self.EPOCHS, tpl))
        assert len(leaves) == len(control_leaves)
        for a, b in zip(leaves, control_leaves):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Supervision: a dead training thread must surface on a QUIET stream
# (watchdog sweep), and error-policy=restart must revive the backend
# mid-stream with checkpoint resume + epoch-boundary realignment.
# ---------------------------------------------------------------------------
class TestTrainerSupervision:
    def _push_epoch(self, src, frames, ep, n=N, sleep=0.0):
        for i in range(n):
            fr = TensorFrame([frames[i][0], frames[i][1]])
            fr.meta["epoch"] = ep
            fr.meta["sample_index"] = i
            src.push(fr)
            if sleep:
                time.sleep(sleep)

    def test_quiet_stream_death_surfaces(self, tmp_path):
        """A trainer that dies with no further frames arriving must not
        hang until EOS: the sweep routes the error through fail-stop
        within seconds and wait() raises."""
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(CFG))
        pipe = parse_pipeline(
            f"appsrc name=src ! tensor_trainer name=train framework=jax "
            f"model-config={cfg_path} num-inputs=1 num-labels=1 "
            f"num-training-samples={N} epochs=3 ! tensor_sink name=out"
        )
        pipe.start()
        frames = _make_frames()
        FAULTS.arm("trainer.step", exc=RuntimeError("chaos: quiet death"),
                   times=1)
        self._push_epoch(pipe["src"], frames, 0)
        # no EOS, no more frames: only the sweeper can surface this
        t0 = time.monotonic()
        with pytest.raises(ElementError, match="trainer failed"):
            pipe.wait(timeout=60)
        assert time.monotonic() - t0 < 30
        assert pipe.health()["train"]["state"] == "failed"
        assert pipe.health()["train"]["train_alive"] == 0
        pipe.stop()

    def test_restart_policy_revives_and_realigns(self, tmp_path):
        """error-policy=restart: the revived backend resumes from the
        durable checkpoint, drops the un-resumable partial epoch from
        the live stream (counted as gap), and completes the run."""
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(CFG))
        ck_dir = str(tmp_path / "ck")
        pipe = parse_pipeline(
            f"appsrc name=src ! tensor_trainer name=train framework=jax "
            f"model-config={cfg_path} num-inputs=1 num-labels=1 "
            f"num-training-samples={N} epochs=3 checkpoint-path={ck_dir} "
            "checkpoint-interval=1 error-policy=restart max-restarts=3 ! "
            "tensor_sink name=out"
        )
        pipe.start()
        frames = _make_frames()
        train = pipe["train"]
        self._push_epoch(pipe["src"], frames, 0)
        deadline = time.monotonic() + 120
        while ckpt.latest_step(ck_dir) != 1:
            assert time.monotonic() < deadline, "epoch-1 checkpoint missing"
            time.sleep(0.05)
        # kill the NEXT optimizer step (mid-epoch-2, checkpoint durable)
        FAULTS.arm("trainer.step", exc=TransientError("chaos: preempted"),
                   times=1)
        self._push_epoch(pipe["src"], frames, 1, sleep=0.01)
        deadline = time.monotonic() + 60
        while train.health_info()["train_restarts"] < 1:
            assert time.monotonic() < deadline, "supervisor never revived"
            time.sleep(0.05)
        FAULTS.reset()
        # the partial epoch is gone from the live stream: supply enough
        # fresh epochs for the realign to finish the configured 3
        for ep in (2, 3, 4):
            self._push_epoch(pipe["src"], frames, ep)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=300)
        h = train.health_info()
        assert h["train_restarts"] == 1
        assert h["train_resumes"] == 1
        assert h["train_epochs"] == 3
        assert h["train_gap_samples"] >= 1  # realign is counted, never silent
        assert not pipe.errors
        pipe.stop()


# ---------------------------------------------------------------------------
# Starvation-free co-hosting: the memory watermark pauses training
# (resumable, counted) and training finishes with zero sample loss.
# ---------------------------------------------------------------------------
class TestPressurePause:
    def test_watermark_pauses_and_resumes(self, tmp_path):
        frames = _make_frames()
        data_path, json_path = _write_repo(str(tmp_path), frames)
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(CFG))
        pressure = {"on": True}
        pipe = parse_pipeline(
            f"datareposrc location={data_path} json={json_path} epochs=2 ! "
            f"tensor_trainer name=train framework=jax model-config={cfg_path} "
            f"num-inputs=1 num-labels=1 num-training-samples={N} epochs=2 "
            f"checkpoint-path={tmp_path / 'ck'} ! tensor_sink name=out"
        )
        pipe.enable_memory_monitor(
            high=0.90, low=0.75, sustain_s=0.0, min_poll_s=0.05,
            sample=lambda: ((95, 100, 0) if pressure["on"] else (10, 100, 0)),
        )
        pipe.start()
        train = pipe["train"]
        deadline = time.monotonic() + 60
        while not train.health_info()["train_paused"]:
            assert time.monotonic() < deadline, "pressure never paused training"
            time.sleep(0.02)
        h = train.health_info()
        assert h["train_pauses"] == 1
        frozen = h["train_steps"]
        time.sleep(0.3)  # paused means FROZEN, not slow
        assert train.health_info()["train_steps"] == frozen
        pressure["on"] = False
        pipe.wait(timeout=300)
        h = train.health_info()
        assert h["train_paused"] == 0
        assert h["train_epochs"] == 2
        assert h["train_samples"] == 2 * N  # resumable pause: zero loss
        assert h["train_pauses"] == 1
        pipe.stop()


# ---------------------------------------------------------------------------
# The promotion gate: first candidate promotes through the staged hot
# swap, a regressed candidate is refused, a promotion failure (fault
# site) degrades without killing serving, and the gate recovers.
# ---------------------------------------------------------------------------
class TestValidatorGate:
    def test_gate_promote_refuse_recover(self, tmp_path):
        import jax
        from flax import serialization

        from nnstreamer_tpu.core.checkpoint import atomic_write_bytes
        from nnstreamer_tpu.trainer.jax_trainer import make_loss_fn

        frames = _make_frames(n=N + 8)
        data_path, json_path = _write_repo(str(tmp_path), frames)
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(CFG))
        fn, params, opt = _templates()
        # two candidates with deterministically DIFFERENT held-out loss:
        # rank them with the gate's own objective and plant better first
        shifted = jax.tree_util.tree_map(lambda a: a + 0.5, params)
        xs = [np.stack([f[0] for f in frames[N:]])]
        ys = [np.stack([f[1] for f in frames[N:]])]
        loss_fn = jax.jit(make_loss_fn(fn, "softmax_ce"))
        cands = sorted(
            (params, shifted), key=lambda p: float(loss_fn(p, xs, ys)[0]))
        better, worse = cands
        base_path = str(tmp_path / "base.msgpack")
        atomic_write_bytes(base_path, serialization.to_bytes(params))
        ck_dir = str(tmp_path / "ck")
        promote_path = str(tmp_path / "promoted.msgpack")

        pipe = parse_pipeline(
            f"appsrc name=stats ! model_validator name=gate "
            f"checkpoint-path={ck_dir} model-config={cfg_path} "
            f"data-location={data_path} data-json={json_path} "
            f"holdout-start={N} metric=loss target=serve "
            f"promote-path={promote_path} ! tensor_sink name=vs "
            f"appsrc name=src ! tensor_filter name=serve framework=jax-xla "
            f"model={base_path} custom=arch:mnist_cnn,classes:{CLASSES} "
            "is-updatable=true staged-reload=true observation-window=2 "
            "rollback-error-burst=3 ! tensor_sink name=out"
        )
        pipe.start()
        gate, serve = pipe["gate"], pipe["serve"]
        stat = np.zeros(5, np.float64)

        def pump_until(cond, tag, deadline_s=120.0):
            deadline = time.monotonic() + deadline_s
            while not cond():
                assert time.monotonic() < deadline, tag
                pipe["src"].push(frames[0][0])
                time.sleep(0.02)

        # 1. first candidate always promotes (staged swap commits, then
        #    the observation window closes on clean frames)
        ckpt.save_state(ck_dir, 1, {"params": better, "opt_state": opt})
        pipe["stats"].push(stat)
        pump_until(lambda: serve.health_info()["model_version"] == 1
                   and serve.health_info()["swap_state"] == "idle",
                   "first promotion never committed")
        assert gate.health_info()["train_promotions"] == 1

        # 2. a regressed candidate is refused; the serving model stays
        ckpt.save_state(ck_dir, 2, {"params": worse, "opt_state": opt})
        pipe["stats"].push(stat)
        pump_until(lambda: gate.health_info()["train_promotions_refused"] == 1,
                   "regression never refused")
        h = gate.health_info()
        assert h["train_promotions"] == 1 and h["train_validations"] == 2
        assert serve.health_info()["model_version"] == 1

        # 3. promotion failure (fault site): counted, serving untouched,
        #    the pipeline stays alive
        ckpt.save_state(ck_dir, 3, {"params": better, "opt_state": opt})
        FAULTS.arm("trainer.promote",
                   exc=RuntimeError("chaos: export refused"), times=1)
        pipe["stats"].push(stat)
        pump_until(lambda: gate.health_info()["train_promote_failures"] == 1,
                   "promotion failure never counted")
        FAULTS.reset()
        assert serve.health_info()["model_version"] == 1
        assert not pipe.errors

        # 4. the gate recovers: the next candidate promotes cleanly
        ckpt.save_state(ck_dir, 4, {"params": better, "opt_state": opt})
        pipe["stats"].push(stat)
        pump_until(lambda: serve.health_info()["model_version"] == 2
                   and serve.health_info()["swap_state"] == "idle",
                   "gate did not recover after a promote failure")
        assert gate.health_info()["train_promotions"] == 2
        assert serve.health_info()["rollbacks"] == 0
        pipe["src"].end_of_stream()
        pipe["stats"].end_of_stream()
        pipe.wait(timeout=60)
        pipe.stop()


# ---------------------------------------------------------------------------
# Truncated-repo prefix -> trainer e2e: a killed repo writer leaves a
# partial tail; training runs on the complete prefix, loudly counted.
# ---------------------------------------------------------------------------
class TestTruncatedRepoTraining:
    def test_trains_on_complete_prefix(self, tmp_path):
        frames = _make_frames(n=24)
        # claim 24 samples, end the file mid-sample-17
        data_path, json_path = _write_repo(
            str(tmp_path), frames, claim=24,
            truncate_bytes=7 * (28 * 28 * 4 + 4) + 100)
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(CFG))
        pipe = parse_pipeline(
            f"datareposrc name=repo location={data_path} json={json_path} "
            f"epochs=1 ! "
            f"tensor_trainer name=train framework=jax model-config={cfg_path} "
            f"num-inputs=1 num-labels=1 num-training-samples={N} epochs=1 ! "
            "tensor_sink name=out"
        )
        pipe.start()
        pipe.wait(timeout=300)
        assert pipe.health()["repo"]["truncated_samples"] == 8
        h = pipe["train"].health_info()
        assert h["train_epochs"] == 1
        assert h["train_samples"] == N  # the complete 16-sample prefix
        assert not pipe.errors
        pipe.stop()


# ---------------------------------------------------------------------------
# Co-hosted serving (async-sim device): training in the same pipeline
# graph must not cost serving a frame or reorder one.  What the chip
# pays for co-hosting is the benchmark's to measure, not a CPU clock's.
# ---------------------------------------------------------------------------
class TestCoHostedServing:
    SERVE = (
        "appsrc name=src max-buffers=512 ! "
        "tensor_filter name=serve framework=async-sim custom=compute_ms:5 "
        "max-batch=8 dispatch-depth=4 ! tensor_sink name=out max-stored=1"
    )

    def test_cohosted_delivers_all_in_order(self, tmp_path):
        frames = _make_frames()
        data_path, json_path = _write_repo(str(tmp_path), frames)
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps(CFG))
        co = parse_pipeline(
            f"datareposrc location={data_path} json={json_path} epochs=500 ! "
            f"tensor_trainer name=train framework=jax model-config={cfg_path} "
            f"num-inputs=1 num-labels=1 num-training-samples={N} epochs=500 ! "
            "tensor_sink name=tsink " + self.SERVE,
            name="cohosted",
        )
        co.start()
        train = co["train"]
        # past BOTH jit compiles (train step + epoch-boundary eval) and
        # into steady state before serving starts
        deadline = time.monotonic() + 120
        while train.health_info()["train_steps"] < 10 * STEPS_PER_EPOCH:
            assert time.monotonic() < deadline, "training never reached steady state"
            time.sleep(0.05)
        steps_before = train.health_info()["train_steps"]
        got = []
        co["out"].connect_new_data(
            lambda f: got.append(float(np.asarray(f.tensors[0])[0])))
        n = 400
        for i in range(n):
            co["src"].push(np.full((64,), i, np.float32))
        deadline = time.monotonic() + 60
        while len(got) < n and time.monotonic() < deadline:
            time.sleep(0.002)
        h = train.health_info()
        serve = co.health()["serve"]
        co.stop()
        # serving delivered everything it was pushed, in order, through
        # the async window (y = 2x + 1), with nothing dead-lettered...
        assert got == [2.0 * i + 1.0 for i in range(n)]
        assert serve["dead_letters"] == 0 and serve["restarts"] == 0
        # ...while training genuinely ran through the same window
        assert h["train_alive"] == 1 and h["train_steps"] > steps_before


# ---------------------------------------------------------------------------
# The continuous-learning chaos e2e (acceptance): kill mid-epoch ->
# bit-identical resume; refuse a regression; roll back a bad promotion
# with zero frame loss; pressure-pause while co-hosted serving lives.
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_chaos_train_script():
    from tools.chaos_fleet import run_train_script

    v = run_train_script(seed=0)
    assert v["ok"], v["checks"]
    assert v["resume"]["params_bit_identical"]
    assert v["resume"]["replay_skipped"] == 32
    assert v["refusal"]["refused"] == 1
    assert v["rollback"]["rollbacks"] == 1
    assert v["rollback"]["served"] == v["rollback"]["pushed"]
    assert v["pressure"]["pauses"] == 1
