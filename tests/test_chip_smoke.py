"""chip_smoke.py's phase functions at tiny sizes on CPU (kernels in the
Pallas interpreter), plus the bring-up rules they rest on: the hardware
probe starts no process, and an unsharded generator keeps params AND KV
cache on the element's device.

The chip run itself (``python chip_smoke.py`` through the chip tool) is the
acceptance test; these keep the script and its phases importable, runnable
and fatal-on-failure between chip runs.
"""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from nnstreamer_tpu.pipeline import parse_pipeline

TINY_LM = "vocab:64,d_model:32,heads:4,layers:1,d_ff:64,seq:96,dtype:float32"
TINY_GEN = dict(custom=TINY_LM, vocab=64, slots=2, max_new=8, chunk=4,
                prompt_lens=(5, 9, 5, 9), prefix_len=70)
TINY_STREAM = dict(size=32, width="0.25", classes=17, max_batch=8)


@pytest.fixture(autouse=True)
def _out_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "smoke"))


def test_stream_phase_tiny(monkeypatch):
    tail = 3

    def tail_apart(pipe, frames, timeout_s):
        """``chip_smoke._run_labeling`` with the ragged tail pushed once the
        full batches are out: on the chip the model's pace fills the
        batches; here the scheduler may cut 19 frames 6 + 7 + 6, all of
        one bucket, and the phase then (rightly) refuses the run."""
        import time

        head = len(frames) - tail
        for f in frames[:head]:
            pipe["src"].push(f)
        deadline = time.monotonic() + timeout_s
        while len(pipe["out"].frames) < head and time.monotonic() < deadline:
            time.sleep(0.005)
        for f in frames[head:]:
            pipe["src"].push(f)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=timeout_s)
        out = pipe["out"].frames
        assert len(out) == len(frames)
        return [(int(np.asarray(fr.tensors[0]).reshape(-1)[0]),
                 float(fr.meta["label_score"])) for fr in out]

    monkeypatch.setattr(chip_smoke, "_run_labeling", tail_apart)
    r = chip_smoke.phase_stream(
        platform="cpu", full_batches=2, tail=tail, sample=4, **TINY_STREAM)
    assert r["frames"] == 19 and r["dtype"] == "float32"
    assert 8 in r["buckets_compiled"] and r["invokes"] >= 3


def test_generate_phase_tiny():
    r = chip_smoke.phase_generate(platform="cpu", **TINY_GEN)
    assert r["streams"] == 4 and r["tokens"] == 32
    assert r["prefix"]["prefix_hits"] == 1
    assert r["prefix"]["prefix_hit_tokens"] == 64
    assert r["donate"] is False  # a CPU model asks for no donation
    # so its join copies: nothing it was passed is deleted
    assert r["join"] == {"leaves": 4, "deleted": 0, "slot_zero": True,
                         "neighbour_equal": True}


def test_train_phase_tiny():
    r = chip_smoke.phase_train(
        platform="cpu", n_train=64, n_valid=16, epochs=3, batch_size=16,
        checkpoint_steps=4, dtype="float32")
    assert r["steps"] == 12 == r["restored_step"]
    assert r["epoch_losses"][-1] < r["epoch_losses"][0]


def test_kernels_phase_tiny_runs_every_pallas_kernel_interpreted():
    """The first test to execute the top-1 and normalize kernels at all
    (interpreter), next to their TPU lowering as text."""
    r = chip_smoke.phase_kernels(
        platform="cpu", interpret=True, top1_batches=(1, 2, 8), classes=17,
        norm_shape=(2, 8, 8, 3), attn_shapes=((1, 32, 2, 8), (1, 21, 3, 8)),
        attn_stream_shape=(1, 37, 4, 8),
        decode_shapes=((3, 256, 4, 4, 32), (3, 128, 4, 2, 64)),
        ring_shapes=((3, 128, 4, 2, 64),),
        chunk_shapes=((False, 128, 256, 2, 1, 128), (True, 128, 128, 2, 1, 128)), expert_shapes=((5, 32, 128, 4, 2), (40, 32, 128, 4, 2), (300, 32, 128, 4, 2)))
    names = " ".join(r["kernels"])
    for kernel in ("top1", "normalize_u8", "flash(", "flash_grad",
                   "decode_attention(3, 256", "decode_attention(3, 128",
                   "decode_attention(3, 128, 4, 2, 64) ring",
                   "chunk_attention(128, 256, 2, 1, 128)", "chunk_attention(128, 128, 2, 1, 128) ring",
                   "touched_experts_ffn gated(5,", "touched_experts_ffn gated(40,",
                   "grouped_experts_ffn gated(300,"):
        assert kernel in names


def test_mesh_phase_tiny_gives_every_device_work():
    """Two virtual devices here (each extra replica is one more MobileNet
    compile); the chip run does four."""
    r = chip_smoke.phase_mesh(
        platform="cpu", n=2, stream_sizes={**TINY_STREAM, "max_batch": 4},
        generate_sizes={**TINY_GEN, "prompt_lens": (5, 9), "max_new": 4,
                        "prefix_len": 0},
        replica_frames=4)
    for key in ("dp_devices", "replica_devices", "tp_params_on",
                "tp_cache_on"):
        assert len(set(r[key])) == 2, (key, r[key])


def test_a_failing_phase_is_fatal():
    """Wrong platform expectation = the phase's own device assertion
    fires; nothing swallows it."""
    with pytest.raises(AssertionError, match="must live on 'tpu'"):
        chip_smoke.phase_kernels(
            platform="tpu", interpret=True, top1_batches=(2,), classes=17,
            norm_shape=(1, 8, 8, 3), attn_shapes=((1, 16, 1, 8),),
            attn_stream_shape=(1, 16, 1, 8), decode_shapes=(), ring_shapes=(), chunk_shapes=(),
            expert_shapes=())


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() == 3
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err and "'cpu'" in err


class _FakeTpu:
    platform, device_kind, id = "tpu", "fake", 0


_FAKE_DEVICE = {"platform": "tpu", "kind": "fake", "count": 1}


def _main_on_a_fake_tpu(monkeypatch, **phases):
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    for name in ("stream", "generate", "train", "kernels"):
        monkeypatch.setattr(chip_smoke, f"phase_{name}",
                            phases.get(name, lambda **_: {}))
    from nnstreamer_tpu.core import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: None)


def test_main_ends_stdout_on_the_exact_verdict(monkeypatch, capsys):
    """The driver reads the last stdout line: exactly ``ok`` and ``device``
    (platform, kind, count), nothing more; the summary above it carries
    the rest and ends with ``"claim": null``."""
    _main_on_a_fake_tpu(monkeypatch)
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": _FAKE_DEVICE}
    summary = json.loads(lines[-2])
    assert summary["phase"] == "summary" and summary["ok"] is True
    assert set(summary["phases"]) == {"stream", "generate", "train",
                                      "kernels"}
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_main_reports_the_failed_phase_and_dies(monkeypatch, capsys):
    """Forcing one phase to fail ends the whole run non-zero: one line
    names the phase, the verdict says ``ok: false``, then the exception
    leaves main()."""

    def boom(**_):
        raise RuntimeError("phase exploded")

    _main_on_a_fake_tpu(monkeypatch, train=boom)
    with pytest.raises(RuntimeError, match="phase exploded"):
        chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": False, "device": _FAKE_DEVICE}
    assert json.loads(lines[-2]) == {
        "phase": "train", "ok": False,
        "error": "RuntimeError: phase exploded", "claim": None}


# ---------------------------------------------------------------------------
# the rules the phases rest on
# ---------------------------------------------------------------------------
def test_hw_probe_starts_no_subprocess(monkeypatch):
    from nnstreamer_tpu.core import hw

    def no_process(*a, **k):
        raise AssertionError("hw.probe() must not start a process")

    monkeypatch.setattr(subprocess, "run", no_process)
    monkeypatch.setattr(subprocess, "Popen", no_process)
    hw.reset()
    info = hw.probe()
    assert info["platform"] == "cpu" and info["accelerated"] is False
    assert info["num_devices"] == len(jax.devices())
    assert info["device_kind"] == jax.devices()[0].device_kind
    assert hw.preferred_dtype() == "float32"
    assert "subprocess" not in open(hw.__file__).read()


@pytest.mark.parametrize("slots", [2, 0])
def test_unsharded_generator_lives_on_the_elements_device(slots):
    """Params and KV cache share the element's device — checked on a
    NON-default virtual device, so a build pinned to cpu:0 (or a cache
    left to jit's default placement) fails it."""
    dev = jax.devices()[3]
    pipe = parse_pipeline(
        f"appsrc name=src ! tensor_generator name=gen slots={slots} "
        f"custom={TINY_LM} max-new=4 chunk=2 ! tensor_sink name=out")
    with jax.default_device(dev):
        pipe.start()
    try:
        gen = pipe["gen"]
        assert chip_smoke._devices_of(gen._params) == {dev}
        if slots:
            assert chip_smoke._devices_of(gen._engine._cache) == {dev}
        pipe["src"].push(np.arange(6, dtype=np.int32)[None])
        pipe["src"].end_of_stream()
        pipe.wait(timeout=120)
        toks = np.concatenate(
            [np.asarray(f.tensors[0]) for f in pipe["out"].frames], axis=1)
        assert toks.shape == (1, 4)
        # after the steps ran: still there (the steps followed the state,
        # not the process default)
        assert chip_smoke._devices_of(gen._params) == {dev}
        if slots:
            assert chip_smoke._devices_of(gen._engine._cache) == {dev}
    finally:
        pipe.stop()


def test_pallas_kernels_follow_the_lowering_platform():
    """The kernel/jnp choice is made per lowering platform: the same
    traced program holds the Mosaic call for a TPU and none for CPU."""
    from nnstreamer_tpu.ops.labeling import top1

    traced = jax.jit(top1).trace(np.zeros((4, 17), np.float32))
    assert "tpu_custom_call" in traced.lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in traced.lower(
        lowering_platforms=("cpu",)).as_text()
    # partitioned over a mesh the fused decoder keeps to jnp
    no_kernel = jax.jit(lambda x: top1(x, use_pallas=False)).trace(
        np.zeros((4, 17), np.float32))
    assert "tpu_custom_call" not in no_kernel.lower(
        lowering_platforms=("tpu",)).as_text()


def test_script_runs_standalone_and_names_the_missing_device(tmp_path):
    """As the driver runs it: a fresh process, no accelerator -> non-zero
    exit, no verdict on stdout, the reason on stderr."""
    r = subprocess.run(
        [sys.executable, chip_smoke.__file__], capture_output=True,
        text=True, timeout=120, cwd=str(tmp_path))
    assert r.returncode == 3
    assert r.stdout == "" and "needs a TPU" in r.stderr
