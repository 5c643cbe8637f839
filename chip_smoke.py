#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three paths users pay for ONCE each, through the entry points a
user would call, at the full width of a model the repo supports, in ONE
process on one TPU (a chip belongs to one process at a time):

* **stream**   — the README quick-start graph: ``appsrc ! tensor_filter
  framework=jax-xla (MobileNet-v2 1.0, 224x224x3 uint8, 1001 classes, bf16)
  max-batch=128 ! tensor_decoder mode=image_labeling ! tensor_sink`` on
  host-sourced frames;
* **generate** — a server that answers requests: ``tensor_query_serversrc !
  tensor_generator slots=4 ! tensor_query_serversink`` + ``tensor_query_client``
  streams, the zoo transformer at its defaults;
* **train**    — ``datareposrc ! tensor_trainer`` (jax, mnist_cnn, synthetic
  set) with a marker-committed checkpoint;
* **kernels**  — every Pallas kernel compiled for the chip at the shapes the
  zoo uses, against its jnp reference;
* **mesh**     — only with >= 4 devices: dp:4 stream, tp:4 generator, four
  pinned one-chip replicas.

Weights are random from a seed; depth and sizes are what the zoo ships.
Each phase prints one JSON line, then a summary line (per-phase seconds,
compile cache, ``"claim": null``); the LAST stdout line is the verdict and
holds exactly ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.  Exit code 0 only if every phase passed.  With no TPU
(e.g. ``JAX_PLATFORMS=cpu``) it says why on stderr, prints no verdict and
exits 3.  Needs no git and no network; writes only under
``chiprun_out/chip_smoke/`` and the compile cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).

The phase functions take their sizes, so tests/test_chip_smoke.py calls
them tiny on CPU (kernels in the Pallas interpreter).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileMeter:
    """Process-wide compile accounting from jax's own monitoring events:
    seconds inside backend compile (or cache retrieval), programs
    compiled, persistent-cache hits / misses."""

    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.counts = {"compile_s": 0.0, "compiles": 0,
                       "cache_hits": 0, "cache_misses": 0}
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        key = _CACHE_EVENTS.get(event)
        if key:
            with self._lock:
                self.counts[key] += 1

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.counts["compile_s"] += float(duration)
                self.counts["compiles"] += 1

    def snapshot(self):
        with self._lock:
            return dict(self.counts)


def _devices_of(tree):
    """Every device holding a shard of any array leaf of ``tree``."""
    import jax

    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            devs |= set(leaf.devices())
    return devs


def _assert_on(tree, platform, what, n_devices=None):
    devs = _devices_of(tree)
    if not devs:
        raise AssertionError(f"{what}: no device arrays")
    wrong = sorted(str(d) for d in devs if d.platform != platform)
    if wrong:
        raise AssertionError(
            f"{what} must live on {platform!r} devices; found {wrong}")
    if n_devices is not None and len(devs) != n_devices:
        raise AssertionError(
            f"{what}: expected {n_devices} distinct devices, got "
            f"{sorted(str(d) for d in devs)}")
    return sorted(str(d) for d in devs)


def _assert_healthy(pipe):
    """Pipeline.health(): no element errored, restarted or dead-lettered."""
    for name, h in pipe.health().items():
        if (h.get("restarts") or h.get("dead_letters") or h.get("last_error")
                or h.get("state") in (
                    "failed", "stalled", "restarting", "degraded")):
            raise AssertionError(f"element {name} unhealthy: {h}")
    if pipe.errors:
        raise AssertionError(f"pipeline errors: {pipe.errors}")


# ---------------------------------------------------------------------------
# stream
# ---------------------------------------------------------------------------
def _make_frames(n, size, seed=0):
    """Host uint8 frames with per-frame structure (a bright band whose
    position and level vary), so different frames excite different
    features."""
    import numpy as np

    rng = np.random.default_rng(seed)
    frames = []
    for i in range(n):
        f = rng.integers(0, 96, (size, size, 3), dtype=np.uint8)
        lo = (i * 7) % max(1, size - size // 4)
        f[lo:lo + size // 4, :, i % 3] += np.uint8(64 + (i * 13) % 96)
        frames.append(f)
    return frames


def _run_labeling(pipe, frames, timeout_s):
    """Push host frames through a started labeling pipeline; return the
    (label_index, label_score) pairs in arrival order."""
    import numpy as np

    for f in frames:
        pipe["src"].push(f)
    pipe["src"].end_of_stream()
    pipe.wait(timeout=timeout_s)
    out = pipe["out"].frames
    if len(out) != len(frames):
        raise AssertionError(
            f"delivered {len(out)} of {len(frames)} frames")
    return [(int(np.asarray(fr.tensors[0]).reshape(-1)[0]),
             float(fr.meta["label_score"])) for fr in out]


def _assert_outputs_stay(backend, frames, platform, n_devices=None):
    """What the backend computes for a host batch lives where its params
    live.  Goes through the pipeline's own entry point (donated batch) at a
    bucket the stream has compiled, so it costs a run, not a compile."""
    import numpy as np

    (out,) = backend.invoke_batch_donated([np.stack(frames)])
    _assert_on(out, platform, "filter output", n_devices)
    if _devices_of(out) != _devices_of(backend._params):
        raise AssertionError("filter output left the params' devices")


def _labeling_graph(labels, max_batch, extra=""):
    """The README quick-start graph (``extra`` = more filter props)."""
    return (
        "appsrc name=src ! "
        "tensor_filter name=f framework=jax-xla model=smoke_mnet "
        f"max-batch={max_batch} {extra}! "
        f"tensor_decoder mode=image_labeling option1={labels} ! "
        "tensor_sink name=out")


def _write_labels(classes):
    os.makedirs(OUT_DIR, exist_ok=True)
    labels = os.path.join(OUT_DIR, "labels.txt")
    with open(labels, "w") as f:
        f.write("\n".join(f"class{i}" for i in range(classes)))
    return labels


def _check_labels(got, ref_logits, classes, dtype):
    """Labels against the float32 reference argmax.  A reduced-precision
    model may pick another class when the reference's own margin between
    the two is within that precision's error — a tie, judged by score
    margin in units of the reference's logit spread."""
    import numpy as np

    # bf16 carries 8 mantissa bits through ~50 layers; float32 agrees to
    # rounding.  Tolerance = that error as a share of the logit spread.
    rel = 0.08 if dtype == "bfloat16" else 1e-4
    ties = 0
    for i, ((idx, score), ref) in enumerate(zip(got, ref_logits)):
        if not 0 <= idx < classes:
            raise AssertionError(f"frame {i}: label {idx} out of range")
        if not np.isfinite(score):
            raise AssertionError(f"frame {i}: score {score} not finite")
        tol = rel * float(ref.max() - ref.min()) + 1e-6
        want = int(np.argmax(ref))
        if abs(score - float(ref[idx])) > tol:
            raise AssertionError(
                f"frame {i}: score {score} vs float32 logit "
                f"{float(ref[idx])} (tol {tol})")
        if idx != want:
            margin = float(ref[want] - ref[idx])
            if margin > tol:
                raise AssertionError(
                    f"frame {i}: label {idx} != float32 argmax {want} "
                    f"with margin {margin} > tol {tol}")
            ties += 1
    return ties


def phase_stream(*, platform, size=224, width="1.0", classes=1001,
                 max_batch=128, full_batches=4, tail=37, sample=16,
                 timeout_s=900.0):
    import jax
    import numpy as np

    from nnstreamer_tpu.backends.jax_xla import (
        register_jax_model, unregister_jax_model)
    from nnstreamer_tpu.core import hw
    from nnstreamer_tpu.models import build
    from nnstreamer_tpu.pipeline import parse_pipeline

    zoo = {"size": str(size), "width": width, "classes": str(classes)}
    # the quick-start build: no dtype asked, the in-process hw probe picks
    # bfloat16 on an accelerator
    fn, params, in_spec, out_spec = build("mobilenet_v2", dict(zoo))
    dtype = hw.preferred_dtype()
    if dtype != ("float32" if platform == "cpu" else "bfloat16"):
        raise AssertionError(f"hw probe picked {dtype} on {platform!r}")
    labels = _write_labels(classes)
    register_jax_model("smoke_mnet", fn, params, in_spec, out_spec)
    try:
        pipe = parse_pipeline(
            _labeling_graph(labels, max_batch), name="smoke-stream")
        pipe.start()
        try:
            backend = pipe["f"].backend
            params_on = _assert_on(backend._params, platform, "filter params")
            n = max_batch * full_batches + tail
            frames = _make_frames(n, size)
            got = _run_labeling(pipe, frames, timeout_s)
            _assert_outputs_stay(backend, frames[:max_batch], platform)
            _assert_healthy(pipe)
            invokes = backend.stats.total_invoke_num
            # compiled batch buckets (a lone frame takes the per-frame
            # program: bucket 1)
            buckets = sorted({k[3][0][0] if k[1] else 1
                              for k in backend._jit_cache if len(k) > 3})
            # full micro-batches formed, and the ragged tail compiled a
            # smaller bucket of its own
            if max_batch not in buckets or len(buckets) < 2:
                raise AssertionError(f"batch buckets compiled: {buckets}")
        finally:
            pipe.stop()
    finally:
        unregister_jax_model("smoke_mnet")

    # float32 jnp reference on a sample of frames (same seed, same params
    # pytree: flax keeps params in float32 whatever the compute dtype)
    ref_fn, ref_params, _, _ = build(
        "mobilenet_v2", {**zoo, "dtype": "float32"})
    idxs = sorted({int(i) for i in np.linspace(0, n - 1, sample)})
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, x: ref_fn(p, [x])[0])(
            ref_params, np.stack([frames[i] for i in idxs])), np.float32)
    ties = _check_labels([got[i] for i in idxs], ref, classes, dtype)
    return {
        "frames": n, "labels_distinct": len({g[0] for g in got}),
        "dtype": dtype, "params_on": params_on, "invokes": invokes,
        "buckets_compiled": buckets, "sample": len(idxs),
        "ties_by_margin": ties,
    }


# ---------------------------------------------------------------------------
# generate
# ---------------------------------------------------------------------------
def _serve(custom, slots, max_new, chunk, extra="", sid=0):
    from nnstreamer_tpu.pipeline import parse_pipeline

    custom_prop = f"custom={custom} " if custom else ""
    server = parse_pipeline(
        f"tensor_query_serversrc name=ssrc id={sid} port=0 ! "
        f"tensor_generator name=gen slots={slots} {custom_prop}"
        f"max-new={max_new} chunk={chunk} {extra}! "
        f"tensor_query_serversink id={sid}",
        name=f"smoke-gen-server-{sid}",
    )
    server.start()
    return server, server["ssrc"].props["port"]


def _stream_request(port, prompt, timeout_s, name):
    """One ``tensor_query_client stream=true`` request: tokens (1, N)."""
    import numpy as np

    from nnstreamer_tpu.pipeline import parse_pipeline

    client = parse_pipeline(
        f"appsrc name=src ! tensor_query_client port={port} stream=true "
        f"timeout={timeout_s} ! tensor_sink name=out", name=name)
    client.start()
    try:
        client["src"].push(prompt)
        client["src"].end_of_stream()
        client.wait(timeout=timeout_s + 30)
        frames = list(client["out"].frames)
        _assert_healthy(client)
    finally:
        client.stop()
    if not frames or not frames[-1].meta.get("final"):
        raise AssertionError(f"{name}: stream did not complete")
    if any(fr.meta.get("evicted") for fr in frames):
        raise AssertionError(f"{name}: stream evicted")
    return np.concatenate(
        [np.asarray(fr.tensors[0]) for fr in frames if fr.tensors], axis=1)


def _concurrent_requests(port, prompts, timeout_s, tag):
    results, errors = {}, []

    def run(i, p):
        try:
            results[i] = _stream_request(port, p, timeout_s, f"{tag}{i}")
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i, p), name=f"{tag}{i}")
               for i, p in enumerate(prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout_s + 60)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads) or len(results) != len(prompts):
        raise AssertionError(f"{tag}: {len(results)}/{len(prompts)} streams")
    return [results[i] for i in range(len(prompts))]


def _lm_props(custom):
    props = dict(kv.split(":", 1) for kv in custom.split(",") if ":" in kv)
    props.setdefault("dtype", "bfloat16")  # the generator's own default
    return props


def _one_shot_reference(custom, max_new, prompts):
    """Greedy tokens from the UNSLOTTED one-shot path (zoo
    ``generate:<N>`` = models/transformer.make_generate), one compiled
    program per prompt length."""
    import jax
    import numpy as np

    from nnstreamer_tpu.models import build

    fn, params, _, _ = build(
        "transformer", {**_lm_props(custom), "generate": str(max_new)})
    gen = jax.jit(lambda p, t: fn(p, [t])[0])
    return [np.asarray(gen(params, p))[:, p.shape[1]:] for p in prompts]


def _check_greedy(custom, prompts, streams):
    """Streams against the one-shot reference.  Equal token for token is
    the rule; where a reduced-precision stream leaves the reference, the
    two ran different XLA programs over the same bf16 model, and the
    stream is right iff every token it picked is the float32 model's
    greedy pick given all tokens before it — or ties with that pick
    within the precision's error, judged by logit margin in units of the
    float32 logit spread (a float32 model gets no such slack).  Returns
    (streams equal to the one-shot path, tokens accepted as ties)."""
    import jax
    import numpy as np

    from nnstreamer_tpu.models import build

    props = _lm_props(custom)
    max_new = streams[0].shape[1]
    want = _one_shot_reference(custom, max_new, prompts)
    exact = [bool(np.array_equal(g, w)) for g, w in zip(streams, want)]
    if all(exact):
        return len(streams), 0
    if props["dtype"] == "float32":
        i = exact.index(False)
        raise AssertionError(
            f"stream {i}: float32 tokens {streams[i][0].tolist()} != "
            f"one-shot {want[i][0].tolist()}")
    fn, params, _, _ = build("transformer", {**props, "dtype": "float32"})
    logits_of = jax.jit(lambda p, t: fn(p, [t])[0])
    rel = 0.05  # bf16 activations through the stack, f32 lm_head
    ties = 0
    for i, (prompt, toks) in enumerate(zip(prompts, streams)):
        if exact[i]:
            continue
        seq = np.concatenate([prompt, toks], axis=1)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(logits_of(params, seq), np.float32)[0]
        for j, tok in enumerate(toks[0]):
            ref = logits[prompt.shape[1] + j - 1]
            margin = float(ref.max() - ref[tok])
            tol = rel * float(ref.max() - ref.min())
            if margin > tol:
                raise AssertionError(
                    f"stream {i} token {j}: picked {int(tok)} where the "
                    f"float32 model prefers {int(np.argmax(ref))} by "
                    f"{margin} (tol {tol}); one-shot tokens "
                    f"{want[i][0].tolist()} vs {toks[0].tolist()}")
            ties += margin > 0.0
    return sum(exact), int(ties)


def _gen_state(server, platform, n_devices=None):
    gen = server["gen"]
    return (_assert_on(gen._params, platform, "generator params", n_devices),
            _assert_on(gen._engine._cache, platform, "generator KV cache",
                       n_devices))


def _wait_idle(server, timeout_s=30.0):
    t0 = time.monotonic()
    while not server["gen"]._engine.idle():
        if time.monotonic() - t0 > timeout_s:
            raise AssertionError("slot engine did not go idle")
        time.sleep(0.01)


def _join_in_place(engine, donate):
    """One join on the served model's own cache, its requests behind it:
    slot 0 reads zero afterwards and slot 1 what it read before, and the
    leaves that were passed in are gone exactly where the model donates
    (the join then wrote its rows in place; else it made a second cache)."""
    import jax
    import numpy as np

    old = jax.tree.leaves(engine._cache)
    if not any(np.asarray(c[0]).any() for c in old):
        raise AssertionError("slot 0 holds nothing a join could zero")
    beside = [np.asarray(c[1]) for c in old]
    engine._cache = engine.model.reset_slot(engine._cache, np.int32(0))
    deleted = sum(c.is_deleted() for c in old)
    if deleted != (len(old) if donate else 0):
        raise AssertionError(
            f"join with donate={donate}: {deleted} of {len(old)} cache "
            "leaves it was passed are deleted")
    for c, b in zip(jax.tree.leaves(engine._cache), beside):
        if np.asarray(c[0]).any():
            raise AssertionError("join left rows of its slot standing")
        if not np.array_equal(np.asarray(c[1]), b):
            raise AssertionError("join moved a neighbour's rows")
    return {"leaves": len(old), "deleted": deleted, "slot_zero": True,
            "neighbour_equal": True}


def phase_generate(*, platform, custom="", vocab=256, slots=4, max_new=32,
                   chunk=8, prompt_lens=(5, 12, 33, 70, 5, 12, 33, 70),
                   prefix_len=70, mesh="", sid=910, timeout_s=600.0):
    import numpy as np

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, vocab, (1, n)).astype(np.int32)
               for n in prompt_lens]
    n_mesh = None
    extra = ""
    if mesh:
        extra = f"mesh={mesh} "
        n_mesh = int(mesh.split(":")[1])
    server, port = _serve(custom, slots, max_new, chunk, extra=extra, sid=sid)
    try:
        engine = server["gen"]._engine
        donate = bool(engine.model._donate)
        if donate != (platform != "cpu"):
            raise AssertionError(
                f"KV-cache donation is {donate} on platform {platform!r}")
        before = _gen_state(server, platform, n_mesh)  # before the 1st step
        got = _concurrent_requests(port, prompts, timeout_s, "smoke-cli")
        _wait_idle(server)
        after = _gen_state(server, platform, n_mesh)   # after donated steps
        if before != after:
            raise AssertionError(f"generator state moved: {before} -> {after}")
        health = server.health()["gen"]
        _assert_healthy(server)
        join = _join_in_place(engine, donate)
    finally:
        server.stop()
    for i, toks in enumerate(got):
        if toks.shape != (1, max_new):
            raise AssertionError(f"stream {i}: {toks.shape} != (1, {max_new})")
    exact, ties = _check_greedy(custom, prompts, got)
    if health["gen_completed"] != len(prompts):
        raise AssertionError(f"completed {health['gen_completed']}")

    # one repeated prompt through the shared-prefix pool: the second pass
    # attaches pages the first one exported — against a cache the jitted
    # steps donate
    prefix_hits = None
    if prefix_len:
        p = rng.integers(0, vocab, (1, prefix_len)).astype(np.int32)
        server, port = _serve(custom, slots, max_new, chunk,
                              extra=extra + "prefix-cache=on ", sid=sid + 1)
        try:
            cold = _stream_request(port, p, timeout_s, "smoke-prefix-cold")
            warm = _stream_request(port, p, timeout_s, "smoke-prefix-warm")
            _wait_idle(server)
            _gen_state(server, platform, n_mesh)
            health2 = server.health()["gen"]
            _assert_healthy(server)
        finally:
            server.stop()
        # same pages, same programs: attach must be bit-exact
        if not np.array_equal(cold, warm):
            raise AssertionError(
                f"prefix-attached stream {warm[0].tolist()} != cold stream "
                f"{cold[0].tolist()}")
        _check_greedy(custom, [p], [cold])
        if health2["prefix_hits"] < 1 or health2["prefix_publishes"] < 1:
            raise AssertionError(f"prefix pool idle: {health2}")
        prefix_hits = {k: health2[k] for k in (
            "prefix_hits", "prefix_publishes", "prefix_hit_tokens")}
    return {
        "streams": len(prompts), "tokens": int(sum(g.size for g in got)),
        "equal_to_one_shot": exact, "ties_by_margin": ties,
        "params_on": after[0], "cache_on": after[1], "donate": donate,
        "join": join,
        "decode_steps": health["gen_decode_steps"],
        "tokens_per_step": health["gen_tokens_per_step"],
        "decode_compiles": health["gen_decode_compiles"],
        "prefix": prefix_hits, "mesh": mesh or None,
    }


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def phase_train(*, platform, n_train=2048, n_valid=256, epochs=4,
                batch_size=256, checkpoint_steps=16, dtype="bfloat16",
                timeout_s=600.0):
    import shutil

    import jax
    import numpy as np
    import optax

    from nnstreamer_tpu.core import checkpoint as ckpt
    from nnstreamer_tpu.pipeline import parse_pipeline
    from nnstreamer_tpu.trainer.jax_trainer import write_synthetic_mnist

    work = os.path.join(OUT_DIR, "train")
    shutil.rmtree(work, ignore_errors=True)
    data_path, json_path = write_synthetic_mnist(work, n_train + n_valid)
    cfg = {"arch": "mnist_cnn",
           "arch_props": {"dtype": dtype, "classes": "10"},
           "optimizer": "adam", "learning_rate": 3e-3,
           "batch_size": batch_size}
    cfg_path = os.path.join(work, "cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    ckpt_dir = os.path.join(work, "ckpt")
    pipe = parse_pipeline(
        f"datareposrc location={data_path} json={json_path} epochs={epochs} ! "
        f"tensor_trainer name=t framework=jax model-config={cfg_path} "
        f"num-inputs=1 num-labels=1 num-training-samples={n_train} "
        f"num-validation-samples={n_valid} epochs={epochs} "
        f"checkpoint-path={ckpt_dir} checkpoint-steps={checkpoint_steps} ! "
        "tensor_sink name=out",
        name="smoke-train",
    )
    pipe.start()
    try:
        pipe.wait(timeout=timeout_s)
        stats = [np.asarray(fr.tensors[0]) for fr in pipe["out"].frames]
        backend = pipe["t"].backend
        if backend.error is not None:
            raise backend.error
        health = pipe.health()["t"]
        _assert_healthy(pipe)
        params_on = _assert_on(backend.params, platform, "trainer params")
        opt_on = _assert_on(backend.opt_state, platform, "optimizer state")
        params = jax.tree.map(np.asarray, backend.params)
        opt_state = jax.tree.map(np.asarray, backend.opt_state)
    finally:
        pipe.stop()
    losses = [float(s[1]) for s in stats]
    if len(losses) != epochs or not all(np.isfinite(losses)):
        raise AssertionError(f"epoch losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    steps = epochs * -(-n_train // batch_size)
    if health["train_steps"] != steps or health["train_checkpoints"] < 1:
        raise AssertionError(f"trainer accounting: {health}")

    # the newest marker-committed checkpoint is the end-of-run state: it
    # must restore bit-identically (a torn save is never selectable)
    step = ckpt.latest_step(ckpt_dir)
    if step != steps:
        raise AssertionError(f"latest committed checkpoint {step} != {steps}")
    template = {"params": params,
                "opt_state": jax.tree.map(
                    np.asarray, optax.adam(3e-3).init(params))}
    restored = ckpt.restore_state(ckpt_dir, step, template)
    for name, want, got in (("params", params, restored["params"]),
                            ("opt_state", opt_state, restored["opt_state"])):
        w, g = jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)
        if len(w) != len(g) or not all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(w, g)):
            raise AssertionError(f"checkpoint {name} not bit-identical")
    if ckpt.load_meta(ckpt_dir, step).get("cursor", {}).get("step") != steps:
        raise AssertionError("checkpoint marker lost the data cursor")
    return {
        "steps": steps, "epoch_losses": [round(x, 4) for x in losses],
        "final_accuracy": round(float(stats[-1][2]), 4),
        "checkpoints": health["train_checkpoints"],
        "restored_step": step, "params_on": params_on, "opt_on": opt_on,
    }


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _run_kernel(fn, args, interpret):
    """Run ``fn(*args, interpret=...)``; return (outputs, lowered-for-TPU
    text).  On the chip the text is the very program that is then compiled
    and executed; in a CPU dry run the kernel body executes in the Pallas
    interpreter and the TPU lowering is taken alongside."""
    import jax

    if interpret:
        text = jax.jit(functools.partial(fn, interpret=False)).trace(
            *args).lower(lowering_platforms=("tpu",)).as_text()
        out = jax.jit(functools.partial(fn, interpret=True))(*args)
    else:
        lowered = jax.jit(functools.partial(fn, interpret=False)).lower(*args)
        text = lowered.as_text()
        out = lowered.compile()(*args)
    if "tpu_custom_call" not in text:
        raise AssertionError(
            f"{getattr(fn, '__name__', fn)}: the lowered program holds no "
            "TPU custom call — the kernel did not run")
    return out


def phase_kernels(*, platform, interpret=False, top1_batches=(1, 2, 8, 128),
                  classes=1001, norm_shape=(128, 224, 224, 3),
                  attn_shapes=((2, 256, 4, 32), (2, 197, 3, 64)),
                  attn_stream_shape=(2, 577, 16, 64),
                  decode_shapes=((16, 1024, 20, 20, 64), (8, 4096, 32, 2, 128)),
                  ring_shapes=((16, 4096, 128, 8, 128),),
                  chunk_shapes=((False, 1024, 16384, 128, 8, 128), (True, 1024, 4096, 128, 8, 128)),
                  expert_shapes=((16, 4096, 4096, 16, 2), (1024, 4096, 4096, 16, 2),
                                 (1024, 2048, 1792, 32, 4))):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nnstreamer_tpu.models.transformer import _attend_blocked, kv_attend_write
    from nnstreamer_tpu.ops.chunk_attention import chunk_attention
    from nnstreamer_tpu.ops.decode_attention import decode_attention, ring_skip
    from nnstreamer_tpu.ops.expert_ffn import MAX_TOKENS, held_experts_ffn
    from nnstreamer_tpu.ops.flash_attention import (
        flash_attention, flash_attention_grad)
    from nnstreamer_tpu.ops.labeling import top1
    from nnstreamer_tpu.ops.preprocess import normalize_u8
    from nnstreamer_tpu.parallel.ring_attention import reference_attention

    rng = np.random.default_rng(3)
    checked = []

    for b in top1_batches:
        x = jnp.asarray(rng.normal(size=(b, classes)).astype(np.float32))
        idx, val = _run_kernel(
            lambda x, interpret: top1(x, interpret=interpret), (x,), interpret)
        _assert_on((idx, val), platform, "top1 output")
        ridx, rval = jax.jit(lambda x: top1(x, use_pallas=False))(x)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_array_equal(np.asarray(val), np.asarray(rval))
        checked.append(f"top1{(b, classes)}")

    x = jnp.asarray(rng.integers(0, 256, norm_shape, dtype=np.uint8))
    y = _run_kernel(
        lambda x, interpret: normalize_u8(x, interpret=interpret),
        (x,), interpret)
    want = jax.jit(lambda x: normalize_u8(x, use_pallas=False))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    checked.append(f"normalize_u8{tuple(norm_shape)}")

    # bf16 in, f32 accumulation: ~2^-8 relative on O(1) outputs
    tol = 2e-2
    # the stream cell's shape (ViT-L/16-384) runs non-causal only: the one
    # pass over resident keys, an overhanging 577 -> 640 block, head pairs
    for shape, causals in [(s, (True, False)) for s in attn_shapes] + [
            (attn_stream_shape, (False,))]:
        q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
                   .astype(jnp.bfloat16) for _ in range(3))
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        for causal in causals:
            out = _run_kernel(
                lambda q, k, v, interpret, c=causal: flash_attention(
                    q, k, v, causal=c, interpret=interpret),
                (q, k, v), interpret)
            with jax.default_matmul_precision("highest"):
                ref = reference_attention(*f32, causal=causal)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
            if not err < tol:
                raise AssertionError(
                    f"flash {shape} causal={causal}: max err {err}")
            checked.append(f"flash{shape} causal={causal} err={err:.1e}")

    # the per-token read of a slotted KV cache, bounded by fill: slots (B,
    # max_seq S, H query heads on J KV heads of Dh) at fills from none to
    # all, one idle, against the jnp form it stands in for
    # ``ring``: a window layer's leaf, written round (its S rows are the
    # window): positions run past S, and a full leaf's step leaves out the
    # row the new token overwrites
    mk = lambda *shape: jnp.asarray(
        rng.normal(size=shape).astype(np.float32)).astype(jnp.bfloat16)
    for ring, (B, S, H, J, Dh) in [(False, s) for s in decode_shapes] + [
            (True, s) for s in ring_shapes]:
        ck, cv = mk(B, S, J * Dh), mk(B, S, J * Dh)
        q, k, v = mk(B, 1, H * Dh), mk(B, 1, J * Dh), mk(B, 1, J * Dh)
        pos = jnp.asarray(rng.integers(0, (3 if ring else 1) * S + 1, B),
                          jnp.int32).at[0].set(S)
        active = jnp.ones((B,), jnp.int32).at[1].set(0)
        n = jnp.where(active > 0, jnp.minimum(pos, S), 0)
        out = _run_kernel(
            lambda ck, cv, q, k, v, n, pos, interpret, H=H, ring=ring: decode_attention(
                ck, cv, q, k, v, n, n_heads=H, interpret=interpret,
                skip=ring_skip(pos, ck.shape[1]) if ring else None),
            (ck, cv, q, k, v, n, pos), interpret)
        ref = jax.jit(lambda *a, H=H, J=J, ring=ring: kv_attend_write(
            *a, H, n_kv_heads=J, single_device=False, ring=ring)[2])(ck, cv, q, k, v, pos)
        live = np.asarray(active) > 0
        err = float(np.max(np.abs(
            np.asarray(out, np.float32) - np.asarray(ref, np.float32))[live]))
        name = f"decode_attention{(B, S, H, J, Dh)}" + (" ring" if ring else "")
        if not err < tol:
            raise AssertionError(f"{name}: max err {err}")
        checked.append(f"{name} err={err:.1e}")

    # a prefill chunk's attention over one slot's rows of a global leaf and
    # of a round window leaf, bounded by fill, against the blocked jnp form
    for ring, T, S, H, J, Dh in chunk_shapes:
        ck, cv = mk(2, S, J * Dh), mk(2, S, J * Dh)
        q, k, v = mk(2, T, H * Dh), mk(2, T, J * Dh), mk(2, T, J * Dh)
        pos = jnp.asarray([S // 4 + 3, (3 if ring else 1) * S - T], jnp.int32)
        out = _run_kernel(
            lambda ck, cv, q, k, v, pos, interpret, H=H, ring=ring: chunk_attention(
                ck, cv, q, k, v, pos, n_heads=H, ring=ring, interpret=interpret),
            (ck, cv, q, k, v, pos), interpret)
        ref = jax.jit(functools.partial(_attend_blocked, H=H, J=J, ring=ring))(
            ck, cv, q, k, v, pos)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        name = f"chunk_attention{(T, S, H, J, Dh)}" + (" ring" if ring else "")
        if not err < tol:
            raise AssertionError(f"{name}: max err {err}")
        checked.append(f"{name} err={err:.1e}")

    # the held experts' part in its gated form (three matrices an expert),
    # by the op's one entry: a decode step's rows through the small-batch
    # kernel, a 1024-row prefill chunk at both long-chunk cells' widths
    # through the grouped one, top-k of the held experts, against a loop
    # over the experts
    for M, D, F, held, k in expert_shapes:
        x = mk(M, D)
        wg, wu, wd = (mk(held, *shape) * (shape[0] ** -0.5)
                      for shape in ((D, F), (D, F), (F, D)))
        chosen = jnp.asarray(np.stack(
            [rng.permutation(held)[:k] for _ in range(M)]), jnp.int32)
        weights = jnp.full((M, k), 1.0 / k, jnp.float32)
        out = _run_kernel(
            lambda x, lid, w, up, down, gate_w, interpret: held_experts_ffn(
                x, lid, w, up, down, gate_w, interpret=interpret),
            (x, chosen, weights, wu, wd, wg), interpret)

        def loop(x, chosen, wu, wd, wg):
            def one(acc, e):
                mm = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
                hid = (jax.nn.silu(mm(x, wg[e])) * mm(x, wu[e])).astype(x.dtype)
                gate = jnp.sum(jnp.where(chosen == e, 1.0 / k, 0.0), axis=1, keepdims=True)
                return acc + gate * mm(hid, wd[e]), None

            return jax.lax.scan(one, jnp.zeros((M, D), jnp.float32), jnp.arange(held))[0]

        ref = jax.jit(loop)(x, chosen, wu, wd, wg)
        err = float(jnp.max(jnp.abs(out - ref)))
        name = ("grouped" if M > MAX_TOKENS else "touched") + "_experts_ffn gated"
        if not err < tol:
            raise AssertionError(f"{name} {(M, D, F, held)}: max err {err}")
        checked.append(f"{name}{(M, D, F, held)} err={err:.1e}")

    def loss_kernel(q, k, v, interpret):
        o = flash_attention_grad(q, k, v, True, 128, 128, interpret)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    q, k, v = (jnp.asarray(rng.normal(size=attn_shapes[0]).astype(np.float32))
               for _ in range(3))
    grads = _run_kernel(
        lambda q, k, v, interpret: jax.grad(
            functools.partial(loss_kernel, interpret=interpret),
            argnums=(0, 1, 2))(q, k, v),
        (q, k, v), interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(grads, want))
    scale = max(float(jnp.max(jnp.abs(b))) for b in want)
    if not err < 5e-2 * max(1.0, scale):
        raise AssertionError(f"flash_attention_grad: max err {err}")
    checked.append(f"flash_grad{attn_shapes[0]} err={err:.1e}")
    return {"kernels": checked, "interpret": bool(interpret)}


# ---------------------------------------------------------------------------
# mesh (>= 4 devices)
# ---------------------------------------------------------------------------
def phase_mesh(*, platform, stream_sizes=None, generate_sizes=None,
               replica_frames=32, n=4):
    from nnstreamer_tpu.backends.jax_xla import (
        register_jax_model, unregister_jax_model)
    from nnstreamer_tpu.models import build
    from nnstreamer_tpu.pipeline import parse_pipeline

    s = dict(size=224, width="1.0", classes=1001, max_batch=128)
    s.update(stream_sizes or {})
    zoo = {"size": str(s["size"]), "width": s["width"],
           "classes": str(s["classes"])}
    fn, params, in_spec, out_spec = build("mobilenet_v2", dict(zoo))
    labels = _write_labels(s["classes"])
    frames = _make_frames(s["max_batch"] + 5, s["size"], seed=1)
    register_jax_model("smoke_mnet", fn, params, in_spec, out_spec)
    try:
        # one logical filter over a dp mesh: batches scatter over 4 devices
        pipe = parse_pipeline(
            _labeling_graph(labels, s["max_batch"], f"mesh=dp:{n} "),
            name="smoke-mesh-dp")
        pipe.start()
        try:
            be = pipe["f"].backend
            dp_devices = _assert_on(be._params, platform, "dp params", n)
            dp_labels = _run_labeling(pipe, frames, 600.0)
            _assert_outputs_stay(be, frames[:s["max_batch"]], platform, n)
            if be.mesh_scatters < 1:
                raise AssertionError("no batch was scattered over the mesh")
            _assert_healthy(pipe)
        finally:
            pipe.stop()

        # four one-chip replicas, each pinned to its own device
        pipes = [parse_pipeline(
            _labeling_graph(labels, replica_frames,
                            f"accelerator=true:{platform}.{i} "),
            name=f"smoke-replica-{i}") for i in range(n)]
        for p in pipes:
            p.start()
        try:
            replica_devices, replica_labels = [], []
            for p in pipes:
                replica_devices += _assert_on(
                    p["f"].backend._params, platform, "replica params", 1)
                replica_labels.append(
                    _run_labeling(p, frames[:replica_frames], 600.0))
                _assert_outputs_stay(
                    p["f"].backend, frames[:replica_frames], platform, 1)
                _assert_healthy(p)
        finally:
            for p in pipes:
                p.stop()
    finally:
        unregister_jax_model("smoke_mnet")
    if len(set(replica_devices)) != n:
        raise AssertionError(f"replicas share devices: {replica_devices}")
    # same frames, same weights: every placement must label alike (scores
    # agree to reduced-precision rounding across program shapes)
    for labels_i in replica_labels:
        for (a, sa), (b, sb) in zip(labels_i, dp_labels):
            if a != b and abs(sa - sb) > 0.05 * max(1.0, abs(sb)):
                raise AssertionError(f"replica label {a} != dp label {b}")

    gen = phase_generate(platform=platform, mesh=f"tp:{n}", sid=930,
                         **(generate_sizes or {}))
    return {"dp_devices": dp_devices, "replica_devices": replica_devices,
            "tp_params_on": gen["params_on"], "tp_cache_on": gen["cache_on"],
            "tp_streams": gen["streams"]}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def _cache_entries(path):
    try:
        return len(os.listdir(path)) if path else 0
    except OSError:
        return 0


def main() -> int:
    t_start = time.perf_counter()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: jax found no usable backend: {e}",
              file=sys.stderr)
        return 3
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax.devices()[0] is {dev} "
              f"(platform {dev.platform!r}, JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 3

    from nnstreamer_tpu.core import compile_cache
    from nnstreamer_tpu.native import runtime as native_runtime

    cache_dir = compile_cache.enable()
    # every pipeline below runs on the native mailbox: build it (blocking)
    # before the first start(), and refuse to measure anything else
    mailbox = native_runtime.mailbox_impl()
    if mailbox != "native":
        print("chip_smoke: native mailbox failed to build", file=sys.stderr)
        return 4
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    entries_before = _cache_entries(cache_dir)
    print(json.dumps({
        "phase": "start", **device, "jax": jax.__version__,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": entries_before, "mailbox": mailbox,
    }), flush=True)

    meter = CompileMeter()
    phases = [("stream", phase_stream), ("generate", phase_generate),
              ("train", phase_train), ("kernels", phase_kernels)]
    if len(devices) >= 4:
        phases.append(("mesh", phase_mesh))
    summary = {}
    for name, fn in phases:
        c0, t0 = meter.snapshot(), time.perf_counter()
        try:
            result = fn(platform=dev.platform)
        except BaseException as e:
            # say which phase died, end stdout on the verdict, die with it
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:2000],
                              "claim": None}), flush=True)
            print(json.dumps({"ok": False, "device": device}), flush=True)
            raise
        c1 = meter.snapshot()
        line = {
            "phase": name, "ok": True, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(c1["compile_s"] - c0["compile_s"], 2),
            "compiles": c1["compiles"] - c0["compiles"],
            "cache_hits": c1["cache_hits"] - c0["cache_hits"],
            "cache_misses": c1["cache_misses"] - c0["cache_misses"],
            "mailbox": mailbox, **result,
        }
        print(json.dumps(line), flush=True)
        summary[name] = {k: line[k] for k in (
            "seconds", "compile_seconds", "cache_hits", "cache_misses")}
    if len(devices) < 4:
        print(json.dumps({"phase": "mesh",
                          "not_run": f"{len(devices)} device(s)"}),
              flush=True)
    total = meter.snapshot()
    print(json.dumps({
        "phase": "summary", "ok": True, "phases": summary,
        "seconds": round(time.perf_counter() - t_start, 2),
        "compile_seconds": round(total["compile_s"], 2),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": [entries_before, _cache_entries(cache_dir)],
        "cache_hits": total["cache_hits"],
        "cache_misses": total["cache_misses"],
        "mailbox": mailbox, "claim": None,
    }), flush=True)
    # the verdict: exactly these keys, the device as jax reports it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
