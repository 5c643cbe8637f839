"""JAX/Optax trainer delegate — the in-framework analog of NNTrainer.

Model config (the ``model-config`` property, JSON file or inline JSON)::

    {"arch": "mnist_cnn", "arch_props": {"dtype": "float32"},
     "optimizer": "adam", "learning_rate": 1e-3, "batch_size": 32,
     "loss": "softmax_ce"}

Data protocol (≙ trainer ABI push_data, SURVEY §3.4): each incoming frame
carries ``num-inputs`` input tensors followed by ``num-labels`` label
tensors; every ``num-training-samples`` + ``num-validation-samples`` frames
form one epoch (train split first, then validation) — the exact contract of
the reference element (``gsttensor_trainer.c`` header: total expected =
(train+valid)×epochs).

The training loop runs on a dedicated thread; samples stream in through a
bounded queue (backpressure to the pipeline).  Each optimizer step is one
jitted donate-argnums XLA call over a micro-batch.

Crash safety (net-new vs the reference; the preemptible-TPU contract):

* **Step-grain durable checkpoints** — ``checkpoint-steps=N`` saves
  params + optimizer state every N optimizer steps (plus every epoch
  boundary) under ``checkpoint-path``, each committed by an atomic
  completion marker (core/checkpoint.py) carrying the **data cursor**:
  global step, epoch, position-in-epoch, stream position, and the last
  datarepo ``(epoch, sample_index)`` incorporated.  A torn save is never
  resumed.
* **Exact-step resume** — a restarted pipeline (``resume=true``) restores
  the newest durable checkpoint and fast-forwards the deterministic
  datarepo replay by the cursor's stream position: zero samples re-trained,
  zero lost, final params bit-identical to an uninterrupted run at
  checkpoint grain (the replay skip only engages for frames stamped with
  the datarepo ``epoch`` meta; direct-API feeds keep the legacy
  continue-from-epoch behavior).
* **Resumable pause** — :meth:`pause`/:meth:`unpause` gate the train loop
  between steps; a paused trainer stops consuming, the bounded queue
  backpressures the pipeline, and no sample is lost (the element couples
  this to the memory watermark so training never starves serving).
* **Fault sites** — ``trainer.step``, ``trainer.checkpoint`` (pre-save)
  and ``trainer.checkpoint.commit`` (the torn-save gap between the Orbax
  write and the marker) make every failure path chip-free testable.
"""

from __future__ import annotations

import functools
import json
import os
import queue
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core.buffer import TensorFrame
from ..core.log import get_logger
from .base import (
    EVENT_EPOCH_COMPLETION,
    EVENT_TRAINING_COMPLETION,
    TrainerBackend,
    TrainerStatus,
    register_trainer,
)

log = get_logger("jax-trainer")


def _truthy(v) -> bool:
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes", "on")
    return bool(v)


def make_loss_fn(fn, loss_kind: str):
    """The one loss builder shared by the trainer's train/eval steps and
    the model_validator's held-out scorer (the gate must judge candidates
    by the same objective training optimizes).  Returns
    ``loss_fn(params, xs, ys) -> (loss, accuracy)``, jit-traceable."""
    import jax
    import jax.numpy as jnp

    def loss_fn(p, xs, ys):
        logits = fn(p, xs)[0]
        if loss_kind == "softmax_ce":
            labels = ys[0]
            # one-hot only when the trailing dim is the class dim;
            # (B,1) integer labels must NOT be argmax'd
            if labels.ndim == logits.ndim and labels.shape[-1] == logits.shape[-1]:
                labels = jnp.argmax(labels, axis=-1)
            labels = labels.reshape(-1).astype(jnp.int32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
            acc = jnp.mean(
                (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
            )
            return -jnp.mean(ll), acc
        if loss_kind == "mse":
            target = ys[0].astype(logits.dtype)
            return jnp.mean((logits - target) ** 2), jnp.zeros(())
        raise ValueError(f"unknown loss {loss_kind!r}")

    return loss_fn


class JaxTrainer(TrainerBackend):
    NAME = "jax"

    def __init__(self):
        super().__init__()
        self._cfg: Dict[str, Any] = {}
        self._props: Dict[str, Any] = {}
        self._q: "queue.Queue[Optional[TensorFrame]]" = queue.Queue(256)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._paused = threading.Event()
        self.params = None
        self.opt_state = None  # newest optimizer state (same devices as params)
        self._fn = None
        self.error: Optional[BaseException] = None
        # mesh (``mesh=`` grammar, PR-13) — set by _build when armed
        self._mesh = None
        self._mesh_axes: Dict[str, int] = {}
        self._batch_put = None  # device_put batches onto the dp axis
        # exact step/sample accounting (the element exports these as
        # nns.train.*; the chaos harness and the kill/resume truth table
        # pin them)
        self.steps = 0                # optimizer steps completed
        self.samples_trained = 0      # samples incorporated by train steps
        self.checkpoints = 0          # durable (marker-committed) saves
        self.resumes = 0              # restores from a durable checkpoint
        self.resumed_at = -1          # global step the last resume restored
        self.replay_skipped = 0       # already-trained frames skipped on resume
        self.gap_samples = 0          # frames dropped realigning a mid-stream restart
        self.trained_log: List[Tuple[int, int]] = []  # (epoch, sample_index) ledger

    # -- ABI ----------------------------------------------------------------
    def create(self, props: Dict[str, Any]) -> None:
        self._props = dict(props)
        cfg_text = props.get("model-config") or "{}"
        if os.path.isfile(cfg_text):
            with open(cfg_text) as f:
                self._cfg = json.load(f)
        else:
            self._cfg = json.loads(cfg_text)
        if "arch" not in self._cfg:
            raise ValueError("trainer model-config must name an 'arch'")

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._train_loop, name="jax-trainer", daemon=True
        )
        self._thread.start()

    def push_data(self, frame: TensorFrame) -> None:
        while not self._stop.is_set():
            if self._thread is not None and not self._thread.is_alive():
                return  # trainer died; don't spin (its error is surfaced)
            try:
                self._q.put(frame, timeout=0.2)
                return
            except queue.Full:
                continue

    def _put_sentinel(self) -> None:
        # never block: if the queue is full the consumer is gone — drain one
        try:
            self._q.put_nowait(None)
        except queue.Full:
            try:
                self._q.get_nowait()
                self._q.put_nowait(None)
            except (queue.Empty, queue.Full):
                pass

    def end_of_data(self) -> None:
        # block-put like push_data: the consumer is still alive here, and a
        # lossy put would drop a real sample from the final epoch
        while not self._stop.is_set():
            if self._thread is None or not self._thread.is_alive():
                self._put_sentinel()
                return
            try:
                self._q.put(None, timeout=0.2)
                return
            except queue.Full:
                continue

    def stop(self) -> None:
        self._stop.set()
        self._put_sentinel()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def thread_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- resumable pause (starvation-free co-hosting) ------------------------
    def pause(self) -> None:
        """Stop taking train steps at the next step boundary.  The loop
        stops consuming, the bounded queue backpressures the pipeline:
        resumable, zero samples lost."""
        self._paused.set()

    def unpause(self) -> None:
        self._paused.clear()

    @property
    def paused(self) -> bool:
        return self._paused.is_set()

    # -- internals ----------------------------------------------------------
    def _build(self):
        import jax
        import optax

        from .. import models as zoo
        from ..core.compile_cache import enable as enable_compile_cache
        from ..core.hw import default_device

        enable_compile_cache()  # before the trainer's first compile
        arch = self._cfg["arch"]
        arch_props = {k: str(v) for k, v in self._cfg.get("arch_props", {}).items()}
        fn, params, _, _ = zoo.build(arch, arch_props)
        load_path = self._props.get("model-load-path")
        if load_path:
            params = _load_params(load_path, params)
        # commit params to the training device (loaded checkpoints arrive
        # as host arrays) so every step compiles and runs there, and init
        # the optimizer as one compiled call (eager tree_map would
        # dispatch a tiny op per leaf)
        mesh_spec = str(self._props.get("mesh") or "")
        if mesh_spec.strip() not in ("", "0", "off", "none"):
            params = self._arm_mesh(mesh_spec, params)
            if zoo.takes_single_device(fn):
                # a model that holds a Mosaic kernel (the ViT's attention)
                # keeps to XLA in a program partitioned over the mesh
                fn = functools.partial(fn, single_device=False)
        else:
            params = jax.device_put(params, default_device())
        lr = float(self._cfg.get("learning_rate", 1e-3))
        opt_name = self._cfg.get("optimizer", "adam")
        tx = {
            "adam": optax.adam,
            "adamw": optax.adamw,
            "sgd": optax.sgd,
        }[opt_name](lr)
        opt_state = jax.jit(tx.init)(params)

        loss_fn = make_loss_fn(fn, self._cfg.get("loss", "softmax_ce"))

        @jax.jit
        def eval_step(p, xs, ys):
            return loss_fn(p, xs, ys)

        def _step(p, opt, xs, ys):
            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, xs, ys)
            updates, opt = tx.update(grads, opt, p)
            p = optax.apply_updates(p, updates)
            return p, opt, loss, acc

        train_step = jax.jit(_step, donate_argnums=(0, 1))
        return fn, params, opt_state, train_step, eval_step

    def _arm_mesh(self, spec: str, params):
        """Shard jitted train steps via the serving ``mesh=`` grammar
        (PR-13): params/opt_state replicated over the mesh, batches
        scattered on the ``dp`` axis.  Gradients psum implicitly through
        jit's partitioner — the training analog of the filter's sharded
        invoke."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import claim_devices, make_mesh, parse_mesh_spec

        axes = parse_mesh_spec(spec)
        devices = claim_devices(axes)
        mesh = make_mesh(axes, devices)
        self._mesh, self._mesh_axes = mesh, axes
        repl = NamedSharding(mesh, P())
        dp = int(mesh.shape.get("dp", 1))
        if dp > 1:
            batch_sh = NamedSharding(mesh, P("dp"))

            def put(a):
                # the final partial batch may not split across dp —
                # replicate it (one odd-shaped compile, exact math)
                sh = batch_sh if a.shape[0] % dp == 0 else repl
                return jax.device_put(a, sh)

            self._batch_put = put
        else:
            self._batch_put = lambda a: jax.device_put(a, repl)
        log.info("trainer mesh armed: %s over %d device(s)", spec, mesh.size)
        return jax.device_put(params, repl)

    def _batches(self, samples, batch_size: int):
        for i in range(0, len(samples), batch_size):
            chunk = samples[i : i + batch_size]
            xs = [np.stack([s[0][t] for s in chunk]) for t in range(len(chunk[0][0]))]
            ys = [np.stack([s[1][t] for s in chunk]) for t in range(len(chunk[0][1]))]
            yield xs, ys

    def _train_loop(self) -> None:
        try:
            self._fn, self.params, opt_state, train_step, eval_step = self._build()
            opt_state, cursor = self._maybe_resume(opt_state)
        except Exception as e:
            log.exception("trainer build failed")
            self.error = e  # surfaced by the element's watchdog sweep
            self.notify(EVENT_TRAINING_COMPLETION)
            return
        try:
            self._train_body(opt_state, train_step, eval_step, cursor)
        except Exception as e:
            log.exception("training failed")
            self.error = e
        self.notify(EVENT_TRAINING_COMPLETION)

    def _maybe_resume(self, opt_state):
        """Durable-checkpoint resume (preemptible-TPU recovery): restore
        params + optimizer state + the data cursor from the newest
        marker-committed checkpoint under ``checkpoint-path`` when
        ``resume=1``.  Torn saves are invisible (core/checkpoint.py)."""
        from ..core import checkpoint as ckpt

        path = self._props.get("checkpoint-path")
        if not (path and _truthy(self._props.get("resume", False))):
            return opt_state, None
        step = ckpt.latest_step(path)
        if step is None:
            log.info("resume requested but no checkpoint under %s", path)
            return opt_state, None
        state = ckpt.restore_state(
            path, step, {"params": self.params, "opt_state": opt_state}
        )
        self.params = state["params"]
        cursor = ckpt.load_meta(path, step).get("cursor")
        if cursor is None:
            # pre-cursor checkpoint id semantics: id == completed epochs
            cursor = {"unit": "epoch", "epoch": int(step), "epoch_pos": 0,
                      "stream_pos": 0, "step": 0}
        self.resumes += 1
        self.resumed_at = int(cursor.get("step", 0))
        self.steps = self.resumed_at
        log.info("resumed from %s step %d (cursor %s)", path, step, cursor)
        return state["opt_state"], cursor

    def _ckpt(self, opt_state, cursor: Dict[str, Any]) -> None:
        """One durable checkpoint: Orbax write, then the atomic
        completion marker carrying the data cursor.  The two fault sites
        bracket the torn-save gap."""
        from ..core import checkpoint as ckpt
        from ..core.resilience import FAULTS

        path = self._props.get("checkpoint-path")
        if not path:
            return
        cid = int(cursor["step"] if cursor["unit"] == "step"
                  else cursor["epoch"])
        if cid == getattr(self, "_last_ckpt_id", None):
            return  # epoch boundary coinciding with a step checkpoint
        FAULTS.check("trainer.checkpoint")
        ckpt.write_state(path, cid, {"params": self.params, "opt_state": opt_state})
        FAULTS.check("trainer.checkpoint.commit")
        ckpt.commit_state(path, cid, {"cursor": cursor})
        keep = int(self._props.get("checkpoint-keep", 3))
        ckpt.prune(path, keep)
        self.checkpoints += 1
        self._last_ckpt_id = cid
        log.info("checkpointed %s %d to %s", cursor["unit"], cid, path)

    def _train_body(self, opt_state, train_step, eval_step,
                    cursor: Optional[Dict[str, Any]] = None) -> None:
        from ..core.resilience import FAULTS

        n_in = int(self._props.get("num-inputs", 1))
        n_lab = int(self._props.get("num-labels", 1))
        n_train = int(self._props.get("num-training-samples", 0))
        n_valid = int(self._props.get("num-validation-samples", 0))
        epochs = int(self._props.get("epochs", 1))
        batch_size = int(self._cfg.get("batch_size", 32))
        ckpt_steps = int(self._props.get("checkpoint-steps", 0) or 0)
        ckpt_interval = int(self._props.get("checkpoint-interval", 1))
        per_epoch = n_train + n_valid
        midstream = _truthy(self._props.get("_midstream-restart", False))

        cursor = cursor or {}
        done_epochs = int(cursor.get("epoch", 0))
        gstep = int(cursor.get("step", 0))
        epoch_pos = int(cursor.get("epoch_pos", 0))
        stream_pos = int(cursor.get("stream_pos", 0))
        ep_losses = [float(x) for x in cursor.get("ep_losses", [])]
        ep_accs = [float(x) for x in cursor.get("ep_accs", [])]
        # resume fast-forward: the deterministic datarepo replay re-emits
        # every frame from sample 0; skip exactly the cursor's stream
        # position (only meta-stamped frames — a direct-API feed is the
        # caller resuming where IT left off, so nothing is skipped)
        skip_left = 0 if midstream else stream_pos
        # mid-stream backend restart: the live stream does NOT replay, and
        # frames between the checkpoint and the crash are gone — drop the
        # rest of the partial epoch (counted) and realign exactly at the
        # next epoch boundary the datarepo meta announces
        realign = midstream and per_epoch > 0
        realign_seen: Optional[int] = None

        train_buf: List[Tuple[List[np.ndarray], List[np.ndarray], Any]] = []
        valid_buf: List[Tuple[List[np.ndarray], List[np.ndarray], Any]] = []
        tail_buf: List[Tuple[List[np.ndarray], List[np.ndarray]]] = []

        def cursor_now(unit: str) -> Dict[str, Any]:
            c: Dict[str, Any] = {
                "unit": unit, "step": gstep, "epoch": done_epochs,
                "epoch_pos": epoch_pos, "stream_pos": stream_pos,
                "ep_losses": ep_losses, "ep_accs": ep_accs,
            }
            if self.trained_log:
                c["meta_epoch"], c["sample_index"] = self.trained_log[-1]
            return c

        def do_step(batch) -> None:
            nonlocal opt_state, gstep, stream_pos
            FAULTS.check("trainer.step")
            xs = [np.stack([s[0][t] for s in batch])
                  for t in range(len(batch[0][0]))]
            ys = [np.stack([s[1][t] for s in batch])
                  for t in range(len(batch[0][1]))]
            if self._batch_put is not None:
                xs = [self._batch_put(a) for a in xs]
                ys = [self._batch_put(a) for a in ys]
            self.params, opt_state, loss, acc = train_step(
                self.params, opt_state, xs, ys
            )
            self.opt_state = opt_state
            gstep += 1
            stream_pos += len(batch)
            self.steps = gstep
            self.samples_trained += len(batch)
            for s in batch:
                if s[2] is not None:
                    self.trained_log.append(s[2])
            ep_losses.append(float(loss))
            ep_accs.append(float(acc))
            if ckpt_steps > 0 and gstep % ckpt_steps == 0:
                self._ckpt(opt_state, cursor_now("step"))

        def finish_epoch() -> None:
            nonlocal done_epochs, epoch_pos, stream_pos
            nonlocal ep_losses, ep_accs, valid_buf
            vlosses, vaccs = [], []
            for bx, by in self._batches(
                    [(s[0], s[1]) for s in valid_buf], batch_size
            ) if valid_buf else ():
                loss, acc = eval_step(self.params, bx, by)
                vlosses.append(float(loss))
                vaccs.append(float(acc))
            for s in valid_buf:
                if s[2] is not None:
                    self.trained_log.append(s[2])
            stream_pos += len(valid_buf)
            done_epochs += 1
            epoch_pos = 0
            valid_buf = []
            self.status = TrainerStatus(
                epoch_count=done_epochs,
                training_loss=float(np.mean(ep_losses)) if ep_losses else 0.0,
                training_accuracy=float(np.mean(ep_accs)) if ep_accs else 0.0,
                validation_loss=float(np.mean(vlosses)) if vlosses else 0.0,
                validation_accuracy=float(np.mean(vaccs)) if vaccs else 0.0,
            )
            ep_losses, ep_accs = [], []
            self.notify(EVENT_EPOCH_COMPLETION)
            if ckpt_steps > 0:
                self._ckpt(opt_state, cursor_now("step"))
            elif ckpt_interval > 0 and done_epochs % ckpt_interval == 0:
                self._ckpt(opt_state, cursor_now("epoch"))

        while not self._stop.is_set() and (epochs <= 0 or done_epochs < epochs):
            # resumable pause: between steps only — never mid-step, never
            # consuming (the bounded queue backpressures the pipeline)
            if self._paused.is_set():
                self._stop.wait(0.05)
                continue
            try:
                frame = self._q.get(timeout=0.2)
            except queue.Empty:
                continue
            if frame is None:
                break
            meta_ep = frame.meta.get("epoch") if frame.meta else None
            if skip_left > 0 and meta_ep is not None:
                skip_left -= 1
                self.replay_skipped += 1
                continue
            if realign and meta_ep is not None:
                if realign_seen is None:
                    realign_seen = int(meta_ep)
                if int(meta_ep) == realign_seen:
                    self.gap_samples += 1
                    continue
                realign = False  # fresh epoch boundary: exact from here
                epoch_pos = 0
                train_buf, valid_buf = [], []
                ep_losses, ep_accs = [], []
            elif realign and meta_ep is None:
                realign = False  # no meta: continue from the cursor as-is
            xs = [np.asarray(t) for t in frame.tensors[:n_in]]
            ys = [np.asarray(t) for t in frame.tensors[n_in : n_in + n_lab]]
            tag = (
                (int(meta_ep), int(frame.meta.get("sample_index", -1)))
                if meta_ep is not None else None
            )
            if not per_epoch:
                tail_buf.append((xs, ys))
                continue
            if epoch_pos < n_train:
                train_buf.append((xs, ys, tag))
                flush = (len(train_buf) >= batch_size
                         or epoch_pos == n_train - 1)
            else:
                valid_buf.append((xs, ys, tag))
                flush = False
            epoch_pos += 1
            if flush:
                batch, train_buf = train_buf, []
                do_step(batch)
            if epoch_pos >= per_epoch:
                finish_epoch()

        if (train_buf or valid_buf) and not self._stop.is_set():
            log.warning(
                "dropping %d leftover samples (incomplete epoch of %d)",
                len(train_buf) + len(valid_buf), per_epoch,
            )
        if tail_buf and not self._stop.is_set():
            # num-training-samples unset: the whole stream is the dataset;
            # honor epochs= by re-iterating it instead of silently saving
            # the untrained init (done_epochs already counts resumed ones)
            while done_epochs < max(1, epochs) and not self._stop.is_set():
                for bx, by in self._batches(tail_buf, batch_size):
                    FAULTS.check("trainer.step")
                    if self._batch_put is not None:
                        bx = [self._batch_put(a) for a in bx]
                        by = [self._batch_put(a) for a in by]
                    self.params, opt_state, loss, acc = train_step(
                        self.params, opt_state, bx, by
                    )
                    gstep += 1
                    self.steps = gstep
                    self.samples_trained += len(bx[0])
                    ep_losses.append(float(loss))
                    ep_accs.append(float(acc))
                done_epochs += 1
                self.status = TrainerStatus(
                    epoch_count=done_epochs,
                    training_loss=float(np.mean(ep_losses)) if ep_losses else 0.0,
                    training_accuracy=float(np.mean(ep_accs)) if ep_accs else 0.0,
                )
                ep_losses, ep_accs = [], []
                self.notify(EVENT_EPOCH_COMPLETION)
                epoch_pos = 0
                if ckpt_steps > 0:
                    self._ckpt(opt_state, cursor_now("step"))
                elif ckpt_interval > 0 and done_epochs % ckpt_interval == 0:
                    self._ckpt(opt_state, cursor_now("epoch"))
        save_path = self._props.get("model-save-path")
        if save_path and self.params is not None:
            _save_params(save_path, self.params)
            log.info("model saved to %s", save_path)


def _save_params(path: str, params) -> None:
    if path.endswith(".msgpack"):
        from flax import serialization

        from ..core.checkpoint import atomic_write_bytes

        # temp-sibling + fsync + os.replace (the datareposink pattern):
        # a crash mid-save leaves the previous complete model, never a
        # torn file a co-hosted serving filter could hot-load
        atomic_write_bytes(path, serialization.to_bytes(params))
    else:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckptr.save(os.path.abspath(path), params, force=True)
        ckptr.wait_until_finished()


def _load_params(path: str, template):
    if path.endswith(".msgpack"):
        from flax import serialization

        with open(path, "rb") as f:
            return serialization.from_bytes(template, f.read())
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(os.path.abspath(path), template)


def write_synthetic_mnist(out_dir: str, n: int) -> Tuple[str, str]:
    """Write ``n`` MNIST-shaped samples of a synthetic learnable task
    (class = brightest of 10 row-bands; seeded, no network) through
    ``appsrc ! datareposink``; returns (data_path, json_path)."""
    from ..pipeline import parse_pipeline

    os.makedirs(out_dir, exist_ok=True)
    data_path = os.path.join(out_dir, "data.bin")
    json_path = os.path.join(out_dir, "data.json")
    rng = np.random.default_rng(0)
    wpipe = parse_pipeline(
        f"appsrc name=src ! datareposink location={data_path} json={json_path}"
    )
    wpipe.start()
    for i in range(n):
        label = i % 10
        img = rng.normal(0.2, 0.05, (28, 28, 1)).astype(np.float32)
        img[label * 2 : label * 2 + 3, :, :] += 0.8
        wpipe["src"].push([img, np.int64([label])])
    wpipe["src"].end_of_stream()
    wpipe.wait(timeout=60)
    wpipe.stop()
    return data_path, json_path


def mnist_epoch_benchmark(
    dtype: str = "bfloat16",
    n_train: int = 2048,
    n_valid: int = 256,
    epochs: int = 3,
    tmp_dir: str = "/tmp/nns_mnist_bench",
    timeout_s: float = 900.0,
) -> Tuple[float, float]:
    """BASELINE.md tracked row: tensor_trainer MNIST CNN epoch time.

    Runs the reference's canonical in-pipeline training config
    (datareposrc -> tensor_trainer, SURVEY §3.4) on a synthetic
    MNIST-shaped dataset and returns (steady-state seconds/epoch, final
    training accuracy).  Epoch 1 includes the XLA compile, so timing uses
    the epochs after it (stats-frame arrival deltas at the sink).
    """
    import json as _json
    import shutil
    import time

    from ..pipeline import parse_pipeline

    shutil.rmtree(tmp_dir, ignore_errors=True)
    data_path, json_path = write_synthetic_mnist(tmp_dir, n_train + n_valid)

    cfg = {
        "arch": "mnist_cnn",
        "arch_props": {"dtype": dtype, "classes": "10"},
        "optimizer": "adam",
        "learning_rate": 3e-3,
        "batch_size": 256,
    }
    cfg_path = os.path.join(tmp_dir, "cfg.json")
    with open(cfg_path, "w") as f:
        _json.dump(cfg, f)

    pipe = parse_pipeline(
        f"datareposrc location={data_path} json={json_path} epochs={epochs} ! "
        f"tensor_trainer name=t framework=jax model-config={cfg_path} "
        f"num-inputs=1 num-labels=1 num-training-samples={n_train} "
        f"num-validation-samples={n_valid} epochs={epochs} ! "
        "tensor_sink name=out"
    )
    arrivals = []
    pipe.start()
    pipe["out"].connect_new_data(lambda f: arrivals.append(time.perf_counter()))
    pipe.wait(timeout=timeout_s)
    stats = [f.tensors[0] for f in pipe["out"].frames]
    pipe.stop()

    if len(arrivals) < 2:
        raise RuntimeError(
            f"expected >=2 epoch stats frames, got {len(arrivals)}"
        )
    deltas = [b - a for a, b in zip(arrivals[1:], arrivals[2:])] or [
        arrivals[1] - arrivals[0]
    ]
    return float(np.mean(deltas)), float(stats[-1][2])


register_trainer(JaxTrainer)
