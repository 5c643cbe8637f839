"""Traffic kind ``flood_stream``: one pusher offers host uint8 frames to an
``appsrc ! ... ! tensor_sink`` graph as fast as back-pressure admits.

The mix's parameters (``traffic/<mix>.json``): ``pool`` frames made from the
seed and cycled in order, ``warm_frames`` delivered before the window opens.
The graph, its props and the buckets to warm are the cell's
(``workloads/<cell>.json``); the widths are the configuration's.

End-to-end metric: ``stream_fps``, every frame the sink delivered inside the
window over the window's seconds.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time

import numpy as np

from .. import harness


def make_frames(seed, pool, size):
    """``pool`` host frames: noise under a bright band whose place, colour
    and level differ from frame to frame, so frames excite different
    features.  The seed chooses the contents, never the amount of work."""
    rng = np.random.default_rng(seed)
    frames = rng.integers(0, 128, (pool, size, size, 3), dtype=np.uint8)
    band = max(1, size // 4)
    for i in range(pool):
        lo = int(rng.integers(0, size - band + 1))
        frames[i, lo:lo + band, :, i % 3] += np.uint8(rng.integers(32, 128))
    return frames


class Session:
    def __init__(self, cell, seed, phases):
        self.cell, self.seed, self.phases = cell, seed, phases
        self.cfg = cell.config
        self.mix = cell.traffic
        self.records = []          # (push index, label, score, arrival time)
        self.pushed = 0
        self._stop = threading.Event()
        self._pusher = None
        self.pipe = None
        self.tmp = tempfile.mkdtemp(prefix="bench_stream_")

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        from nnstreamer_tpu.native import runtime as native
        from nnstreamer_tpu.pipeline import parse_pipeline

        native.available()  # builds the mailbox before any pipeline starts
        self.phases.mark("native mailbox", mailbox=native.mailbox_impl())
        labels = os.path.join(self.tmp, "labels.txt")
        with open(labels, "w") as f:
            f.write("\n".join(f"class{i}" for i in range(self.cfg["classes"])))
        w = self.cell.workload
        custom = w["custom"].format(seed=harness.model_seed(self.seed), **self.cfg)
        self.frames = make_frames(self.seed, self.mix["pool"], self.cfg["size"])
        self.phases.mark("frames from seed", frames=len(self.frames))
        self.pipe = parse_pipeline(
            w["pipeline"].format_map({**w, "custom": custom, "labels": labels}), name="bench-stream")
        self.pipe["out"].connect_new_data(self._on_frame)
        self.pipe.start()
        self.filter = self.pipe[w["filter"]]
        self.phases.mark("parameters and pipeline start")
        # every bucket the window can form: which ones do depends on timing
        backend = self.filter.backend
        for n in w["warm_buckets"]:
            out = backend.invoke_batch_donated([np.ascontiguousarray(self.frames[:n])])
            np.asarray(out[0])
            self.phases.mark(f"program bucket {n}")
        self._pusher = threading.Thread(target=self._push, name="bench-pusher", daemon=True)
        self._pusher.start()
        deadline = time.perf_counter() + 600
        while len(self.records) < self.mix["warm_frames"]:
            if time.perf_counter() > deadline or self.pipe.errors:
                raise RuntimeError(f"warm traffic stalled: {self.pipe.errors}")
            time.sleep(0.01)
        self.phases.mark("warm traffic", frames=len(self.records))

    def _push(self):
        src, frames, n = self.pipe["src"], self.frames, len(self.frames)
        while not self._stop.is_set():
            src.push(frames[self.pushed % n], pts=float(self.pushed))
            self.pushed += 1

    def _on_frame(self, fr):
        self.records.append((int(fr.pts), int(np.asarray(fr.tensors[0]).reshape(-1)[0]),
                             float(fr.meta["label_score"]), time.perf_counter()))

    def counters(self):
        info = dict(self.filter.metrics_info())
        return {"invokes": info["nns.filter.invokes"],
                "invoked_frames": info["nns.filter.invoked_frames"],
                "delivered": len(self.records), "pushed": self.pushed}

    # -- the window -------------------------------------------------------------
    def window(self, seconds, tracer=None):
        self.t0 = time.perf_counter()
        self.c0 = self.counters()
        if tracer:
            tracer.start()
        time.sleep(max(0.0, self.t0 + seconds - time.perf_counter()))
        self.c1 = self.counters()
        self.t1 = time.perf_counter()
        if tracer:
            tracer.join()
        self._stop.set()
        self._pusher.join(30)
        self.pipe["src"].end_of_stream()
        self.pipe.wait(timeout=120)
        self.errors = list(self.pipe.errors)
        self.in_window = [r for r in self.records if self.t0 < r[3] <= self.t1]
        return self.t0

    def end_to_end(self):
        return {"stream_fps": len(self.in_window) / (self.t1 - self.t0)}

    def attempted_failed(self):
        failed = self.pushed - len(self.records)
        return len(self.in_window) + failed, failed

    def facts(self):
        return {"window_s": self.t1 - self.t0, "pushed": self.pushed,
                "delivered": len(self.records), "in_window": len(self.in_window),
                "max_batch": self.cell.workload["max_batch"]}

    def work_units(self, ta, tb, c0, c1):
        """Frames and batches the filter invoked between two counter readings."""
        return {"frames": c1["invoked_frames"] - c0["invoked_frames"],
                "invokes": c1["invokes"] - c0["invokes"]}

    def close(self):
        if self.pipe is not None:
            self.pipe.stop()
        self.pipe = self.filter = None
        shutil.rmtree(self.tmp, ignore_errors=True)

    # -- correct ------------------------------------------------------------------
    def compare(self, control=None):
        """A sample of the frames the sink delivered inside the window, drawn
        from the seed, against the float32 reference of the same pool frames:
        how far the served score is from the reference's logit of the served
        label, as a share of the frame's logit spread (worst and mean)."""
        limits = self.cell.workload["compare"]
        ref = harness.load_module("configs", self.cfg["reference"])
        rng = np.random.default_rng(self.seed + 1)
        n = min(limits["sample"], len(self.in_window))
        if n == 0 or self.errors:
            return {"delivered_in_window": {"value": None, "limit": 0}}, None
        picks = [self.in_window[i] for i in
                 sorted(rng.choice(len(self.in_window), n, replace=False))]
        pool_idx = sorted({p[0] % len(self.frames) for p in picks})
        t_ref = time.perf_counter()
        params = ref.make_params(self.cfg, harness.model_seed(self.seed))
        images = self.frames[pool_idx]
        logits = np.asarray(ref.forward(params, images, self.cfg))
        row = {p: i for i, p in enumerate(pool_idx)}

        def of(table, push_idx):
            return table[row[push_idx % len(self.frames)]]

        def readings(served):
            gap, err = [], []
            for push_idx, label, score in served:
                lg = of(logits, push_idx)
                spread = float(lg.max() - lg.min())
                if not 0 <= label < lg.shape[0]:
                    gap.append(1e30)
                    err.append(1e30)
                    continue
                gap.append(float(lg.max() - lg[label]) / spread)
                err.append(abs(score - float(lg[label])) / spread)
            # a wrong label shows in the score's error too (the served score is
            # then far from the reference's logit of that label); the label
            # gap alone is kept as a diagnostic, since no lower precision
            # flips a label here and so no limit on it has an upper reading
            return {"score_err_max": max(err, default=1e30),
                    "score_err_mean": float(np.mean(err)) if err else 1e30}, max(gap)

        got, label_gap = readings([p[:3] for p in picks])
        compared = {k: {"value": got[k], "limit": limits[k]} for k in got}
        self.check_detail = {"frames": len(pool_idx), "label_gap_max": label_gap,
                             "reference_s": time.perf_counter() - t_ref}
        ctl = None
        if control:
            low = np.asarray(ref.forward(params, images, self.cfg, precision=control))
            served = [(p[0], int(of(low, p[0]).argmax()), float(of(low, p[0]).max()))
                      for p in picks]
            ctl, _ = readings(served)
        return compared, ctl
