"""image_labeling decoder: classification scores -> text label.

Reference: ``ext/nnstreamer/tensor_decoder/tensordec-imagelabel.c`` — argmax
over the score tensor, map through a label file (option1), output
text/x-raw.  Label-file loading analog: ``tensordecutil.c``.

Output frame: tensor = [argmax index] (int32); ``meta["label"]`` carries the
text (the text/x-raw analog).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..core.buffer import TensorFrame
from ..core.telemetry import TRACE_ID_META
from ..core.tracer import span
from ..core.types import FORMAT_STATIC, StreamSpec, TensorSpec
from .util import load_labels


class ImageLabeling:
    NAME = "image_labeling"

    def __init__(self):
        self.labels: Optional[List[str]] = None

    def set_options(self, options):
        if options and options[0]:
            self.labels = load_labels(options[0])

    def get_out_spec(self, in_spec: StreamSpec) -> StreamSpec:
        return StreamSpec(
            (TensorSpec((1,), np.int32, "label_index"),),
            FORMAT_STATIC,
            in_spec.framerate if in_spec else None,
        )

    def decode(self, frame: TensorFrame, in_spec) -> TensorFrame:
        scores = np.asarray(frame.tensors[0]).reshape(-1)
        idx = int(np.argmax(scores))
        return self._emit(frame, idx, float(scores[idx]))

    def _emit(self, frame: TensorFrame, idx: int, score: float) -> TensorFrame:
        # the decoder's host part: index to label
        with span("nns.decoder.labels") as sp:
            if sp.live:
                sp.set(request=frame.meta.get(TRACE_ID_META))
            out = frame.with_tensors([np.asarray([idx], np.int32)])
            out.meta["label_index"] = idx
            out.meta["label_score"] = score
            if self.labels and idx < len(self.labels):
                out.meta["label"] = self.labels[idx]
            return out

    # -- device-fused half (pipeline fusion pass) ---------------------------
    def device_fn(self, outs, single_device=True):
        """jit-traceable half, folded into the upstream filter's XLA
        program: fused argmax+max (Pallas row-reduction when lowered for
        a TPU, ``ops/labeling.py``) so only (index, score) — 8
        bytes/frame — ever crosses PCIe instead of the full score tensor.
        ``single_device`` comes from the backend that compiles this: a
        program partitioned over a mesh cannot hold the Mosaic kernel.

        The pair is packed into ONE float32 (B, 2) tensor so the host
        boundary pays a single transfer per micro-batch instead of two —
        on a latency-bound link each extra output tensor is an extra
        round trip.  float32 holds the index exactly (class counts are
        << 2^24)."""
        import jax.numpy as jnp

        from ..ops.labeling import top1

        idx, score = top1(outs[0], use_pallas=single_device)
        return [
            jnp.stack(
                [idx.astype(jnp.float32), score.astype(jnp.float32)],
                axis=-1,
            )
        ]  # (B, 2)

    def decode_fused(self, frame: TensorFrame, in_spec) -> TensorFrame:
        """Host finishing after device_fn: tensor is [[idx, score]]."""
        packed = np.asarray(frame.tensors[0], np.float64).reshape(-1)
        return self._emit(frame, int(packed[0]), float(packed[1]))

    def decode_fused_batch(self, frame, in_spec):
        """Vectorized host finish for a whole block: one (B, 2) packed
        tensor in, one BatchFrame of (1,) label indices out, per-logical
        labels stamped into frames_info meta (decoder split-batches=false;
        at chip rates the per-frame fan-out dominates the decode)."""
        from ..core.buffer import BatchFrame

        with span("nns.decoder.labels", frames=len(frame.frames_info)):
            packed = np.asarray(frame.tensors[0], np.float64).reshape(-1, 2)
            idx = packed[:, 0].astype(np.int32)
            labels = self.labels
            infos = []
            for j, (p, d, m) in enumerate(frame.frames_info):
                m2 = dict(m)
                i = int(idx[j])
                m2["label_index"] = i
                m2["label_score"] = float(packed[j, 1])
                if labels and i < len(labels):
                    m2["label"] = labels[i]
                infos.append((p, d, m2))
            return BatchFrame(
                tensors=[idx[:, None]],
                pts=frame.pts, duration=frame.duration, meta=dict(frame.meta),
                frames_info=infos,
            )
