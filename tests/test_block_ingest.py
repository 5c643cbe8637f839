"""Block ingest: N logical frames travel as ONE pre-batched stream item.

≙ the reference converter's ``frames-per-tensor`` batching
(gsttensor_converter.c: frames-per-tensor property batches N media frames
into one tensor buffer).  TPU-first rationale: per-frame Python ingest and
per-frame stacking cap pipeline throughput far below the chip's rate; a
block pays those costs once per micro-batch.
"""

import numpy as np
import pytest

from nnstreamer_tpu.backends.jax_xla import (
    register_jax_model,
    unregister_jax_model,
)
from nnstreamer_tpu.core.buffer import BatchFrame
from nnstreamer_tpu.pipeline import parse_pipeline


@pytest.fixture(autouse=True)
def _affine_model():
    register_jax_model("blk_affine", lambda p, xs: [xs[0] * 3.0 - 1.0], None)
    yield
    unregister_jax_model("blk_affine")


def _run(push, n, extra="", timeout=30):
    pipe = parse_pipeline(
        "appsrc name=src ! tensor_filter name=f framework=jax-xla "
        f"model=blk_affine max-batch=8 {extra} ! tensor_sink name=out"
    )
    pipe.start()
    push(pipe["src"])
    pipe["src"].end_of_stream()
    pipe.wait(timeout=timeout)
    frames = pipe["out"].frames
    pipe.stop()
    assert len(frames) == n, f"expected {n} frames, got {len(frames)}"
    return frames


def _expect(frames, values, pts=None):
    got = [float(f.tensors[0][0]) for f in frames]
    assert got == pytest.approx([3.0 * v - 1.0 for v in values])
    if pts is not None:
        assert [f.pts for f in frames] == pytest.approx(pts)


class TestBlockIngest:
    def test_blocks_split_back_to_logical_frames(self):
        """3 blocks x 8 frames -> 24 per-frame outputs, in order, with
        per-logical pts carried through the batch."""
        def push(src):
            for b in range(3):
                block = np.arange(b * 8, b * 8 + 8, dtype=np.float32)
                src.push_block(
                    block[:, None], pts=[0.1 * i for i in range(b * 8, b * 8 + 8)]
                )
        frames = _run(push, 24)
        _expect(frames, list(range(24)), pts=[0.1 * i for i in range(24)])

    def test_block_equals_per_frame_results(self):
        vals = list(range(16))

        def push_frames(src):
            for i in vals:
                src.push(np.float32([i]), pts=i * 0.01)

        def push_blocks(src):
            src.push_block(
                np.float32(vals)[:, None], pts=[i * 0.01 for i in vals]
            )

        per_frame = _run(push_frames, 16)
        per_block = _run(push_blocks, 16)
        for a, b in zip(per_frame, per_block):
            np.testing.assert_allclose(a.tensors[0], b.tensors[0])
            assert a.pts == pytest.approx(b.pts)

    def test_mixed_blocks_and_plain_frames_keep_order(self):
        """A block arriving between plain frames must neither reorder nor
        drop anything (mixed concat path in _handle_prebatched)."""
        def push(src):
            src.push(np.float32([100.0]), pts=0.0)
            src.push_block(np.float32([[0.0], [1.0], [2.0]]),
                           pts=[0.1, 0.2, 0.3])
            src.push(np.float32([200.0]), pts=0.4)
            src.push_block(np.float32([[3.0], [4.0]]), pts=[0.5, 0.6])

        frames = _run(push, 7)
        _expect(frames, [100.0, 0.0, 1.0, 2.0, 200.0, 3.0, 4.0],
                pts=[0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6])

    def test_block_larger_than_max_batch(self):
        """A 20-frame block with max-batch=8: the scheduler never splits a
        queue item, but the filter chunks the invoke to honor max-batch
        (traced batch axes stay <= 8) — all frames come back once, in
        order."""
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model, unregister_jax_model)

        sizes = set()

        def fn(p, xs):
            sizes.add(int(xs[0].shape[0]))
            return [xs[0] * 3.0 - 1.0]

        register_jax_model("blk_chunk", fn, None)
        try:
            pipe = parse_pipeline(
                "appsrc name=src ! tensor_filter framework=jax-xla "
                "model=blk_chunk max-batch=8 ! tensor_sink name=out"
            )
            pipe.start()
            pipe["src"].push_block(
                np.arange(20, dtype=np.float32)[:, None],
                pts=[float(i) for i in range(20)],
            )
            pipe["src"].end_of_stream()
            pipe.wait(timeout=30)
            frames = pipe["out"].frames
            pipe.stop()
            assert len(frames) == 20
            _expect(frames, list(range(20)), pts=[float(i) for i in range(20)])
            assert all(s <= 8 for s in sizes), f"max-batch exceeded: {sizes}"
        finally:
            unregister_jax_model("blk_chunk")

    def test_empty_block_is_a_noop(self):
        def push(src):
            src.push_block(np.zeros((0, 1), np.float32))
            src.push_block(np.float32([[1.0], [2.0]]), pts=[0.0, 0.1])
        frames = _run(push, 2)
        _expect(frames, [1.0, 2.0], pts=[0.0, 0.1])

    def test_outputs_only_combination_with_blocks(self):
        """output-combination=o0 (no input refs) must still apply to block
        rows — and must not need the input block on host."""
        def push(src):
            src.push_block(
                np.arange(4, dtype=np.float32)[:, None],
                pts=[float(i) for i in range(4)],
            )
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=8 dispatch-depth=1 "
            "output-combination=o0 ! tensor_sink name=out"
        )
        pipe.start()
        push(pipe["src"])
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert len(frames) == 4
        _expect(frames, list(range(4)))

    def test_depth_window_drains_blocks_on_eos(self):
        """Parked pre-batched windows (dispatch-depth > 1) must fully drain
        at EOS in order."""
        def push(src):
            for b in range(6):
                src.push_block(
                    np.arange(b * 4, b * 4 + 4, dtype=np.float32)[:, None]
                )
        frames = _run(push, 24, extra="dispatch-depth=4")
        _expect(frames, list(range(24)))

    def test_depth_1_synchronous_blocks(self):
        def push(src):
            for b in range(4):
                src.push_block(
                    np.arange(b * 4, b * 4 + 4, dtype=np.float32)[:, None]
                )
        frames = _run(push, 16, extra="dispatch-depth=1")
        _expect(frames, list(range(16)))

    def test_push_block_framerate_stamps_logical_pts(self):
        """Without explicit pts, push_block stamps per-logical-frame pts
        from the framerate prop, continuing across blocks."""
        pipe = parse_pipeline(
            "appsrc name=src framerate=10/1 ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=8 ! tensor_sink name=out"
        )
        pipe.start()
        pipe["src"].push_block(np.zeros((4, 1), np.float32))
        pipe["src"].push_block(np.zeros((4, 1), np.float32))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert [f.pts for f in frames] == pytest.approx(
            [i * 0.1 for i in range(8)]
        )

    def test_output_combination_with_blocks(self):
        """output-combination needs per-logical input rows: the emit path
        slices the block's inputs (materialized once per block)."""
        def push(src):
            src.push_block(
                np.arange(6, dtype=np.float32)[:, None],
                pts=[float(i) for i in range(6)],
            )
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=8 dispatch-depth=1 "
            "output-combination=i0,o0 ! tensor_sink name=out"
        )
        pipe.start()
        push(pipe["src"])
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert len(frames) == 6
        for i, f in enumerate(frames):
            assert len(f.tensors) == 2
            np.testing.assert_allclose(f.tensors[0], np.float32([i]))
            np.testing.assert_allclose(f.tensors[1], np.float32([3.0 * i - 1.0]))

    def test_input_combination_falls_back(self):
        """input-combination is incompatible with skipping per-frame views:
        blocks take the per-item transform path and results stay correct."""
        def push(src):
            src.push_block(
                np.arange(5, dtype=np.float32)[:, None],
                pts=[float(i) for i in range(5)],
            )
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=8 input-combination=0 ! "
            "tensor_sink name=out"
        )
        pipe.start()
        push(pipe["src"])
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        # the solo-BatchFrame transform path emits the block whole; the
        # sink fans it back out to logical frames
        assert len(frames) == 5
        _expect(frames, list(range(5)))

    def test_fused_decoder_consumes_blocks(self):
        """Device-fused decode (filter + image_labeling compiled into one
        XLA program) must accept pre-batched input and still deliver
        per-logical-frame labels."""
        import tempfile

        register_jax_model("blk_logits", lambda p, xs: [xs[0]], None)
        try:
            with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                             delete=False) as f:
                f.write("\n".join(f"label{i}" for i in range(5)))
                labels = f.name
            pipe = parse_pipeline(
                "appsrc name=src ! tensor_filter name=f framework=jax-xla "
                "model=blk_logits max-batch=8 ! tensor_decoder "
                f"mode=image_labeling option1={labels} ! tensor_sink name=out"
            )
            pipe.start()
            rows = np.float32(
                [np.eye(5, dtype=np.float32)[i % 5] for i in range(12)]
            )
            pipe["src"].push_block(rows)
            pipe["src"].end_of_stream()
            pipe.wait(timeout=30)
            frames = pipe["out"].frames
            pipe.stop()
            assert len(frames) == 12
            assert [f.meta.get("label") for f in frames] == [
                f"label{i % 5}" for i in range(12)
            ]
            assert [int(f.tensors[0][0]) for f in frames] == [
                i % 5 for i in range(12)
            ]
        finally:
            unregister_jax_model("blk_logits")


class TestBlockIngestGuards:
    def test_push_block_rejects_mismatched_pts(self):
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_sink name=out"
        )
        pipe.start()
        with pytest.raises(ValueError, match="pts"):
            pipe["src"].push_block(
                np.zeros((4, 1), np.float32), pts=[0.0, 0.1]
            )
        pipe["src"].end_of_stream()
        pipe.wait(timeout=10)
        pipe.stop()

    def test_push_block_rejects_mismatched_frame_axes(self):
        pipe = parse_pipeline("appsrc name=src ! tensor_sink name=out")
        pipe.start()
        with pytest.raises(ValueError, match="frame axis"):
            pipe["src"].push_block(
                [np.zeros((4, 1), np.float32), np.zeros((3, 1), np.float32)]
            )
        pipe["src"].end_of_stream()
        pipe.wait(timeout=10)
        pipe.stop()

    def test_scheduler_bounds_logical_batch(self):
        """Flooding the queue with blocks must not produce invokes beyond
        max-batch (+ at most one block's worth): traced batch-axis sizes
        stay in {8, 16}, never a whole-queue mega-batch."""
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model, unregister_jax_model)

        sizes = set()

        def fn(p, xs):
            sizes.add(int(xs[0].shape[0]))  # trace-time: one per compile
            return [xs[0] * 2.0]

        register_jax_model("blk_sizes", fn, None)
        try:
            pipe = parse_pipeline(
                "appsrc name=src max-buffers=64 ! tensor_filter "
                "framework=jax-xla model=blk_sizes max-batch=16 ! "
                "tensor_sink name=out"
            )
            pipe.start()
            for b in range(40):
                pipe["src"].push_block(
                    np.full((8, 1), float(b), np.float32)
                )
            pipe["src"].end_of_stream()
            pipe.wait(timeout=60)
            frames = pipe["out"].frames
            pipe.stop()
            assert len(frames) == 320
            assert sizes <= {8, 16}, f"unbounded micro-batch: {sizes}"
        finally:
            unregister_jax_model("blk_sizes")

    def test_block_through_max_batch_1_path(self):
        """max-batch=1 routes blocks through transform(): the batch axis
        must still mean batch (invoke_batch), not one frame's shape."""
        def push(src):
            src.push_block(
                np.arange(6, dtype=np.float32)[:, None],
                pts=[float(i) for i in range(6)],
            )
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=1 ! tensor_sink name=out"
        )
        pipe.start()
        push(pipe["src"])
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert len(frames) == 6
        _expect(frames, list(range(6)), pts=[float(i) for i in range(6)])


class TestConverterEmitBlocks:
    """tensor_converter emit-blocks=true: frames-per-tensor batching that
    emits a transparent BatchFrame (per-frame schema/pts preserved) instead
    of the reference's shape-changed stacked tensor — block ingest from
    pipeline text alone, no appsrc API needed."""

    def test_media_pipeline_blocks_end_to_end(self):
        pipe = parse_pipeline(
            "videotestsrc num-buffers=12 pattern=solid width=8 height=8 "
            "framerate=10/1 ! tensor_converter frames-per-tensor=4 "
            "emit-blocks=true ! tensor_filter framework=jax-xla "
            "model=blk_img max-batch=4 ! tensor_sink name=out"
        )
        from nnstreamer_tpu.backends.jax_xla import (
            register_jax_model, unregister_jax_model)
        # batch-polymorphic like the zoo models: (H,W,C) -> (1,) per frame,
        # (B,H,W,C) -> (B,1) per block (schema negotiates UNBATCHED)
        register_jax_model(
            "blk_img", lambda p, xs: [xs[0].astype("float32").mean(
                axis=(-3, -2, -1))[..., None]], None)
        try:
            pipe.start()
            pipe.wait(timeout=30)
            frames = pipe["out"].frames
            pipe.stop()
            # all 12 logical frames come back, at the SOURCE framerate
            assert len(frames) == 12
            assert [f.pts for f in frames] == pytest.approx(
                [i * 0.1 for i in range(12)]
            )
            # solid pattern: frame i has value (i*8)%256 everywhere
            got = [float(f.tensors[0][0]) for f in frames]
            assert got == pytest.approx([(i * 8) % 256 for i in range(12)])
        finally:
            unregister_jax_model("blk_img")

    def test_partial_tail_block_is_emitted_not_dropped(self):
        """10 frames at frames-per-tensor=4 -> blocks of 4,4,2: the tail
        block flushes at EOS (no schema change, so no reason to drop —
        documented divergence from the reference's stacking mode)."""
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_converter frames-per-tensor=4 "
            "emit-blocks=true ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=4 ! tensor_sink name=out"
        )
        pipe.start()
        for i in range(10):
            pipe["src"].push(np.float32([i]), pts=i * 0.1)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert len(frames) == 10
        _expect(frames, list(range(10)),
                pts=[i * 0.1 for i in range(10)])

    def test_stacking_mode_unchanged_without_emit_blocks(self):
        """Reference semantics intact: fpt=4 without emit-blocks emits
        shape-changed frames and drops the partial tail."""
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_converter frames-per-tensor=4 ! "
            "tensor_sink name=out"
        )
        pipe.start()
        for i in range(10):
            pipe["src"].push(np.float32([i]))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert len(frames) == 2  # 4+4, tail of 2 dropped
        assert frames[0].tensors[0].shape == (4, 1)


class TestWholeBlockDelivery:
    """decoder/sink split-batches=false: blocks stay whole through the
    fused decode (vectorized decode_fused_batch) and arrive at callbacks
    as BatchFrames — the per-frame fan-out disappears from the hot path."""

    def _pipe(self, labels, sink_split):
        from nnstreamer_tpu.backends.jax_xla import register_jax_model
        register_jax_model("blk_pass", lambda p, xs: [xs[0]], None)
        extra = "" if sink_split else " split-batches=false"
        return parse_pipeline(
            "appsrc name=src ! tensor_filter framework=jax-xla "
            "model=blk_pass max-batch=8 ! "
            f"tensor_decoder mode=image_labeling option1={labels}{extra} ! "
            f"tensor_sink name=out{extra}"
        )

    def test_blocks_survive_to_callbacks_with_labels(self):
        import tempfile

        from nnstreamer_tpu.backends.jax_xla import unregister_jax_model
        with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                         delete=False) as f:
            f.write("\n".join(f"L{i}" for i in range(5)))
            labels = f.name
        try:
            pipe = self._pipe(labels, sink_split=False)
            got = []
            pipe["out"].connect_new_data(got.append)
            pipe.start()
            rows = np.float32(
                [np.eye(5, dtype=np.float32)[i % 5] for i in range(16)]
            )
            pipe["src"].push_block(rows[:8], pts=[float(i) for i in range(8)])
            pipe["src"].push_block(rows[8:], pts=[float(i) for i in range(8, 16)])
            pipe["src"].end_of_stream()
            pipe.wait(timeout=30)
            pipe.stop()
            # callbacks received whole blocks...
            assert all(isinstance(f, BatchFrame) for f in got)
            assert sum(f.batch_size for f in got) == 16
            # ...with per-logical labels/pts in frames_info
            flat = [
                (p, m.get("label"))
                for f in got for (p, d, m) in f.frames_info
            ]
            assert flat == [(float(i), f"L{i % 5}") for i in range(16)]
        finally:
            unregister_jax_model("blk_pass")

    def test_split_results_identical_to_block_delivery(self):
        import tempfile

        from nnstreamer_tpu.backends.jax_xla import unregister_jax_model
        with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                         delete=False) as f:
            f.write("\n".join(f"L{i}" for i in range(5)))
            labels = f.name
        rows = np.float32(
            [np.eye(5, dtype=np.float32)[(3 * i) % 5] for i in range(12)]
        )
        try:
            results = {}
            for split in (True, False):
                pipe = self._pipe(labels, sink_split=split)
                pipe.start()
                pipe["src"].push_block(
                    rows, pts=[float(i) for i in range(12)]
                )
                pipe["src"].end_of_stream()
                pipe.wait(timeout=30)
                frames = pipe["out"].frames
                pipe.stop()
                if split:
                    results[split] = [
                        (f.pts, f.meta.get("label"), int(f.tensors[0][0]))
                        for f in frames
                    ]
                else:
                    results[split] = [
                        (p, m.get("label"), int(f.tensors[0][j, 0]))
                        for f in frames
                        for j, (p, d, m) in enumerate(f.frames_info)
                    ]
            assert results[True] == results[False]
        finally:
            unregister_jax_model("blk_pass")


class TestBatchAwareSafetyNet:
    """Non-batch-aware elements must see LOGICAL frames: the scheduler
    splits blocks before per-frame elements (transform/if/...), so a block
    upstream can never smuggle a surprise batch axis into per-frame
    semantics (Element.BATCH_AWARE opt-in)."""

    def test_transform_sees_logical_frames(self):
        """mode=transpose on (2,3) frames would corrupt on a (B,2,3) batch
        axis; with the safety net, blocks and per-frame pushes agree."""
        def run(push):
            pipe = parse_pipeline(
                "appsrc name=src ! tensor_transform mode=transpose "
                "option=1:0 ! tensor_sink name=out"
            )
            pipe.start()
            push(pipe["src"])
            pipe["src"].end_of_stream()
            pipe.wait(timeout=30)
            frames = pipe["out"].frames
            pipe.stop()
            return [np.asarray(f.tensors[0]) for f in frames]

        data = np.arange(24, dtype=np.float32).reshape(4, 2, 3)
        per_frame = run(lambda s: [s.push(d) for d in data])
        per_block = run(lambda s: s.push_block(data))
        assert len(per_block) == 4
        for a, b in zip(per_frame, per_block):
            assert a.shape == (3, 2)
            np.testing.assert_array_equal(a, b)

    def test_tensor_if_routes_per_logical_frame(self):
        """Data-dependent routing must evaluate each logical frame, not
        the whole block once."""
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_if name=cond compared-value=A_VALUE "
            "compared-value-option=0:0 supplied-value=10 operator=GE "
            "then=PASSTHROUGH else=SKIP ! tensor_sink name=out"
        )
        pipe.start()
        vals = np.float32([[3.0], [15.0], [7.0], [22.0]])
        pipe["src"].push_block(vals)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        got = [float(f.tensors[0][0]) for f in frames]
        assert got == [15.0, 22.0]


class TestBatchFrameUnit:
    def test_batchframe_through_push_roundtrip(self):
        """AppSrc.push accepts a hand-built BatchFrame (it IS a
        TensorFrame) — push_block is sugar, not a requirement."""
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter framework=jax-xla "
            "model=blk_affine max-batch=4 ! tensor_sink name=out"
        )
        pipe.start()
        bf = BatchFrame(
            tensors=[np.float32([[1.0], [2.0]])],
            pts=0.0,
            frames_info=[(0.0, None, {}), (0.1, None, {})],
        )
        pipe["src"].push(bf)
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        frames = pipe["out"].frames
        pipe.stop()
        assert len(frames) == 2
        _expect(frames, [1.0, 2.0], pts=[0.0, 0.1])
