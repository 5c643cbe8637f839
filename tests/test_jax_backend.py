"""jax-xla backend tests (CPU-forced via conftest; the TPU path runs in
chip_smoke.py and the benchmark cells)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.backends import find_backend
from nnstreamer_tpu.backends.jax_xla import register_jax_model, unregister_jax_model
from nnstreamer_tpu.core.types import FORMAT_STATIC, StreamSpec, TensorSpec
from nnstreamer_tpu.pipeline import parse_pipeline


@pytest.fixture
def affine_model():
    # y = 2x + 1 — trivially verifiable through the jit path
    params = {"w": jnp.float32(2.0), "b": jnp.float32(1.0)}
    register_jax_model("affine", lambda p, xs: [xs[0] * p["w"] + p["b"]], params)
    yield
    unregister_jax_model("affine")


class TestJaxXlaBackend:
    def test_invoke(self, affine_model):
        be = find_backend("jax-xla")()
        be.open("affine", {})
        out = be.invoke([np.float32([1, 2, 3])])
        np.testing.assert_allclose(np.asarray(out[0]), [3, 5, 7])
        be.close()

    def test_invoke_batch_bucketing(self, affine_model):
        be = find_backend("jax-xla")()
        be.open("affine", {})
        # batch of 5 pads to bucket 8, slices back to 5
        out = be.invoke_batch([np.ones((5, 4), np.float32)])
        assert np.asarray(out[0]).shape == (5, 4)
        np.testing.assert_allclose(np.asarray(out[0]), 3.0)
        # same bucket reuses the compiled executable
        assert len(be._jit_cache) == 1
        out = be.invoke_batch([np.ones((7, 4), np.float32)])
        assert np.asarray(out[0]).shape == (7, 4)
        assert len(be._jit_cache) == 1  # still bucket 8
        be.close()

    def test_set_input_info_eval_shape(self, affine_model):
        be = find_backend("jax-xla")()
        be.open("affine", {})
        out_spec = be.set_input_info(
            StreamSpec((TensorSpec((4,), np.float32),), FORMAT_STATIC)
        )
        assert out_spec.tensors[0].shape == (4,)
        assert out_spec.tensors[0].dtype == np.dtype(np.float32)
        be.close()

    def test_outputs_stay_on_device(self, affine_model):
        be = find_backend("jax-xla")()
        be.open("affine", {})
        out = be.invoke([np.float32([1.0])])
        assert isinstance(out[0], jax.Array)  # no host round trip
        be.close()

    def test_unresolvable_model_n(self):
        be = find_backend("jax-xla")()
        with pytest.raises(FileNotFoundError):
            be.open("no_such_model", {})

    def test_py_file_model(self, tmp_path, affine_model):
        p = tmp_path / "model.py"
        p.write_text(
            "import jax.numpy as jnp\n"
            "def get_model():\n"
            "    return (lambda params, xs: [xs[0] ** 2], None)\n"
        )
        be = find_backend("jax-xla")()
        be.open(str(p), {})
        out = be.invoke([np.float32([3.0])])
        np.testing.assert_allclose(np.asarray(out[0]), [9.0])
        be.close()

    def test_donated_entry_skips_donation_on_cpu(self, affine_model):
        """invoke_batch_donated on CPU: XLA ignores donation (and warns
        per compile), so the donated entry point must not request it —
        donated_calls counts the routing, donated_applied stays 0, and
        results are identical to the plain path."""
        be = find_backend("jax-xla")()
        be.open("affine", {})
        x = np.ones((4, 3), np.float32)
        out = be.timed_invoke_batch_donated([x.copy()])
        np.testing.assert_allclose(np.asarray(out[0]), 3.0)
        assert be.stats.donated_calls == 1
        assert be.stats.donated_applied == 0  # CPU: donation skipped
        # same executable as the plain path (no donated compile forked)
        be.invoke_batch([x.copy()])
        assert len(be._jit_cache) == 1
        be.close()

    def test_donate_custom_prop_forces_donation(self, affine_model):
        """custom=donate:true pins donation even on CPU (the legacy
        opt-in: the caller takes responsibility for input privacy) —
        the compiled call carries donate_argnums and results stay
        correct (XLA on CPU ignores the alias request, warning only)."""
        be = find_backend("jax-xla")()
        be.open("affine", {"custom": "donate:true"})
        x = np.arange(12, dtype=np.float32).reshape(4, 3)
        out = be.timed_invoke_batch_donated([x.copy()])
        np.testing.assert_allclose(np.asarray(out[0]), x * 2.0 + 1.0)
        assert be.stats.donated_applied == 1
        # the donated variant compiled under its own cache key
        assert any(key[0] is True for key in be._jit_cache)
        be.close()

    def test_to_device_never_aliases_staging_buffer(self, affine_model):
        """The staging lane's buffer-reuse contract: to_device must have
        fully copied OFF the host array before returning.  XLA's CPU
        client zero-copies aligned numpy buffers in device_put, so a
        naive placement would hand back a jax.Array aliasing the pooled
        staging buffer — mutating the buffer afterwards (exactly what
        the lane does for the next batch) must not change the staged
        values."""
        be = find_backend("jax-xla")()
        be.open("affine", {})
        buf = np.ones((4, 3), np.float32)
        dev = be.to_device([buf])
        buf[:] = 777.0  # the lane reuses the staging buffer immediately
        np.testing.assert_allclose(np.asarray(dev[0]), 1.0)
        be.close()

    def test_hot_reload_swaps_params(self, affine_model):
        params2 = {"w": jnp.float32(10.0), "b": jnp.float32(0.0)}
        register_jax_model("affine2", lambda p, xs: [xs[0] * p["w"] + p["b"]], params2)
        try:
            be = find_backend("jax-xla")()
            be.open("affine", {})
            np.testing.assert_allclose(np.asarray(be.invoke([np.float32([1])])[0]), [3])
            be.reload("affine2")
            np.testing.assert_allclose(np.asarray(be.invoke([np.float32([1])])[0]), [10])
            be.close()
        finally:
            unregister_jax_model("affine2")


class TestJaxXlaInPipeline:
    def test_pipeline_with_batching(self, affine_model):
        pipe = parse_pipeline(
            "appsrc name=src ! tensor_filter name=f framework=jax-xla model=affine "
            "max-batch=8 ! tensor_sink name=out"
        )
        pipe.start()
        for i in range(12):
            pipe["src"].push(np.float32([i]))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=30)
        pipe.stop()
        vals = [float(f.tensors[0][0]) for f in pipe["out"].frames]
        assert vals == [2.0 * i + 1.0 for i in range(12)]


class TestMobileNetV2:
    @pytest.mark.slow  # tier-1 budget: ~20s mobilenet compile; the
    # kws/mnist family forwards keep the zoo-backend path covered
    def test_forward_shapes_cpu(self):
        # tiny input keeps CPU compile fast; the real 224 size runs on the chip
        from nnstreamer_tpu.models import build

        fn, params, in_spec, out_spec = build(
            "mobilenet_v2", {"size": "32", "classes": "10", "dtype": "float32"}
        )
        img = np.random.default_rng(0).integers(0, 255, (32, 32, 3), np.uint8)
        out = fn(params, [jnp.asarray(img)])
        assert np.asarray(out[0]).shape == (10,)
        batch = jnp.stack([jnp.asarray(img)] * 2)
        out_b = fn(params, [batch])
        assert np.asarray(out_b[0]).shape == (2, 10)
        # deterministic given fixed seed/params
        np.testing.assert_allclose(
            np.asarray(out_b[0][0]), np.asarray(out[0]), rtol=1e-5, atol=1e-5
        )

    def test_zoo_unknown_n(self):
        from nnstreamer_tpu.models import build

        with pytest.raises(KeyError):
            build("resnet9000")
