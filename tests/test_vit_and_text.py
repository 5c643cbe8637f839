"""ViT family + textual LLM pipeline (tokenizer/detokenizer pair).

ViT: the transformer-era vision classifier (net-new vs the reference's
convnet zoo), reusing the attention machinery incl. the Pallas flash
kernel.  Text: byte-level tokenizer converter + detokenizer decoder make
the transformer generate-serving pipeline text-in/text-out.
"""

import jax
import numpy as np
import pytest

from nnstreamer_tpu.models import build
from nnstreamer_tpu.pipeline import parse_pipeline


class TestViT:
    def test_forward_shapes(self, rng):
        fn, params, in_spec, out_spec = build(
            "vit",
            {"dtype": "float32", "size": "64", "patch": "16",
             "d_model": "32", "heads": "2", "layers": "2", "d_ff": "64",
             "classes": "11"},
        )
        imgs = rng.integers(0, 255, (2, 64, 64, 3), np.uint8)
        out = jax.jit(lambda p, x: fn(p, [x])[0])(params, imgs)
        assert out.shape == (2, 11)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_default_path_runs_off_tpu(self, rng):
        """Off-TPU the ViT's one attention call lowers the fused-XLA
        reference, without error at ViT's non-block-divisible token count
        ((64/16)^2+1=17), and an ``attn`` property is no longer read.
        The kernel itself (incl. the overhanging block and its masking)
        is pinned by test_vit_token_count_flash_kernel below and
        tests/test_flash_attention.py — NOT by this path."""
        props = {"dtype": "float32", "size": "64", "patch": "16",
                 "d_model": "32", "heads": "2", "layers": "1", "d_ff": "64",
                 "classes": "7", "seed": "3"}
        f_f, p_f, _, _ = build("vit", props)
        imgs = rng.integers(0, 255, (1, 64, 64, 3), np.uint8)
        y = np.asarray(f_f(p_f, [imgs])[0])
        assert y.shape == (1, 7) and np.all(np.isfinite(y))
        f_k, p_k, _, _ = build("vit", {**props, "attn": "flash"})
        np.testing.assert_array_equal(np.asarray(f_k(p_k, [imgs])[0]), y)
        text = jax.jit(lambda p, x: f_f(p, [x])[0]).lower(p_f, imgs).as_text()
        assert "tpu_custom_call" not in text

    @pytest.mark.parametrize("mesh", ["", "dp:2"])
    def test_backend_tells_the_vit_whether_it_compiles_for_one_device(
            self, rng, monkeypatch, mesh):
        """A Mosaic call cannot sit in a mesh-partitioned program: under
        ``mesh=`` the backend binds ``single_device=False`` and the ViT's
        attention keeps to XLA; without a mesh it is True."""
        import importlib

        # (the package re-exports a function of the module's name)
        fa = importlib.import_module("nnstreamer_tpu.ops.flash_attention")
        seen = []
        real = fa.flash_attention_qkv

        def spy(qkv, n_heads, causal, single_device):
            seen.append(single_device)
            return real(qkv, n_heads, causal, single_device)

        monkeypatch.setattr(fa, "flash_attention_qkv", spy)
        custom = ("arch:vit,size:32,patch:16,d_model:32,heads:2,layers:1,"
                  "d_ff:64,classes:5,dtype:float32")
        pipe = parse_pipeline(
            f"appsrc name=src ! tensor_filter framework=jax-xla model=zoo "
            f"custom={custom} max-batch=2 {('mesh=' + mesh) if mesh else ''} "
            "! tensor_sink name=out")
        pipe.start()
        try:
            for _ in range(2):
                pipe["src"].push(rng.integers(0, 255, (32, 32, 3), np.uint8))
            pipe["src"].end_of_stream()
            pipe.wait(timeout=120)
        finally:
            pipe.stop()
        assert len(pipe["out"].frames) == 2
        # the first call is the zoo's init program, which never holds the
        # kernel (it needs shapes only); the rest are the backend's
        assert not seen[0] and seen[1:] and set(seen[1:]) == {not mesh}

    def test_vit_token_count_flash_kernel(self, rng):
        """The REAL kernel (interpret mode) at ViT-224's token count
        T=197: non-divisible by the 128 block, non-causal — exercises the
        wrapper's padding + the kernel's valid_len masking."""
        from nnstreamer_tpu.ops.flash_attention import flash_attention
        from nnstreamer_tpu.parallel.ring_attention import (
            reference_attention,
        )

        q, k, v = (
            jax.numpy.asarray(
                rng.normal(size=(1, 197, 2, 16)).astype(np.float32)
            )
            for _ in range(3)
        )
        got = flash_attention(q, k, v, causal=False, interpret=True)
        want = reference_attention(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.slow  # tier-1 budget: ~19s second full ViT compile (int8
    # twin); float forward + flash parity above keep ViT covered tier-1
    def test_quantized_tracks_float(self, rng):
        """quantize:int8 (QuantDense encoder): same weights as the float
        build (param-path trick), logits stay correlated."""
        common = {
            "dtype": "float32", "size": "64", "patch": "16",
            "d_model": "32", "heads": "2", "layers": "2", "d_ff": "64",
            "classes": "31", "seed": "9",
        }
        f_q, p_q, _, _ = build("vit", {**common, "quantize": "int8"})
        f_f, p_f, _, _ = build("vit", common)
        for a, b in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_f)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        imgs = rng.integers(0, 255, (4, 64, 64, 3), np.uint8)
        y_q = np.asarray(f_q(p_q, [imgs])[0])
        y_f = np.asarray(f_f(p_f, [imgs])[0])
        corr = np.corrcoef(y_q.ravel(), y_f.ravel())[0, 1]
        assert corr > 0.8, corr

    def test_rejects_bad_patch(self):
        try:
            build("vit", {"size": "65", "patch": "16"})
        except ValueError as e:
            assert "divisible" in str(e)
        else:
            raise AssertionError("expected ValueError")


class TestTextPipeline:
    def test_tokenize_generate_detokenize(self):
        from nnstreamer_tpu.backends.jax_xla import register_jax_model

        fn, params, ins, outs = build(
            "transformer",
            {"dtype": "float32", "vocab": "256", "d_model": "32",
             "heads": "2", "layers": "2", "d_ff": "64", "seq": "64",
             "generate": "8", "seed": "5"},
        )
        register_jax_model("text_lm", fn, params, ins, outs)

        pipe = parse_pipeline(
            "appsrc name=src ! tensor_converter mode=custom:tokenizer ! "
            "tensor_filter framework=jax-xla model=text_lm ! "
            "tensor_decoder mode=detokenizer ! tensor_sink name=out",
            name="text-llm",
        )
        pipe.start()
        prompt = "hello tpu"
        pipe["src"].push(np.frombuffer(prompt.encode(), np.uint8))
        pipe["src"].end_of_stream()
        pipe.wait(timeout=120)
        f = pipe["out"].frames[0]
        pipe.stop()
        text = f.meta["text"]
        # completion = prompt + 8 generated byte-level tokens
        assert text.startswith(prompt)
        assert len(f.tensors[0]) == len(prompt) + 8

    def test_tokenizer_roundtrip(self):
        import nnstreamer_tpu.converters  # noqa: F401
        import nnstreamer_tpu.decoders  # noqa: F401
        from nnstreamer_tpu.core.buffer import TensorFrame
        from nnstreamer_tpu.core.registry import (
            KIND_CONVERTER,
            KIND_DECODER,
            get,
        )

        tok = get(KIND_CONVERTER, "tokenizer")()
        detok = get(KIND_DECODER, "detokenizer")()
        msg = "round-trip é"
        toks = tok.convert(
            TensorFrame([np.frombuffer(msg.encode(), np.uint8)])
        )
        assert toks.tensors[0].dtype == np.int32
        back = detok.decode(toks, None)
        assert back.meta["text"] == msg
