"""L6 tools: pbtxt converter, confchk, codegen, launch CLI."""

import os
import subprocess
import sys

import numpy as np
import pytest

from nnstreamer_tpu.cli import codegen, confchk, pbtxt
from nnstreamer_tpu.pipeline import parse_pipeline


class TestPbtxt:
    def test_linear_roundtrip(self):
        text = (
            "appsrc name=src ! tensor_transform mode=arithmetic "
            "option=add:1 ! tensor_sink name=out"
        )
        pb = pbtxt.pipeline_text_to_pbtxt(text)
        assert 'type: "tensor_transform"' in pb
        assert 'key: "option"' in pb and 'value: "add:1"' in pb
        assert 'link { src: "src" src_pad: 0 sink:' in pb
        text2 = pbtxt.pbtxt_to_pipeline_text(pb)
        # the regenerated text must itself produce an equivalent pbtxt
        assert pbtxt.pipeline_text_to_pbtxt(text2) == pb

    def test_branching_roundtrip(self):
        text = (
            "appsrc name=a ! mux.  appsrc name=b ! mux.  "
            "tensor_mux name=mux sync-mode=nosync ! tensor_sink name=out"
        )
        pb = pbtxt.pipeline_text_to_pbtxt(text)
        assert pb.count("node {") == 4
        assert pb.count("link {") == 3
        pipe = pbtxt.pbtxt_to_pipeline(pb)
        # run it: 2-pad mux still works after the roundtrip
        pipe.start()
        pipe["a"].push(np.int32([1]))
        pipe["b"].push(np.int32([2]))
        pipe["a"].end_of_stream()
        pipe["b"].end_of_stream()
        pipe.wait(timeout=15)
        pipe.stop()
        assert len(pipe["out"].frames[0].tensors) == 2

    def test_tee_fanout_roundtrip(self):
        text = (
            "appsrc name=src ! tee name=t  "
            "t. ! tensor_sink name=s1  t. ! tensor_sink name=s2"
        )
        pb = pbtxt.pipeline_text_to_pbtxt(text)
        text2 = pbtxt.pbtxt_to_pipeline_text(pb)
        # regenerated text must parse and produce the identical pbtxt
        assert pbtxt.pipeline_text_to_pbtxt(text2) == pb

    def test_mux_sink_pad_order_preserved(self):
        # pbtxt links listed in REVERSE pad order: regenerated text must
        # still put a on pad 1 and b on pad 0
        pb = (
            'node { name: "a" type: "appsrc" }\n'
            'node { name: "b" type: "appsrc" }\n'
            'node { name: "m" type: "tensor_mux" }\n'
            'node { name: "out" type: "tensor_sink" }\n'
            'link { src: "a" src_pad: 0 sink: "m" sink_pad: 1 }\n'
            'link { src: "b" src_pad: 0 sink: "m" sink_pad: 0 }\n'
            'link { src: "m" src_pad: 0 sink: "out" sink_pad: 0 }\n'
        )
        text = pbtxt.pbtxt_to_pipeline_text(pb)
        pipe = parse_pipeline(text)
        pipe.start()
        pipe["a"].push(np.int32([1]))
        pipe["b"].push(np.int32([2]))
        pipe["a"].end_of_stream()
        pipe["b"].end_of_stream()
        pipe.wait(timeout=15)
        pipe.stop()
        f = pipe["out"].frames[0]
        # pad 0 (b) first, pad 1 (a) second
        assert [int(t[0]) for t in f.tensors] == [2, 1]

    def test_quote_escaping_roundtrip(self):
        text = 'appsrc name=src ! tensor_sink name=out'
        pipe = parse_pipeline(text)
        # poke a property value containing quotes/backslash through pbtxt
        pb = pbtxt.pipeline_to_pbtxt(pipe).replace(
            'name: "src"', 'name: "src"'
        )
        pipe2 = pbtxt.pbtxt_to_pipeline(pb)
        assert set(pipe2.elements) == {"src", "out"}
        # writer escapes embedded quotes so its own output re-parses
        from nnstreamer_tpu.cli.pbtxt import _q

        assert _q('a="b"') == 'a=\\"b\\"'

    def test_bad_pbtxt(self):
        from nnstreamer_tpu.pipeline.parser import ParseError

        with pytest.raises(ParseError):
            pbtxt.pbtxt_to_pipeline("node { name: unbalanced")
        with pytest.raises(ParseError):
            pbtxt.pbtxt_to_pipeline('node { name: "x" type: "nonexistent" }')


class TestConfchk:
    def test_report_lists_elements_and_backends(self):
        rep = confchk.report()
        assert "tensor_filter" in rep
        assert "tensor_converter" in rep
        assert "filter subplugins" in rep
        assert "jax-xla" in rep
        assert "decoder subplugins" in rep

    def test_report_states_the_facts_chip_smoke_asserts(self):
        """Same vocabulary, same in-process probe: platform, device kind
        and count, compile-cache directory, mailbox implementation."""
        import jax

        from nnstreamer_tpu.native.runtime import mailbox_impl

        lines = dict(
            (k.strip(), v.strip()) for k, _, v in
            (ln.partition(":") for ln in confchk.report().splitlines())
            if v)
        dev = jax.devices()[0]
        assert lines["jax platform"] == dev.platform == "cpu"
        assert lines["device kind"] == dev.device_kind
        assert lines["device count"] == str(len(jax.devices()))
        assert lines["compile cache dir"] == (
            os.environ.get("JAX_COMPILATION_CACHE_DIR") or "(none: cpu)")
        assert lines["mailbox"] == mailbox_impl()


class TestCodegen:
    def test_python_scaffold_is_loadable(self, tmp_path):
        (path,) = codegen.generate("my_scaler", "python", str(tmp_path))
        ns = {}
        exec(compile(open(path).read(), path, "exec"), ns)
        flt = ns["filter"]("")
        out = flt.invoke([np.zeros((3, 4, 4), np.uint8)])
        assert out[0].shape == (3, 4, 4)

    def test_c_scaffold_compiles_and_runs(self, tmp_path):
        files = codegen.generate("my_native", "c", str(tmp_path))
        assert any(f.endswith(".c") for f in files)
        r = subprocess.run(
            ["make", "-C", str(tmp_path)], capture_output=True, text=True
        )
        assert r.returncode == 0, r.stderr
        so = tmp_path / "my_native.so"
        assert so.exists()
        # run through the custom-native backend
        from nnstreamer_tpu.backends.custom_native import CustomNative

        be = CustomNative()
        be.open(str(so), {})
        ins, outs = be.get_model_info()
        assert tuple(ins.tensors[0].shape) == (3, 224, 224)
        x = np.arange(3 * 224 * 224, dtype=np.uint8).reshape(3, 224, 224)
        (y,) = be.invoke([x])
        np.testing.assert_array_equal(x, y)
        be.close()


class TestLaunchCli:
    def test_launch_runs_pipeline(self):
        r = subprocess.run(
            [
                sys.executable,
                "-m",
                "nnstreamer_tpu.cli.launch",
                "-q",
                "videotestsrc num-buffers=2 ! tensor_converter ! "
                "tensor_sink name=out",
            ],
            capture_output=True,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=120,
        )
        assert r.returncode == 0, r.stderr


class TestInspectCli:
    def test_list_all_elements(self, capsys):
        from nnstreamer_tpu.cli.inspect import main

        assert main([]) == 0
        out = capsys.readouterr().out
        assert "tensor_filter" in out and "appsrc" in out
        assert "decoder subplugins" in out

    def test_inspect_element_properties(self, capsys):
        from nnstreamer_tpu.cli.inspect import main

        assert main(["tensor_filter"]) == 0
        out = capsys.readouterr().out
        assert "framework" in out and "max-batch" in out

    def test_unknown_element_suggests(self, capsys):
        from nnstreamer_tpu.cli.inspect import main

        assert main(["tensor_filt"]) == 1
        out = capsys.readouterr().out
        assert "did you mean" in out and "tensor_filter" in out


class TestConvertCli:
    """nns-tpu-convert: third-party model -> native .jaxexport artifact
    (≙ vendor offline compilers: snpe-onnx-to-dlc, edgetpu_compiler)."""

    def test_tflite_roundtrip(self, tmp_path):
        from test_tflite_import import build_affine_tflite
        from nnstreamer_tpu.cli.convert import main as convert_main
        from nnstreamer_tpu.elements.filter import SingleShot

        src = tmp_path / "aff.tflite"
        src.write_bytes(build_affine_tflite())
        dst = tmp_path / "aff.jaxexport"
        assert convert_main([str(src), str(dst)]) == 0
        with SingleShot("jax-xla", str(dst)) as m:
            (out,) = m.invoke([np.full((1, 4), 3.0, np.float32)])
            np.testing.assert_allclose(np.asarray(out),
                                       np.full((1, 4), 7.0))

    def test_onnx_default_output_name(self, tmp_path):
        from test_onnx_import import build_mlp
        from nnstreamer_tpu.cli.convert import main as convert_main

        blob, _ = build_mlp()
        src = tmp_path / "mlp.onnx"
        src.write_bytes(blob)
        assert convert_main([str(src)]) == 0
        assert (tmp_path / "mlp.jaxexport").exists()

    def test_unsupported_format_fails_clearly(self, tmp_path):
        from nnstreamer_tpu.cli.convert import main as convert_main

        src = tmp_path / "model.caffemodel"
        src.write_bytes(b"x")
        with pytest.raises(SystemExit, match="unsupported source format"):
            convert_main([str(src)])

    def test_convert_conv_model_batch_polymorphic(self, tmp_path):
        """Shape-sensitive graphs (Conv) convert with the default
        symbolic batch dim and serve micro-batched (regression: the
        extra axis must vmap, never reach the conv)."""
        from test_onnx_import import build_cnn
        from nnstreamer_tpu.cli.convert import main as convert_main
        from nnstreamer_tpu.backends.jax_xla import JaxXla

        blob, _ = build_cnn()
        src = tmp_path / "cnn.onnx"
        src.write_bytes(blob)
        dst = tmp_path / "cnn.jaxexport"
        assert convert_main([str(src), str(dst)]) == 0
        be = JaxXla()
        be.open(str(dst), {})
        try:
            xs = np.random.default_rng(0).standard_normal(
                (3, 1, 3, 16, 16)).astype(np.float32)
            (out,) = be.invoke_batch([xs])
            assert np.asarray(out).shape == (3, 1, 5)
            (o1,) = be.invoke([xs[0]])
            np.testing.assert_allclose(np.asarray(out)[0],
                                       np.asarray(o1), rtol=1e-5)
        finally:
            be.close()
