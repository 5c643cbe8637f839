"""tensor_decoder: tensor stream -> media/labels/boxes via decoder subplugins.

Reference: ``gst/nnstreamer/elements/gsttensor_decoder.c`` (mode prop + 9
option strings passed to the subplugin, ``nnstreamer_decoder_find`` :177) and
the decoder ABI ``GstTensorDecoderDef`` {init, exit, setOption, getOutCaps,
decode} (``nnstreamer_plugin_api_decoder.h:38-61``).

Decoder subplugins register under registry kind "decoder" with the contract:

    class MyDecoder:
        NAME = "my_mode"
        def set_options(self, options: list[str]) -> None: ...
        def get_out_spec(self, in_spec: StreamSpec) -> StreamSpec: ...
        def decode(self, frame: TensorFrame, in_spec) -> TensorFrame: ...
"""

from __future__ import annotations


from ..core import registry
from ..core.buffer import BatchFrame
from ..core.tracer import BATCH_SEQ_META, span
from ..core.types import ANY
from ..pipeline.element import ElementError, Property, TransformElement, element
from .. import decoders as _decoders  # noqa: F401 — registers decoder modes

_N_OPTIONS = 9  # reference carries option1..option9


@element("tensor_decoder")
class TensorDecoder(TransformElement):
    BATCH_AWARE = True  # splits blocks itself (or keeps them whole, fused)

    PROPERTIES = {
        "mode": Property(str, "", "decoder subplugin name"),
        **{
            f"option{i}": Property(str, "", f"mode-specific option {i}")
            for i in range(1, _N_OPTIONS + 1)
        },
        "max-buffers": Property(int, 0, "mailbox depth override"),
        "config-file": Property(
            str, "", "key=value file applied as properties (explicit "
            "pipeline-text properties win; ≙ gsttensor_decoder config-file)"
        ),
        "device-fused": Property(
            str, "auto",
            "auto = let the pipeline fold this decoder's device half "
            "(subplugin device_fn) into the upstream jax-xla filter's XLA "
            "program; never = always decode on host",
        ),
        "split-batches": Property(
            bool, True,
            "fan incoming BatchFrames out to per-frame decodes (false = "
            "decode the block vectorized and pass it downstream whole, "
            "when the subplugin implements decode_fused_batch)",
        ),
    }

    def __init__(self, name=None):
        super().__init__(name)
        self._dec = None
        self._fused = False  # set by the pipeline's device-fusion pass

    # -- device fusion (pipeline pass) --------------------------------------
    @property
    def can_fuse_device(self) -> bool:
        if (
            self._dec is None
            or not hasattr(self._dec, "device_fn")
            or not hasattr(self._dec, "decode_fused")
            or self.props["device-fused"] == "never"
        ):
            return False
        # subplugins with per-configuration device support (e.g.
        # bounding_boxes: only some box modes are traceable) gate here
        supports = getattr(self._dec, "supports_device_fn", None)
        return supports() if callable(supports) else True

    def enable_fused(self) -> None:
        self._fused = True

    def start(self):
        self._apply_config_file()
        self._fused = False  # re-fused (or not) by the pass on every start
        mode = self.props["mode"]
        if not mode:
            raise ElementError(f"{self.name}: decoder requires mode=")
        try:
            cls = registry.get(registry.KIND_DECODER, mode)
        except KeyError:
            raise ElementError(f"{self.name}: unknown decoder mode {mode!r}") from None
        self._dec = cls() if isinstance(cls, type) else cls
        options = [self.props[f"option{i}"] for i in range(1, _N_OPTIONS + 1)]
        if hasattr(self._dec, "set_options"):
            self._dec.set_options(options)

    def stop(self):
        if self._dec is not None and hasattr(self._dec, "exit"):
            self._dec.exit()
        self._dec = None

    def derive_spec(self, pad=0):
        in_spec = self.sink_specs.get(0, ANY)
        if self._dec is not None and hasattr(self._dec, "get_out_spec"):
            return self._dec.get_out_spec(in_spec)
        return ANY

    def transform(self, frame):
        assert self._dec is not None, f"{self.name} not started"
        if self._fused:
            return self._dec.decode_fused(frame, self.sink_specs.get(0, ANY))
        return self._dec.decode(frame, self.sink_specs.get(0, ANY))

    def handle_frame(self, pad, frame):
        # batch-through fast path: the upstream filter hands the whole
        # micro-batch as ONE device-resident BatchFrame; split() does the
        # single (tiny, post-device_fn) device->host transfer, then the
        # host finisher runs per logical frame.
        if not isinstance(frame, BatchFrame):
            return super().handle_frame(pad, frame)
        # ``seq`` is the upstream filter's number for this micro-batch
        with span("nns.decoder.batch", seq=frame.meta.get(BATCH_SEQ_META),
                  frames=frame.batch_size):
            spec = self.sink_specs.get(0, ANY)
            if (
                self._fused
                and not self.props["split-batches"]
                and hasattr(self._dec, "decode_fused_batch")
            ):
                # vectorized host finish: the block stays whole (chip-rate
                # streams: the per-frame fan-out is itself a bottleneck)
                return [(0, self._dec.decode_fused_batch(frame, spec))]
            dec = self._dec.decode_fused if self._fused else self._dec.decode
            return [(0, dec(f, spec)) for f in frame.split()]
