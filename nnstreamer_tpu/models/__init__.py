"""Model zoo: flax implementations of the model families the reference's
example pipelines run (MobileNet-v2, SSD-MobileNet, YOLOv5, PoseNet, MNIST
CNN, plus a long-context transformer for the parallel/ subsystem).

``build(name, custom_props)`` returns ``(fn, params, in_spec, out_spec)``
with ``fn(params, inputs: list) -> list`` jit-traceable — the contract the
jax-xla backend consumes (``custom=arch:<name>``).  A family that holds a
Mosaic kernel (the ViT's attention) also takes ``single_device=True``: its
compiler passes False under a mesh, where such a call cannot be partitioned.
"""

from __future__ import annotations

from importlib import import_module
from typing import Dict, Optional, Tuple

_ZOO = {
    "mobilenet_v2": "nnstreamer_tpu.models.mobilenet_v2",
    "ssd_mobilenet_v2": "nnstreamer_tpu.models.ssd_mobilenet",
    "yolov5s": "nnstreamer_tpu.models.yolov5",
    "posenet": "nnstreamer_tpu.models.posenet",
    "mnist_cnn": "nnstreamer_tpu.models.mnist_cnn",
    "transformer": "nnstreamer_tpu.models.transformer",
    "deeplab": "nnstreamer_tpu.models.deeplab",
    "kws_cnn": "nnstreamer_tpu.models.kws_cnn",
    "vit": "nnstreamer_tpu.models.vit",
}


def takes_single_device(fn) -> bool:
    """Whether ``fn`` (a zoo model, a fused postprocess) takes the
    ``single_device`` keyword its compiler binds."""
    import inspect

    try:
        return "single_device" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def available() -> Tuple[str, ...]:
    """Families whose modules are actually present."""
    import importlib.util

    return tuple(
        name for name, mod in _ZOO.items()
        if importlib.util.find_spec(mod) is not None
    )


def build(name: str, custom_props: Optional[Dict[str, str]] = None):
    if name not in _ZOO:
        raise KeyError(f"unknown model family {name!r}; available: {sorted(_ZOO)}")
    try:
        mod = import_module(_ZOO[name])
    except ModuleNotFoundError as e:
        raise KeyError(f"model family {name!r} is not built yet: {e}") from None
    props = dict(custom_props or {})
    if "dtype" not in props:
        # hw-probed default: bfloat16 on accelerators (MXU-native),
        # float32 on host CPU (core/hw.py, ≙ reference hw_accel.c probe)
        from ..core import hw

        props["dtype"] = hw.preferred_dtype()
    return mod.build(props)
