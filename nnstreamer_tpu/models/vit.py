"""ViT image classifier (flax) — the transformer-era vision family.

The reference's model zoo is convnet-centric (per-vendor tflite/onnx
classifiers); a Vision Transformer is the TPU-native complement: patch
embedding + attention blocks are large dense matmuls that map straight
onto the MXU.  Attention is ONE call, ``ops/flash_attention.py``'s
``flash_attention_qkv`` on the fused qkv projection, and what runs is
decided by what the code can observe, not by a property: a program
lowered for one TPU device runs the Pallas kernel (the score matrix stays
in VMEM), every other platform — and a program compiled for a mesh, which
a Mosaic call cannot be partitioned over — the fused-XLA reference.

Zoo entry ``vit``: fn(params, [images_u8 (N,S,S,3)], single_device=True)
-> [logits (N,classes)]; ``single_device`` comes from whoever compiles the
function (``backends/jax_xla.py``, the trainer: False under ``mesh=``).
Props: size (default 224), patch (16), d_model (192), heads (3),
layers (6), d_ff (768), classes (1001), dtype.
"""

from __future__ import annotations

from typing import Any, List

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import FORMAT_STATIC, StreamSpec, TensorSpec
from ._init_util import host_init


class EncoderBlock(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    quant: bool = False  # int8 MXU dense layers (_quant_flax.QuantDense)
    single_device: bool = True  # False: compiled for a mesh, no Mosaic call

    def _dense(self, features, name):
        from ._quant_flax import dense_or_quant

        # same explicit name -> same param path/RNG fold either way
        return dense_or_quant(self.quant, features, self.dtype, name)

    @nn.compact
    def __call__(self, x):  # (B, T, D), pre-norm ViT block
        from ..ops.flash_attention import flash_attention_qkv

        D = x.shape[-1]
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        qkv = self._dense(3 * D, "attn_qkv")(h)
        # kernel forward on a TPU, recompute backward; (B, T, 3D) -> (B, T, D)
        a = flash_attention_qkv(qkv, self.n_heads, False, self.single_device)
        x = x + self._dense(D, "attn_out")(a)
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = self._dense(self.d_ff, "mlp_up")(h)
        h = jax.nn.gelu(h)
        return x + self._dense(D, "mlp_down")(h)


class ViT(nn.Module):
    size: int = 224
    patch: int = 16
    d_model: int = 192
    n_heads: int = 3
    n_layers: int = 6
    d_ff: int = 768
    num_classes: int = 1001
    dtype: Any = jnp.bfloat16
    quant: bool = False
    single_device: bool = True

    @nn.compact
    def __call__(self, x):  # (B, S, S, 3) uint8 or float
        if x.dtype == jnp.uint8:
            x = x.astype(self.dtype) * (2.0 / 255.0) - 1.0
        else:
            x = x.astype(self.dtype)
        # patchify as one conv: the embedding matmul the MXU loves
        x = nn.Conv(
            self.d_model, (self.patch, self.patch),
            strides=(self.patch, self.patch), padding="VALID",
            dtype=self.dtype, name="patch_embed",
        )(x)
        B = x.shape[0]
        x = x.reshape(B, -1, self.d_model)  # (B, T, D)
        T = x.shape[1]
        cls = self.param(
            "cls", nn.initializers.zeros, (1, 1, self.d_model)
        ).astype(self.dtype)
        x = jnp.concatenate([jnp.broadcast_to(cls, (B, 1, self.d_model)), x], 1)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (1, T + 1, self.d_model),
        ).astype(self.dtype)
        x = x + pos
        for i in range(self.n_layers):
            x = EncoderBlock(
                self.d_model, self.n_heads, self.d_ff,
                dtype=self.dtype, quant=self.quant,
                single_device=self.single_device, name=f"block{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        return nn.Dense(
            self.num_classes, dtype=jnp.float32, name="head"
        )(x[:, 0].astype(jnp.float32))


def build(custom_props=None):
    """Zoo entry: fn(params, [images_u8 (N,S,S,3)]) -> [logits]."""
    props = custom_props or {}
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        props.get("dtype", "bfloat16")
    ]
    size = int(props.get("size", "224"))
    patch = int(props.get("patch", "16"))
    if size % patch:
        raise ValueError(f"size {size} not divisible by patch {patch}")
    model = ViT(
        size=size,
        patch=patch,
        d_model=int(props.get("d_model", "192")),
        n_heads=int(props.get("heads", "3")),
        n_layers=int(props.get("layers", "6")),
        d_ff=int(props.get("d_ff", "768")),
        num_classes=int(props.get("classes", "1001")),
        dtype=dtype,
        quant=props.get("quantize", "") == "int8",
    )
    # init needs the shapes only: its program keeps to XLA, whatever
    # device it is compiled for
    variables = host_init(
        model.clone(single_device=False).init,
        int(props.get("seed", "0")),
        np.zeros((1, size, size, 3), np.uint8),
    )

    def fn(params, inputs: List[Any], single_device: bool = True) -> List[Any]:
        x = inputs[0]
        single = x.ndim == 3
        if single:
            x = x[None]
        out = model.clone(single_device=single_device).apply(params, x)
        return [out[0] if single else out]

    in_spec = StreamSpec(
        (TensorSpec((size, size, 3), np.uint8, "image"),), FORMAT_STATIC
    )
    out_spec = StreamSpec(
        (TensorSpec((model.num_classes,), np.float32, "logits"),),
        FORMAT_STATIC,
    )
    return fn, variables, in_spec, out_spec
