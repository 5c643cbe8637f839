"""Fused uint8 -> float normalize (scale + bias + cast) as a Pallas kernel.

The canonical image-ingest hot path (``tensor_transform mode=arithmetic``
chains + typecast in the reference, ORC-accelerated there): one VMEM-tiled
pass computing ``x * scale + bias`` in the target dtype.  A program
lowered for a TPU runs the Pallas kernel (VPU elementwise, lane-aligned
tiles); every other platform lowers the identical jnp expression
(``lax.platform_dependent``: the choice follows the device the program
is compiled for).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128
_ROWS = 2048  # block rows: multiple of every dtype's sublane minimum


def _kernel(x_ref, o_ref, *, scale: float, bias: float):
    x = x_ref[...]
    if jnp.issubdtype(x.dtype, jnp.integer):
        # widen through int32: the one integer->float path every Mosaic
        # generation lowers for 8-bit inputs
        x = x.astype(jnp.int32)
    o_ref[...] = (x.astype(jnp.float32) * scale + bias).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "bias", "out_dtype", "interpret"))
def _pallas_normalize(x, *, scale: float, bias: float, out_dtype,
                      interpret: bool = False):
    n = x.size
    tile = _ROWS * _LANES
    padded = (n + tile - 1) // tile * tile
    flat = x.reshape(-1)
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    rows = padded // _LANES
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bias=bias),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), out_dtype),
        grid=(rows // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(flat.reshape(rows, _LANES))
    return out.reshape(-1)[:n].reshape(x.shape)


def normalize_u8(
    x,
    scale: float = 2.0 / 255.0,
    bias: float = -1.0,
    dtype: Any = jnp.bfloat16,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """``x * scale + bias`` cast to `dtype` (default: uint8 [0,255] ->
    [-1, 1] bf16, the MobileNet ingest transform).  Accepts any shape.
    ``interpret`` runs the kernel in the Pallas interpreter (tests)."""
    x = jnp.asarray(x)
    out_dtype = jnp.dtype(dtype)

    def plain(a):
        return (a.astype(jnp.float32) * scale + bias).astype(out_dtype)

    if not use_pallas:
        return plain(x)
    kernel = functools.partial(
        _pallas_normalize, scale=float(scale), bias=float(bias),
        out_dtype=out_dtype)
    if interpret:
        return kernel(x, interpret=True)
    return lax.platform_dependent(x, tpu=kernel, default=plain)
