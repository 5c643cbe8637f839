"""Fused top-1 (argmax + max score) over class logits.

≙ the image-labeling decoder's C argmax loop
(``tensordec-imagelabel.c``), done once per micro-batch on device: a
Pallas row-reduction when the program is lowered for a TPU, the
identical jnp expression on every other platform
(``lax.platform_dependent`` picks at lowering time, from the device the
program is compiled for — never from the process default).
Returning (idx, score) together saves a second pass over HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128
_ROWS = 256  # row block once a batch outgrows one VMEM block


def _kernel(x_ref, idx_ref, val_ref):
    x = x_ref[...]  # (rows, Cp) f32
    m = jnp.max(x, axis=1, keepdims=True)
    # argmax as "first column holding the max" (argmax's own tie rule),
    # reduced in f32: every shape stays 2-D and every reduction is a
    # float lane reduction, which is what Mosaic lowers everywhere.
    # Column indices are exact in f32 (class counts << 2^24).
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1).astype(jnp.float32)
    first = jnp.min(
        jnp.where(x == m, col, float(x.shape[1])), axis=1, keepdims=True)
    idx_ref[...] = first.astype(jnp.int32)
    val_ref[...] = m


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_top1(x, interpret: bool = False):
    """(B, C) -> (argmax int32 (B,), max f32 (B,)) through the kernel."""
    B, C = x.shape
    # pad classes to a lane multiple with -inf (argmax unaffected)
    Cp = (C + _LANES - 1) // _LANES * _LANES
    x = x.astype(jnp.float32)
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C)), constant_values=-jnp.inf)
    rows = B if B <= _ROWS else _ROWS
    idx, val = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ),
        grid=(pl.cdiv(B, rows),),
        in_specs=[pl.BlockSpec((rows, Cp), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(x)
    return idx[:, 0], val[:, 0]


def _jnp_top1(x):
    return (jnp.argmax(x, axis=1).astype(jnp.int32),
            jnp.max(x.astype(jnp.float32), axis=1))


def top1(logits, use_pallas: bool = True, interpret: bool = False):
    """logits (B, C) or (C,) -> (argmax int32, max float32) per row.

    ``use_pallas=False`` is for callers whose program is partitioned
    over a device mesh: a Mosaic kernel cannot be auto-partitioned, so
    the fused decoder passes it from the backend's mesh state.
    ``interpret`` runs the kernel in the Pallas interpreter (tests).
    """
    x = jnp.asarray(logits)
    single = x.ndim == 1
    if single:
        x = x[None]
    if not use_pallas:
        idx, val = _jnp_top1(x)
    elif interpret:
        idx, val = _pallas_top1(x, interpret=True)
    else:
        idx, val = lax.platform_dependent(
            x, tpu=_pallas_top1, default=_jnp_top1)
    if single:
        return idx[0], val[0]
    return idx, val
