"""The held experts' part of a routed-expert layer for a SMALL token batch
(a decode step's slots, one prefill chunk): ``out[m] = sum_e gate[m, e] *
relu(x[m] W_up[e])^2 W_down[e]`` over the experts that have a token, or,
given a third matrix per expert (``gate_w``), ``silu(x[m]
W_gate[e]) * (x[m] W_up[e])`` in the square's place.

With a few tokens per expert the layer is bound by reading expert weights,
so the kernel walks the TOUCHED experts only (a compacted id list, scalar-
prefetched: the weight blocks of an expert nobody chose are never fetched;
the list's tail repeats its last id, and a block index that does not change
costs no copy), streams each expert's ``up`` and ``down`` through VMEM in
tiles of the expert width, runs EVERY token of the batch through the tile
and weighs the result by the token's gate (0 where the expert was not
chosen).  No token is sorted, grouped or dropped, whatever the skew.  The
products a token was not routed to are idle MXU work hidden under the weight
stream as long as the batch is small; a larger batch goes through in blocks
of :data:`MAX_TOKENS` rows, each streaming the experts ITS rows touch.  (On
a v5e, 64 experts of 2688 x 1920: 1.4-1.8 ms a call at 32-256 rows against
9-15 ms for XLA's grouped product ``ragged_dot``: PERF.md section 6, PR 29.)

A Pallas kernel when the program is lowered for a TPU, the caller's own jnp
form on every other platform (``lax.platform_dependent``, as
``ops/labeling.py``); ``interpret=True`` runs the kernel in the Pallas
interpreter (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows one kernel call takes: beyond it the idle products (every token
#: through every touched expert) outweigh the weight stream on a v5e
#: (2 x tokens FLOP per weight byte against 197 TFLOP/s over 819 GB/s)
MAX_TOKENS = 256
_ROWS = 16      # token rows are padded to whole bf16 sublane tiles
_LANES = 128
#: a weight tile's bytes (one of ``up``, ``down`` or ``gate``); two or three
#: arrays, double buffered, stay under the scoped VMEM limit set below
_TILE_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20


def _tile(d, f, itemsize):
    """The widest tile of the expert width ``f`` (whole lane tiles, dividing
    ``f``) whose ``(d, tile)`` block stays within :data:`_TILE_BYTES`."""
    lanes = f // _LANES
    fits = [k for k in range(1, lanes + 1)
            if lanes % k == 0 and d * k * _LANES * itemsize <= _TILE_BYTES]
    return max(fits, default=1) * _LANES


def _kernel(ids_ref, n_ref, x_ref, gate_ref, up_ref, down_ref, *refs):
    """``refs``: the output, after the experts' ``gate`` matrices' tile
    where the activation is gated."""
    wg_ref, out_ref = refs if len(refs) == 2 else (None, refs[0])
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        hid = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        if wg_ref is None:
            hid = jnp.square(jnp.maximum(hid, 0.0)).astype(x.dtype)
        else:
            hid = (jax.nn.silu(jnp.dot(
                x, wg_ref[0], preferred_element_type=jnp.float32)) * hid).astype(x.dtype)
        part = jnp.dot(hid, down_ref[0], preferred_element_type=jnp.float32)
        gates = gate_ref[...]  # (M, held): this expert's column, by a mask
        col = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 1)
        gate = jnp.sum(jnp.where(col == ids_ref[i], gates, 0.0),
                       axis=1, keepdims=True)
        out_ref[...] += part * gate


@functools.partial(jax.jit, static_argnames=("interpret",))
def touched_experts_ffn(x, gates, up, down, gate_w=None, interpret: bool = False):
    """``x`` (M, D), ``gates`` (M, held) float32 (0 where a token did not
    choose the expert), ``up`` (held, D, F), ``down`` (held, F, D) with F
    whole lane tiles -> (M, D) float32.  ``gate_w`` (held, D, F): the
    experts are gated (``silu(x W_gate) * (x W_up)``); None: ``relu(x
    W_up)^2``, the call it was, operand for operand."""
    M, D = x.shape
    held, _, F = up.shape
    if M > MAX_TOKENS:  # unrolled: inside a loop XLA fuses the call and drops its VMEM limit
        return jnp.concatenate([
            touched_experts_ffn(x[i:i + MAX_TOKENS], gates[i:i + MAX_TOKENS], up, down,
                                gate_w, interpret=interpret)
            for i in range(0, M, MAX_TOKENS)])
    pad = (-M) % _ROWS
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        gates = jnp.pad(gates, ((0, pad), (0, 0)))
    touched = jnp.any(gates != 0.0, axis=0)
    n = jnp.sum(touched).astype(jnp.int32)
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(held) < n, order, order[jnp.maximum(n - 1, 0)])
    tf = _tile(D, F, up.dtype.itemsize)
    nf = F // tf

    def tile(i, j, ids, n):  # past the touched experts: the block stays put
        return jnp.where(i < n[0], j, nf - 1)

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((M + pad, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, nf),
            in_specs=[
                pl.BlockSpec((M + pad, D), lambda i, j, ids, n: (0, 0)),
                pl.BlockSpec((M + pad, held), lambda i, j, ids, n: (0, 0)),
                pl.BlockSpec((1, D, tf),
                             lambda i, j, ids, n: (ids[i], 0, tile(i, j, ids, n))),
                pl.BlockSpec((1, tf, D),
                             lambda i, j, ids, n: (ids[i], tile(i, j, ids, n), 0)),
            ] + ([] if gate_w is None else [
                pl.BlockSpec((1, D, tf),
                             lambda i, j, ids, n: (ids[i], 0, tile(i, j, ids, n)))]),
            out_specs=pl.BlockSpec((M + pad, D), lambda i, j, ids, n: (0, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="nns_touched_experts_ffn",
    )(ids, n[None], x, gates, up, down, *([] if gate_w is None else [gate_w]))
    return out[:M]
