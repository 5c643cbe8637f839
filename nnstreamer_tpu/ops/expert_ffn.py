"""The held experts' part of a routed-expert layer: ``out[m] = sum_e gate[m,
e] * relu(x[m] W_up[e])^2 W_down[e]`` over the experts that have a token, or,
given a third matrix per expert (``gate_w``), ``silu(x[m] W_gate[e]) * (x[m]
W_up[e])`` in the square's place.  Two kernels, one entry
(:func:`held_experts_ffn`), chosen by the batch's rows alone.

A SMALL batch (a decode step's slots, a short prefill chunk; up to
:data:`MAX_TOKENS` rows) is bound by reading expert weights, so
``nns_touched_experts_ffn`` walks the TOUCHED experts only (a compacted id
list, scalar-prefetched: the weight blocks of an expert nobody chose are
never fetched; the list's tail repeats its last id, and a block index that
does not change costs no copy), streams each expert's ``up`` and ``down``
through VMEM in tiles of the expert width, runs EVERY token of the batch
through the tile and weighs the result by the token's gate (0 where the
expert was not chosen).  No token is sorted, grouped or dropped, whatever the
skew; the products a token was not routed to are idle MXU work hidden under
the weight stream.  (On a v5e, 64 experts of 2688 x 1920: 1.4-1.8 ms a call
at 32-256 rows against 9-15 ms for XLA's grouped product ``ragged_dot``:
PERF.md section 6, PR 29.)

A LARGE batch (a long prefill chunk) would pay for those idle products, so
``nns_grouped_experts_ffn`` runs each expert over ITS rows only: the picks
that fell on a held expert are laid out by expert, each expert's group
padded to whole row tiles; a scalar-prefetched table gives each row tile its
expert, and the call walks the row tiles: the tile's rows are drawn out of
the batch (which stays in VMEM) by a 0/1 product, go through the expert's
``up`` (and ``gate``) and ``down`` tiles, are weighed by the pick's gate and
added back to their tokens in float32 by the transposed 0/1 product.
Consecutive tiles of one expert keep the expert's block index, tiles past
the last one run nothing and fetch nothing, an expert nobody chose is never
read, and no pick is dropped under skew (there is no capacity).

A Pallas kernel when the program is lowered for a TPU, the caller's own jnp
form on every other platform (``lax.platform_dependent``, as
``ops/labeling.py``); ``interpret=True`` runs the kernels in the Pallas
interpreter (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows the small-batch call takes: beyond it the idle products (every token
#: through every touched expert) outweigh the weight stream on a v5e
#: (2 x tokens FLOP per weight byte against 197 TFLOP/s over 819 GB/s)
MAX_TOKENS = 256
_ROWS = 16      # token rows are padded to whole bf16 sublane tiles
_LANES = 128
#: a weight tile's bytes (one of ``up``, ``down`` or ``gate``); two or three
#: arrays, double buffered, stay under the scoped VMEM limit set below
_TILE_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20
#: what the grouped call keeps in VMEM beside its weight tiles (a block of
#: the batch and its float32 result): its limit is the sum of the two, under
#: a v5e core's 128 MiB
_RESIDENT_BYTES = 32 << 20
#: rows of one tile of the grouped call: one MXU pass.  A tile streams its
#: expert's weights for its own rows, so on a v5e it is bound by that stream
#: and taller tiles only pad more (a 1024-row chunk, 32 experts of 2048 x
#: 1792: 1.4 ms a layer against 1.8 with 256-row tiles; PERF.md section 6,
#: PR 37)
TILE_ROWS = 128


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
    W_up)^2``, the call it was, operand for operand.  The small-batch
    kernel, whatever ``M``: :func:`held_experts_ffn` chooses."""
    M, D = x.shape
    held, _, F = up.shape
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


def _cols(d):
    """Columns of the batch the 0/1 products take at a time: whole lane
    tiles dividing ``d``, at most four."""
    if d % _LANES:
        return d
    lanes = d // _LANES
    return max(k for k in range(1, 5) if lanes % k == 0) * _LANES


def _blocks(m, d):
    """``(blocks, rows a block)`` of a batch of ``m`` rows: one block where
    the rows and their float32 result fit :data:`_RESIDENT_BYTES`, else the
    fewest equal blocks that do (each lays out its own picks)."""
    cap = max(TILE_ROWS, _RESIDENT_BYTES // (6 * d) // TILE_ROWS * TILE_ROWS)
    nb = -(-m // cap)
    rows = -(-m // nb)
    return nb, rows + (-rows) % _ROWS


def _in_blocks(a, nb, mb, fill=0):
    """``a`` (M, ...) as ``nb`` blocks of ``mb`` rows, the rows past ``M``
    holding ``fill``."""
    if nb * mb > a.shape[0]:
        pad = ((0, nb * mb - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
        a = jnp.pad(a, pad, constant_values=fill)
    return a.reshape((nb, mb) + a.shape[1:])


def _layout(lid, held, tiles):
    """One block's picks laid out by held expert, each group padded to whole
    tiles of :data:`TILE_ROWS` rows.  ``lid`` (rows, k) int32, ``held`` where the pick
    fell on no held expert.  Returns ``(dest (rows, k)`` the pick's row in
    that layout, -1 for none, ``expert (tiles,)`` each tile's expert (past
    the last tile: the last tile's), ``n ()`` the tiles that hold a pick``)``."""
    flat = lid.reshape(-1)
    hot = flat[:, None] == jnp.arange(held, dtype=flat.dtype)[None, :]
    upto = jnp.cumsum(hot.astype(jnp.int32), axis=0)  # picks of the expert so far
    per = -(-upto[-1] // TILE_ROWS)                   # tiles an expert
    ends = jnp.cumsum(per)
    start = (ends - per) * TILE_ROWS
    dest = jnp.sum(jnp.where(hot, start[None, :] + upto - 1, 0), axis=1)
    dest = jnp.where(jnp.any(hot, axis=1), dest, -1).reshape(lid.shape)
    n = ends[-1]
    t = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    expert = jnp.sum(t[:, None] >= ends[None, :], axis=1)
    return dest, jnp.minimum(expert, held - 1).astype(jnp.int32), n.astype(jnp.int32)


def tiled_rows(lid, held, d):
    """Rows the grouped call's tiles hold for the picks ``lid`` (M, k) of a
    batch (``held``: the pick fell on no held expert): every block's groups
    padded to whole row tiles."""
    lid = _in_blocks(lid, *_blocks(lid.shape[0], d), fill=held)
    sizes = jnp.sum(lid[..., None] == jnp.arange(held), axis=(1, 2))
    return jnp.sum(-(-sizes // TILE_ROWS) * TILE_ROWS)


def rows_run(lid, held, d):
    """What ``gen_moe_grouped_rows_run`` counts for a batch's picks:
    :func:`tiled_rows` where the kernel is taken, the picks themselves where
    it is not (no TPU); None for a batch the small-batch kernel takes."""
    if lid.shape[0] <= MAX_TOKENS:
        return None
    return jax.lax.platform_dependent(
        lid, tpu=lambda lid: tiled_rows(lid, held, d),
        default=lambda lid: jnp.sum(lid < held))


def _exact(dtype):
    """The precision at which a 0/1 product copies rows of ``dtype``."""
    return None if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST


def _grouped_kernel(expert_ref, n_ref, x_ref, dest_ref, dest_t_ref, w_t_ref,
                    up_ref, down_ref, *refs, cols: int):
    """``refs``: the experts' ``gate`` tile where the activation is gated,
    the output block, and the scratch: the tile's rows, their float32
    result, their gates."""
    wg_ref = refs[0] if len(refs) == 5 else None
    out_ref, rows_ref, acc_ref, g_ref = refs[-4:]
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    M, D = x_ref.shape
    k, tm = dest_t_ref.shape[0], TILE_ROWS

    @pl.when((t == 0) & (j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t < n_ref[b])
    def _():
        @pl.when(j == 0)
        def _():  # the tile's rows out of the batch, exactly: one 1 a row
            row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, M), 0)
            hit = [dest_t_ref[i:i + 1, :] == row for i in range(k)]
            one = jnp.where(functools.reduce(jnp.logical_or, hit), 1.0, 0.0).astype(x_ref.dtype)
            g_ref[...] = sum(
                jnp.sum(jnp.where(h, w_t_ref[i:i + 1, :], 0.0), axis=1, keepdims=True)
                for i, h in enumerate(hit))
            for c in range(0, D, cols):
                rows_ref[:, c:c + cols] = jnp.dot(
                    one, x_ref[:, c:c + cols], preferred_element_type=jnp.float32,
                    precision=_exact(x_ref.dtype)).astype(rows_ref.dtype)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = rows_ref[...]
        hid = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        if wg_ref is None:
            hid = jnp.square(jnp.maximum(hid, 0.0)).astype(x.dtype)
        else:
            hid = (jax.nn.silu(jnp.dot(
                x, wg_ref[0], preferred_element_type=jnp.float32)) * hid).astype(x.dtype)
        acc_ref[...] += jnp.dot(hid, down_ref[0], preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():  # back to the tokens in float32: three bf16 terms hold its 24 bits
            col = t * tm + jax.lax.broadcasted_iota(jnp.int32, (M, tm), 1)
            one = jnp.where(functools.reduce(jnp.logical_or, [
                dest_ref[:, i:i + 1] == col for i in range(k)]), 1.0, 0.0).astype(jnp.bfloat16)
            one = jnp.concatenate([one] * 3, axis=1)
            for c in range(0, D, cols):
                rest, terms = acc_ref[:, c:c + cols] * g_ref[...], []
                for _ in range(3):
                    terms.append(rest.astype(jnp.bfloat16))
                    rest = rest - terms[-1].astype(jnp.float32)
                out_ref[:, c:c + cols] += jnp.dot(
                    one, jnp.concatenate(terms, axis=0),
                    preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_experts_ffn(x, lid, weights, up, down, gate_w=None, interpret: bool = False):
    """``x`` (M, D), ``lid`` (M, k) int32 each pick's held expert (``held``:
    none), ``weights`` (M, k) float32 the picks' gates, ``up`` (held, D, F),
    ``down`` (held, F, D), ``gate_w`` as :func:`touched_experts_ffn` -> (M, D)
    float32.  The grouped kernel, whatever ``M``."""
    M, D = x.shape
    held, _, F = up.shape
    k = lid.shape[1]
    tm, tf = TILE_ROWS, _tile(D, F, up.dtype.itemsize)
    nf = F // tf
    nb, mb = _blocks(M, D)
    tiles = mb * k // tm + held  # no layout of a block's picks holds more
    x, weights = _in_blocks(x, nb, mb), _in_blocks(weights, nb, mb)
    dest, expert, n = jax.vmap(lambda i: _layout(i, held, tiles))(
        _in_blocks(lid, nb, mb, fill=held))

    def weight(b, t, j, expert, n):  # past the last tile: the block stays put
        return expert[b, t], jnp.where(t < n[b], j, nf - 1)

    def wide(b, t, j, expert, n):
        e, f = weight(b, t, j, expert, n)
        return e, 0, f

    def tall(b, t, j, expert, n):
        e, f = weight(b, t, j, expert, n)
        return e, f, 0

    def block(b, t, j, expert, n):
        return b, 0, 0

    def resident(*shape):
        return pl.BlockSpec((None,) + shape, block, pipeline_mode=pl.Buffered(1))

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, cols=_cols(D)),
        out_shape=jax.ShapeDtypeStruct((nb, mb, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, tiles, nf),
            in_specs=[
                resident(mb, D), resident(mb, k), resident(k, mb), resident(k, mb),
                pl.BlockSpec((1, D, tf), wide), pl.BlockSpec((1, tf, D), tall),
            ] + ([] if gate_w is None else [pl.BlockSpec((1, D, tf), wide)]),
            out_specs=resident(mb, D),
            scratch_shapes=[pltpu.VMEM((tm, D), x.dtype), pltpu.VMEM((tm, D), jnp.float32),
                            pltpu.VMEM((tm, 1), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT + _RESIDENT_BYTES),
        interpret=interpret,
        name="nns_grouped_experts_ffn",
    )(expert, n, x, dest, jnp.swapaxes(dest, 1, 2), jnp.swapaxes(weights, 1, 2),
      up, down, *([] if gate_w is None else [gate_w]))
    return out.reshape(nb * mb, D)[:M]


def held_experts_ffn(x, lid, weights, up, down, gate_w=None, interpret: bool = False):
    """The held experts' part for ``x`` (M, D) given each token's picks:
    ``lid`` (M, k) int32 the pick's held expert (``held``: it fell on none),
    ``weights`` (M, k) float32 its gate (0 there) -> (M, D) float32.  Up to
    :data:`MAX_TOKENS` rows stream the touched experts under every row,
    more rows go expert by expert through their own rows."""
    if x.shape[0] > MAX_TOKENS:
        return grouped_experts_ffn(x, lid, weights, up, down, gate_w, interpret=interpret)
    one_hot = lid[:, :, None] == jnp.arange(up.shape[0])[None, None, :]
    gates = jnp.sum(jnp.where(one_hot, weights[:, :, None], 0.0), axis=1)
    return touched_experts_ffn(x, gates, up, down, gate_w, interpret=interpret)
