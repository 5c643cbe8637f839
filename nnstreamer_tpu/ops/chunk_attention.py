"""A prefill chunk's attention over a slotted KV cache, as a Pallas TPU kernel:
the ``T`` new rows of one slot attend over the leaf rows the slot has filled
and over themselves, and no score ever leaves VMEM.

The mathematics is ``models/transformer.py::_attend_blocked``'s, operation
for operation: one running softmax (max, sum and accumulator float32) over
blocks of leaf rows up to the fill and then over the chunk's own rows, every
mask by POSITION (leaf row ``r`` holds position ``r`` below the fill, or on a
leaf written round the newest position ``< pos`` that is ``r mod S``; a query
at ``pos + i`` sees the positions ``<= pos + i`` and, on a round leaf, ``>
pos + i - S``), K and V entering the MXU in the dtype they are stored in and
the probabilities never rounded (``decode_attention._exact_dot``).  The jnp
form writes every block's ``(heads, T, block)`` float32 scores to HBM three
times over; here they live in VMEM, so the chunk's time stops following the
context it attends over so closely.

The grid is ``(slot rows, KV heads, query blocks, key blocks)``; the key axis
runs over the leaf's blocks and then the chunk's own.  A leaf block above the
fill, and a new block wholly after the query block, is skipped: its step
does nothing and its block index stays where it was, which costs no copy.
The ``G`` query heads of a KV head are a loop inside the step, each a ``(tq,
head_dim) x (head_dim, tk)`` product against the leaf's own layout (a KV
head's lanes are a column block of the lane-dense leaf).

Taken when the program is lowered for a TPU, for one device, and the shape
fits (:func:`blocks`); the jnp form everywhere else.  ``interpret=True`` runs
the kernel in the Pallas interpreter (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _exact_dot

_NEG_INF = -1e30
_LANES = 128
_VMEM_LIMIT = 64 << 20


def blocks(T: int, S: int, W: int, n_kv_heads: int):
    """``(query rows, key rows)`` of one step for a chunk of ``T`` rows on
    ``(B, S, W)`` leaves, or None where the kernel does not take the shape:
    a KV head must be whole lane tiles wide, and the blocks (256 query rows
    at the most, 512 key rows at the most, 128 at the least) must divide the
    chunk, and the key block the leaf."""
    if W % n_kv_heads or (W // n_kv_heads) % _LANES:
        return None
    tq = next((n for n in (256, 128) if T % n == 0), None)
    tk = next((n for n in (512, 256, 128) if T % n == 0 and S % n == 0), None)
    return None if tq is None or tk is None else (tq, tk)


def _kernel(pos_ref, nb_ref, q_ref, lk_ref, lv_ref, nk_ref, nv_ref, o_ref,
            m_ref, l_ref, acc_ref, *, tq, tk, n_leaf, groups, rows, ring, scale):
    b, qb, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    dh = lk_ref.shape[-1]
    nt = (((1,), (1,)), ((), ()))   # (tq, dh) x (tk, dh) -> (tq, tk)
    nn = (((1,), (0,)), ((), ()))   # (tq, tk) x (tk, dh) -> (tq, dh)

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    first = qb * tq                 # the query block's first row of the chunk
    qpos = pos + first + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    col = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)

    def attend(k_ref, v_ref, kpos):
        """One key block under the running softmax; ``kpos`` (tq, tk): the
        keys' positions, negative where a row holds none."""
        see = (kpos >= 0) & (kpos <= qpos)
        if ring:
            see = see & (kpos > qpos - rows)
        kblk, vblk = k_ref[0], v_ref[0]
        for g in range(groups):
            s = _exact_dot(q_ref[0, :, g * dh:(g + 1) * dh], kblk, nt) * scale
            s = jnp.where(see, s, _NEG_INF)
            m = jnp.maximum(m_ref[g], s.max(axis=1, keepdims=True))
            # a query that has seen nothing yet carries sums its first real
            # maximum wipes: its own row is always seen, and comes last
            a, p = jnp.exp(m_ref[g] - m), jnp.exp(s - m)
            l_ref[g] = a * l_ref[g] + p.sum(axis=1, keepdims=True)
            acc_ref[g] = a * acc_ref[g] + _exact_dot(p, vblk, nn)
            m_ref[g] = m

    @pl.when((kb < n_leaf) & (kb < nb_ref[b]))
    def _():
        row = kb * tk + col
        if ring:  # the newest position < pos that is row mod rows
            kpos = pos - 1 - lax.rem(pos - 1 - row + rows, rows)
        else:
            kpos = jnp.where(row < pos, row, -1)
        attend(lk_ref, lv_ref, kpos)

    @pl.when((kb >= n_leaf) & ((kb - n_leaf) * tk <= first + tq - 1))
    def _():
        attend(nk_ref, nv_ref, pos + (kb - n_leaf) * tk + col)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _():
        for g in range(groups):
            o_ref[0, :, g * dh:(g + 1) * dh] = (
                acc_ref[g] * (1.0 / l_ref[g])).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_heads", "ring", "interpret"))
def chunk_attention(ck, cv, q, k, v, pos, n_heads: int, ring: bool = False,
                    interpret: bool = False):
    """``ck``/``cv`` (B, S, W) leaves as they lie BEFORE the chunk's write;
    ``q`` (B, T, H x Dh), ``k``/``v`` (B, T, W) the chunk's rows; ``pos``
    (B,) the positions the leaves hold; ``ring``: the leaves are written
    round and their ``S`` rows are the window.  Returns the attention ``(B,
    T, H x Dh)`` in ``q``'s dtype.  The shape must be one :func:`blocks`
    takes."""
    B, S, W = ck.shape
    T = q.shape[1]
    Dh = q.shape[-1] // n_heads
    J = W // Dh
    G = n_heads // J
    took = blocks(T, S, W, J)
    if took is None:
        raise ValueError(f"chunk_attention does not take {q.shape} on {ck.shape}")
    tq, tk = took
    n_leaf, n_new = S // tk, T // tk
    fill = jnp.minimum(pos, S).astype(jnp.int32)
    nb = (fill + tk - 1) // tk

    def leaf(b, j, qb, kb, pos, nb):  # above the fill: the block stays put
        return b, jnp.minimum(kb, jnp.maximum(nb[b] - 1, 0)), j

    def new(b, j, qb, kb, pos, nb):   # after the query block: the block stays put
        last = ((qb + 1) * tq - 1) // tk
        return b, jnp.clip(kb - n_leaf, 0, last), j

    def rows_of_q(b, j, qb, kb, pos, nb):
        return b, qb, j

    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, n_leaf=n_leaf, groups=G, rows=S,
                          ring=ring, scale=float(1.0 / np.sqrt(Dh))),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, J, T // tq, n_leaf + n_new),
            in_specs=[
                pl.BlockSpec((1, tq, G * Dh), rows_of_q),
                pl.BlockSpec((1, tk, Dh), leaf), pl.BlockSpec((1, tk, Dh), leaf),
                pl.BlockSpec((1, tk, Dh), new), pl.BlockSpec((1, tk, Dh), new),
            ],
            out_specs=pl.BlockSpec((1, tq, G * Dh), rows_of_q),
            scratch_shapes=[pltpu.VMEM((G, tq, 1), f32), pltpu.VMEM((G, tq, 1), f32),
                            pltpu.VMEM((G, tq, Dh), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="nns_chunk_attention",
    )(pos.astype(jnp.int32), nb, q, ck, cv, k, v)
