"""Decode attention bounded by fill, as a Pallas TPU kernel: the per-token
(``T == 1``) read of a slotted KV cache touches, for every slot, only the
row blocks that slot has filled, and none for a slot that is not decoding.

The cache leaves are read where they lie: ``(B, S, W)`` lane-dense, ``W =
n_kv_heads x head_dim``, in the dtype they are stored in, and they never
leave HBM as a whole: the kernel takes them in HBM and copies row
blocks of :func:`block_rows` rows into a double buffer in VMEM, slot after
slot, ``ceil(n[b] / block)`` blocks for slot ``b`` (``n`` is the scalar
operand: the rows to read, 0 for a slot that is idle).  The copy of the next
block (of the same slot, or the first of the next slot that has any) is
started before the current one is used, so the chain of DMAs does not stop
at a slot's edge.  A slot with nothing to read costs a scalar branch.

The mathematics is ``models/transformer.py::kv_attend_write``'s, operation
for operation: ``q`` laid block-diagonal over the leaf's minor dim (``(Hp,
W)``: row ``h`` holds query head ``h`` in the lanes of its KV head, zero
elsewhere; grouped-query attention is the case of several rows to a lane
block), so ``q_diag x K^T`` gives every head's scores on the MXU against
the leaf's own layout and ``p x V`` every head's mix of every head's
values, of which the head's own lanes are kept (the kernel lays ``q`` out
itself, by a 0/1 product: no ``(B, Hp, W)`` array is made in HBM).  Scores,
mask, running max, exponentials, sums and the accumulator are float32; K
and V enter the MXU in the dtype they are stored in; the probabilities are
never rounded:
against a bf16 leaf a float32 operand goes in as three bf16 terms that sum
to it (what ``Precision.HIGHEST`` does, at one pass over the leaf block
instead of six), against a float32 leaf the product is ``HIGHEST``.  The
new row joins the same softmax inside the kernel, which also finishes the
division.  Rows of a block past ``n[b]`` are masked out of the scores and
zeroed in V, so nothing above a slot's fill reaches its output.

A leaf written ROUND (a window layer's: position ``p`` at row ``p mod S``,
its ``S`` rows the window) is read the same way: ``n[b] = min(pos, S)`` rows,
and one more scalar operand names the row of a full leaf to leave out of the
softmax, the one the new token is about to overwrite (:func:`ring_skip`).  A
softmax does not care in which order its keys come, so nothing else changes;
without that operand the call is the one it was.

Taken when the program is lowered for a TPU (``lax.platform_dependent``,
as ``ops/expert_ffn.py``), compiled for one device, and the shape fits
(:func:`block_rows`); the caller's own jnp form everywhere else
(:func:`fill_bounded` makes the choice for the read and for the counter of
rows read alike).  ``interpret=True`` runs the kernel in the Pallas
interpreter, and :data:`INTERPRET` makes every caller do so (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # as the jnp form: no NaN in exp-diff
_LANES = 128
_ROWS = 16        # query rows are padded to whole bf16 sublane tiles
#: one buffered row block of one leaf (K and V, double buffered, are four),
#: between :data:`_MIN_BLOCK` and :data:`_MAX_BLOCK` rows
_BLOCK_BYTES = 512 << 10
_MIN_BLOCK, _MAX_BLOCK = 128, 512
#: VMEM of the chips the kernel is built for (v5e, v6e), what XLA keeps of
#: it for its own fusions, and the least the call asks for (:func:`_vmem_limit`)
_VMEM_BYTES, _XLA_SCOPED, _MIN_LIMIT = 128 << 20, 16 << 20, 32 << 20
#: tests set this: the kernel is then taken on every platform, interpreted
INTERPRET = False


def block_rows(S: int, W: int, itemsize: int):
    """Rows of one copied block for ``(B, S, W)`` leaves, or None where the
    kernel does not take the shape: ``W`` must be whole lane tiles and the
    block (a power of two, 128 to 512 rows, within :data:`_BLOCK_BYTES`
    where that leaves 128) must divide ``S``.  Small blocks bound a slot's
    read closer to its fill; large ones cost fewer copies (on a v5e a copy
    and its turn of the loop cost what 80 rows of 2.5 KB do)."""
    if W % _LANES or S % _MIN_BLOCK:
        return None
    block = _MIN_BLOCK
    while (block < _MAX_BLOCK and 2 * block * W * itemsize <= _BLOCK_BYTES
           and S % (2 * block) == 0):
        block *= 2
    return block


def _vmem_limit(leaf_bytes: int) -> int:
    """Scoped VMEM the call asks for: far more than it uses (under 4 MB),
    so that no leaf fits in VMEM beside it.  XLA's memory-space assignment
    cannot know that the kernel reads a part of an operand, and where a
    whole leaf fits in what VMEM is free during the call (42 MB of 128 MiB)
    it copies some there first: 8 of the dense cell's 72 leaves, 336 MB a
    step, seen in an ahead-of-time v5e compile.  What the call reserves is
    not free, so the rule is: VMEM, less XLA's own share, less the leaf,
    plus a margin; a leaf too small to be kept out this way costs little."""
    keep_out = _VMEM_BYTES - _XLA_SCOPED - leaf_bytes + (8 << 20)
    return int(min(_VMEM_BYTES - _XLA_SCOPED, max(_MIN_LIMIT, keep_out)))


def live_rows(pos, active, S: int):
    """The rows each slot's step reads, ``n`` (B,) int32: the ``pos[b]``
    it has filled (at most ``S``), none where ``active[b] == 0`` (``active``
    None: every row is live)."""
    n = jnp.minimum(pos, S).astype(jnp.int32)
    return n if active is None else jnp.where(active > 0, n, 0)


def ring_skip(pos, S: int):
    """On a leaf written round (position ``p`` at row ``p mod S``; its ``S``
    rows are the window, the query's own position counted): the row a full
    leaf's step must not see, ``(B,)`` int32.  Row ``pos mod S`` holds
    position ``pos - S``, one past the window, and is the row the new token
    will overwrite; ``-1`` while the leaf is not full."""
    return jnp.where(pos >= S, pos % S, -1).astype(jnp.int32)


def fill_bounded(kernel, default, *operands, leaf=None, takes=None,
                 single_device: bool = True):
    """``kernel(*operands)`` where a fill-bounded kernel is taken,
    ``default(*operands)`` everywhere else: the one place the choice is
    made, by what the code can see.  Taken when the program is lowered for
    a TPU, compiled for one device (a Mosaic call cannot sit in a program
    partitioned over a mesh) and the shape is one the kernel takes: the
    ``leaf`` (a ``(B, S, W)`` array or shape struct) one :func:`block_rows`
    takes, or ``takes`` as another kernel's own rule says
    (``chunk_attention.blocks``)."""
    if takes is None:
        _, S, W = leaf.shape
        takes = block_rows(S, W, leaf.dtype.itemsize) is not None
    if not takes:
        return default(*operands)
    if INTERPRET:
        return kernel(*operands)
    if not single_device:
        return default(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=default)


def rows_read(n, leaf, single_device: bool = True):
    """Cache rows one step's reads cover in ONE leaf, of the ``B x S`` it
    holds: whole blocks up to each slot's ``n`` where the kernel is taken,
    every row where it is not."""
    B, S, W = leaf.shape
    block = block_rows(S, W, leaf.dtype.itemsize)
    return fill_bounded(
        lambda n: jnp.sum((n + block - 1) // block * block),
        lambda n: jnp.int32(B * S), n, leaf=leaf, single_device=single_device)


def _exact_dot(a, b, dims):
    """``a`` (rows, ·) float32 or bf16 against a leaf block ``b`` in its
    own dtype, float32 out, no operand rounded."""
    if b.dtype != jnp.bfloat16:
        return lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dims,
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    if a.dtype == jnp.bfloat16:
        return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    rows, terms, rest = a.shape[0], [], a
    for _ in range(3):  # three bf16 terms hold a float32's 24 bits
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    out = lax.dot_general(jnp.concatenate(terms, axis=0), b, dims,
                          preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _kernel(n_ref, *refs, block: int, groups: int, scale: float, ring: bool):
    """All slots in one program.  ``n_ref`` (B,) SMEM; with ``ring`` a
    second (B,) SMEM operand follows it, the row of each slot that is masked
    out of the scores (:func:`ring_skip`; -1: none); ``own_ref`` (Hp, W)
    int32: ``g + 1`` where row ``h = j * groups + g`` owns the lane (the
    lanes of KV head ``j``), else 0; ``lay_ref`` (Dh, W) 0/1: lane ``w``
    takes element ``w % Dh`` of a head; ``q_ref`` (B, Hp, Dh) the queries by
    head; ``new_ref`` (B, 1, 2W) the new K row beside the new V row;
    ``k_hbm``/``v_hbm`` the leaves; ``o_ref`` (B, groups, W)."""
    skip_ref, refs = (refs[0], refs[1:]) if ring else (None, refs)
    (own_ref, lay_ref, q_ref, new_ref, k_hbm, v_hbm, o_ref,
     kbuf, vbuf, sem, nxt_ref, m_ref, l_ref, acc_ref) = refs
    B, W = q_ref.shape[0], own_ref.shape[1]
    nt = (((1,), (1,)), ((), ()))   # (rows, W) x (block, W) -> (rows, block)
    nn = (((1,), (0,)), ((), ()))   # (rows, block) x (block, W) -> (rows, W)

    def find_next(i, nxt):  # the next slot after b that reads anything
        b = B - 1 - i
        nxt_ref[b] = nxt
        return jnp.where(n_ref[b] > 0, b, nxt)

    first = lax.fori_loop(0, B, find_next, B)

    def copies(b, i, buf):
        rows = pl.ds(pl.multiple_of(i * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[b, rows], kbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[b, rows], vbuf.at[buf], sem.at[1, buf]))

    @pl.when(first < B)
    def _():
        for c in copies(first, 0, 0):
            c.start()

    def slot(b, item):  # item: blocks copied so far; its parity is the buffer
        n = n_ref[b]
        nb = (n + block - 1) // block

        @pl.when(n == 0)
        def _():  # the new row alone: its own value, for every head
            o_ref[b] = jnp.broadcast_to(
                new_ref[b, :, W:], o_ref.shape[1:]).astype(o_ref.dtype)

        @pl.when(n > 0)
        def _():
            own = own_ref[...]
            # block-diagonal q: head h laid into the lanes of its KV head
            qd = jnp.where(own > 0, _exact_dot(q_ref[b], lay_ref[...], nn),
                           0.0).astype(q_ref.dtype)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def one_block(i, carry):
                buf = (item + i) % 2
                more = i + 1 < nb
                nb_b = jnp.where(more, b, nxt_ref[b])

                @pl.when(nb_b < B)
                def _():
                    for c in copies(nb_b, jnp.where(more, i + 1, 0), 1 - buf):
                        c.start()

                k_copy, v_copy = copies(b, i, buf)
                k_copy.wait()
                s = _exact_dot(qd, kbuf[buf], nt) * scale  # (Hp, block)
                col = i * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                seen = col < n
                if ring:  # the row the new token will overwrite
                    seen = seen & (col != skip_ref[b])
                s = jnp.where(seen, s, _NEG_INF)
                m = jnp.maximum(m_ref[...], s.max(axis=1, keepdims=True))
                a = jnp.exp(m_ref[...] - m)
                p = jnp.exp(s - m)
                l_ref[...] = a * l_ref[...] + p.sum(axis=1, keepdims=True)
                m_ref[...] = m
                v_copy.wait()

                def below_fill(v):  # the slot's last block: rows past n are not its own
                    row = i * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
                    return jnp.where(row < n, v, jnp.zeros_like(v))

                v = lax.cond(more, lambda v: v, below_fill, vbuf[buf])
                acc_ref[...] = a * acc_ref[...] + _exact_dot(p, v, nn)
                return carry

            lax.fori_loop(0, nb, one_block, 0)
            # the new row joins the same softmax; then the division
            k_new = new_ref[b, :, :W].astype(jnp.float32)
            v_new = new_ref[b, :, W:].astype(jnp.float32)
            s_new = jnp.sum(qd.astype(jnp.float32) * k_new, axis=1,
                            keepdims=True) * scale
            m = jnp.maximum(m_ref[...], s_new)
            a, p = jnp.exp(m_ref[...] - m), jnp.exp(s_new - m)
            mix = a * acc_ref[...] + p * v_new
            mix = mix * (1.0 / (a * l_ref[...] + p))
            for g in range(groups):  # a head keeps the lanes of its KV head
                o_ref[b, pl.ds(g, 1), :] = jnp.sum(
                    jnp.where(own == g + 1, mix, 0.0), axis=0, keepdims=True,
                ).astype(o_ref.dtype)

        return item + nb

    lax.fori_loop(0, B, slot, 0)


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret"))
def decode_attention(ck, cv, q, k, v, n, n_heads: int, interpret: bool = False,
                     skip=None):
    """``ck``/``cv`` (B, S, W) leaves; ``q`` (B, 1, H x Dh), ``k``/``v`` (B,
    1, W) the step's new rows; ``n`` (B,) int32 the cache rows each slot
    reads (``<= S``; 0: none); ``skip`` (B,) int32, for a leaf written round:
    one row of each slot left out of the softmax (:func:`ring_skip`), None
    for a leaf written by position (the call is then the one it was,
    operand for operand).  Returns the attention over those rows and the
    new row, ``(B, 1, H x Dh)`` in ``q``'s dtype.  The shape must be one
    :func:`block_rows` takes."""
    B, S, W = ck.shape
    H = n_heads
    Dh = q.shape[-1] // H
    J = W // Dh
    G = H // J
    block = block_rows(S, W, ck.dtype.itemsize)
    if block is None:
        raise ValueError(f"decode_attention does not take leaves {ck.shape}")
    Hp = -(-H // _ROWS) * _ROWS
    ring = skip is not None
    # own[h, w] = g + 1 where lane w lies in the KV head of row h = j*G + g
    head, lane = np.arange(Hp)[:, None], np.arange(W)[None, :]
    own = np.where((head < H) & (head // G == lane // Dh), head % G + 1, 0)
    lay = np.arange(Dh)[:, None] == lane % Dh
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, groups=G,
                          scale=float(1.0 / np.sqrt(Dh)), ring=ring),
        out_shape=jax.ShapeDtypeStruct((B, G, W), q.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * (1 + ring)
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 4
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, block, W), ck.dtype),
            pltpu.VMEM((2, block, W), cv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((B,), jnp.int32),
            pltpu.VMEM((Hp, 1), f32),
            pltpu.VMEM((Hp, 1), f32),
            pltpu.VMEM((Hp, W), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(B * S * W * ck.dtype.itemsize)),
        interpret=interpret,
        name="nns_decode_attention",
    )(n.astype(jnp.int32), *([skip.astype(jnp.int32)] if ring else []),
      jnp.asarray(own, jnp.int32),
      jnp.asarray(lay, jnp.bfloat16),
      jnp.pad(q.reshape(B, H, Dh), ((0, 0), (0, Hp - H), (0, 0))),
      jnp.concatenate([k, v], axis=-1), ck, cv)
    # (B, G, J, Dh) -> heads in order h = j*G + g
    out = jnp.swapaxes(out.reshape(B, G, J, Dh), 1, 2)
    return out.reshape(B, 1, H * Dh)
