"""Decoder-only transformer LM with first-class mesh parallelism.

The long-context / distributed flagship for the parallel subsystem
(SURVEY §5.7-5.8 mark these "absent / net-new" in the reference): a GPT
style LM whose attention runs as ring attention when the sequence axis is
sharded (``sp``), with tensor-parallel params (``tp``) and data-parallel
batch (``dp``) — all via NamedSharding + GSPMD, collectives inserted by XLA
except the explicit ring ppermute.

Provides the zoo ``build`` (inference) and :func:`make_train_step` (the
sharded training step used by ``__graft_entry__.dryrun_multichip`` and the
trainer element).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.types import FORMAT_STATIC, StreamSpec, TensorSpec
from ._init_util import host_init
from ..ops import chunk_attention, decode_attention
from ..parallel.ring_attention import reference_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 256
    dtype: Any = jnp.bfloat16
    # sequence-parallel attention strategy when the mesh has sp > 1:
    # auto (ulysses when heads divide sp, else ring) | ring | ulysses
    sp_strategy: str = "auto"
    # single-device attention kernel: xla (fused reference) | flash
    # (Pallas online-softmax kernel, ops/flash_attention.py)
    attn_impl: str = "xla"
    # int8 MXU dense layers (_quant_flax.QuantDense; quantize:int8 prop)
    quant: bool = False


#: a prefill chunk whose float32 scores over the whole leaf would pass this
#: many bytes attends in row blocks bounded by the slot's fill
#: (:func:`_attend_blocked`); so does every chunk on a leaf written round
_SCORES_BYTES = 256 << 20


def kv_attend_write(ck, cv, q, k, v, pos, n_heads, n_kv_heads=None,
                    active=None, single_device=True, ring=False):
    """The ONE decode-cache step every generation path shares: attend
    over the cache leaves as they lie plus the new rows, then write the
    new rows into the leaves.

    ``ck``/``cv`` are lane-dense ``(B, max_seq, d_model)`` leaves (heads
    contiguous in the minor dim, so a row is whole 128-lane tiles and the
    device layout is the logical one); ``q``/``k``/``v`` are ``(B, T,
    d_model)`` in the cache dtype; ``pos`` is ``(B,)``: row ``b`` holds
    ``pos[b]`` older positions, its new rows are positions ``pos[b] ..
    pos[b]+T-1``, and query ``i`` sees the older ones and new rows
    ``<= i``.  Returns ``(ck, cv, attn (B, T, d_model))``.

    ``n_kv_heads`` (grouped-query attention): the leaves, ``k`` and ``v``
    are ``n_kv_heads x head_dim`` wide, read from the leaves' own minor
    dim, and the ``n_heads / n_kv_heads`` queries of one group share a KV
    head (:func:`_gqa_scores`; no KV row is repeated).  Left out (or
    equal to ``n_heads``) the function is the multi-head one it was,
    operation for operation (:func:`_mha_scores`).

    Reading the leaves BEFORE the write is what keeps the step to one
    read of K and of V: the leaf the contraction reads is the loop's
    own carry, the write is an in-place row scatter nothing downstream
    reads, and no whole-leaf buffer has to exist beside it.  (Written
    first and read second, XLA:TPU stages each leaf through VMEM and
    copies all of it back.)  The write is ``mode="drop"``: a row at or
    past ``max_seq`` writes nothing, where a clamping
    ``dynamic_update_slice`` would overwrite the last rows.

    Scores, mask, softmax and accumulation are float32; K and V are read
    in the dtype they are stored in and the probabilities are never
    rounded.  The softmax is taken over both parts at once: one shared
    max, exponentials summed over cache and new rows, one division after
    the value contraction.

    ``T == 1`` (the per-token step) contracts on the MXU against the
    leaf's own layout: ``q`` is laid block-diagonal over ``d_model`` so
    ``(S, D) x (D, H)`` gives every head's scores, and ``e (H, S) x
    (S, D)`` gives every head's mix of every head's values, of which the
    head diagonal is kept.  The idle products cost H x the MACs on a
    unit the step barely uses; what they buy is that no transposed or
    float32 copy of a leaf is ever made.  ``T > 1`` (prefill) splits the
    rows it reads into heads, which copies them once: small beside the
    chunk's matmuls, and without the H x.

    **The per-token read is bounded by fill** where it can be: for ``T ==
    1`` the rows to read are ``n[b] = min(pos[b], max_seq)`` for a live row
    and 0 for one with ``active[b] == 0`` (``active`` (B,), None: every row
    is live; such a row attends to its new row alone, and nothing reads its
    output), and ``ops/decode_attention.py`` (device operations
    ``nns_decode_attention``) copies ``ceil(n[b] / block)`` row blocks of
    each leaf and no more, the same mathematics in one kernel.  It is taken
    when the program is lowered for a TPU (``lax.platform_dependent``: the
    choice follows the device the program is compiled for), compiled for
    one device (``single_device=False``: the caller compiles for a mesh,
    where a Mosaic call cannot be partitioned) and the leaves are whole
    lane tiles wide with ``max_seq`` a multiple of the block
    (``decode_attention.block_rows``).  Anything else, and ``T > 1`` (a
    prefill chunk reads one slot's rows), is the jnp form below on every
    platform; nothing declines the kernel quietly and no property chooses.

    ``ring``: the leaves are written ROUND and their ``S`` rows are the
    layer's window (the query's own position counted): position ``p`` lies at
    row ``p mod S``, a query at position ``p`` sees positions ``p-S+1 .. p``,
    and every mask is by POSITION, not by row index.  The per-token step
    reads ``min(pos, S)`` rows and leaves out the one row of a full leaf that
    holds position ``p - S`` (the row the new token overwrites:
    ``decode_attention.ring_skip``); a chunk must have ``T <= S`` rows, so
    that the read before the write still finds every position it may see;
    a row with ``active[b] == 0`` writes nothing.

    **A long prefill chunk is bounded by fill too.**  Where the ``(B, H, T,
    S + T)`` float32 scores of ``T > 1`` would pass :data:`_SCORES_BYTES`,
    and on every round leaf, the chunk attends in blocks of leaf rows up to
    the fill (a loop whose trip count is the data's), then over its own rows
    in blocks, one running softmax over all of them
    (:func:`_attend_blocked`): no array grows with ``S``, the same on every
    platform; lowered for one TPU at a shape it takes, the same mathematics
    is ``ops/chunk_attention.py`` (device operations ``nns_chunk_attention``),
    whose scores never leave VMEM.  Smaller chunks keep the whole-leaf form
    they had, operation for operation.
    """
    B, T, D = q.shape
    S = ck.shape[1]
    H, Dh = n_heads, D // n_heads
    dot = functools.partial(
        jnp.einsum, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    scale = 1.0 / np.sqrt(Dh)

    def attend(ck, cv, q, k, v, pos):
        if (n_kv_heads or H) != H:
            s_old, mix_old, s_new, mix_new = _gqa_scores(
                ck, cv, q, k, v, H, n_kv_heads, dot)
        else:
            s_old, mix_old, s_new, mix_new = _mha_scores(ck, cv, q, k, v, H, dot)
        older = jnp.arange(S)[None, :] < pos[:, None]  # (B, S)
        if ring:
            older &= jnp.arange(S)[None, :] != decode_attention.ring_skip(pos, S)[:, None]
        s_old = jnp.where(older[:, None, None], s_old * scale, -1e30)
        s_new = jnp.where(jnp.tri(T, dtype=bool), s_new * scale, -1e30)
        top = jnp.maximum(s_old.max(axis=-1), s_new.max(axis=-1))[..., None]
        e_old, e_new = jnp.exp(s_old - top), jnp.exp(s_new - top)
        total = e_old.sum(axis=-1) + e_new.sum(axis=-1)  # (B, H, T)
        mix = mix_old(e_old) + mix_new(e_new)
        attn = jnp.moveaxis(mix / total[..., None], 1, 2)  # (B, T, H, Dh)
        return attn.reshape(B, T, D).astype(q.dtype)

    if T == 1:
        def bounded(ck, cv, q, k, v, pos):
            return decode_attention.decode_attention(
                ck, cv, q, k, v, decode_attention.live_rows(pos, active, S),
                n_heads=H, interpret=decode_attention.INTERPRET,
                skip=decode_attention.ring_skip(pos, S) if ring else None)

        attn = decode_attention.fill_bounded(
            bounded, attend, ck, cv, q, k, v, pos,
            leaf=ck, single_device=single_device)
    elif ring or 4 * B * H * T * (S + T) > _SCORES_BYTES:
        J = n_kv_heads or H

        def in_vmem(ck, cv, q, k, v, pos):
            return chunk_attention.chunk_attention(
                ck, cv, q, k, v, pos, n_heads=H, ring=ring,
                interpret=decode_attention.INTERPRET)

        attn = decode_attention.fill_bounded(
            in_vmem, functools.partial(_attend_blocked, H=H, J=J, ring=ring),
            ck, cv, q, k, v, pos, single_device=single_device,
            takes=chunk_attention.blocks(T, S, ck.shape[2], J) is not None)
    else:
        attn = attend(ck, cv, q, k, v, pos)

    slot = jnp.arange(B)[:, None]
    rows = pos[:, None] + jnp.arange(T)[None, :]  # (B, T)
    if ring:
        if T > S:
            raise ValueError(f"a chunk of {T} rows on a round leaf of {S}")
        rows = rows % S
        if active is not None:  # an idle row's leaf comes out bit-equal
            rows = jnp.where(active[:, None] > 0, rows, S)

    def write(c, new):
        return c.at[slot, rows].set(
            new.astype(c.dtype), mode="drop", indices_are_sorted=not ring,
            unique_indices=True,
        )

    return write(ck, k), write(cv, v), attn


def _attend_blocked(ck, cv, q, k, v, pos, H, J, ring):
    """A prefill chunk's attention in blocks, bounded by fill and window:
    ``ceil(min(pos, S) / block)`` blocks of leaf rows (the loop's trip count
    follows the data: rows above the fill are never read), then the chunk's
    own rows in blocks, all under one running softmax (max, sum and
    accumulator float32; K and V read in the dtype they are stored in; the
    probabilities never rounded).  Every mask is by position: leaf row ``r``
    holds position ``r`` below the fill, or on a round leaf the newest
    position ``< pos`` that is ``r mod S``; a query at ``pos + i`` sees the
    positions ``<= pos + i`` and, on a round leaf, ``> pos + i - S``.  Peak
    temporaries are a few ``(B, H, T, block)`` float32 arrays, whatever
    ``S``."""
    B, T, D = q.shape
    S = ck.shape[1]
    Dh, G = D // H, H // J
    dot = functools.partial(
        jnp.einsum, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    scale = 1.0 / np.sqrt(Dh)
    # a block's scores are (B, H, T, block) float32: 128 MB at the most
    block = int(np.clip((128 << 20) // (4 * B * H * T), 128, 512))
    block = 1 << (block.bit_length() - 1)
    q5 = q.reshape(B, T, J, G, Dh)
    qpos = pos[:, None] + jnp.arange(T)[None, :]  # (B, T)

    def one(carry, kb, vb, kpos):
        """``kb``/``vb`` (B, n, J x Dh), ``kpos`` (B, n): the rows'
        positions, negative where a row holds none."""
        m, l, acc = carry
        n = kb.shape[1]
        see = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= qpos[:, :, None])
        if ring:
            see &= kpos[:, None, :] > qpos[:, :, None] - S
        s = dot("btjgd,bsjd->bjgts", q5, kb.reshape(B, n, J, Dh)) * scale
        s = jnp.where(see[:, None, None], s, -1e30)
        top = jnp.maximum(m, s.max(axis=-1))
        # a query that has seen nothing yet carries sums its first real
        # maximum wipes (exp(-1e30 - top) == 0): its own row is always seen
        a, e = jnp.exp(m - top), jnp.exp(s - top[..., None])
        acc = a[..., None] * acc + dot("bjgts,bsjd->bjgtd", e, vb.reshape(B, n, J, Dh))
        return top, a * l + e.sum(axis=-1), acc

    carry = (jnp.full((B, J, G, T), -1e30, jnp.float32),
             jnp.zeros((B, J, G, T), jnp.float32),
             jnp.zeros((B, J, G, T, Dh), jnp.float32))
    n = min(block, S)
    fill = jnp.minimum(pos, S)

    def old_block(i, carry):
        start = jnp.minimum(i * n, S - n)  # the last block of a ragged leaf overlaps
        rows = start + jnp.arange(n)[None, :]  # (1, n)
        if ring:
            newest = pos[:, None] - 1
            kpos = newest - (newest - rows) % S
        else:
            kpos = jnp.where(rows < pos[:, None], rows, -1)
        kpos = jnp.where((rows >= i * n) & (rows < fill[:, None]), kpos, -1)
        kb, vb = (jax.lax.dynamic_slice_in_dim(c, start, n, axis=1) for c in (ck, cv))
        return one(carry, kb, vb, kpos)

    carry = jax.lax.fori_loop(0, (jnp.max(fill) + n - 1) // n, old_block, carry)
    for a in range(0, T, block):
        carry = one(carry, k[:, a:a + block], v[:, a:a + block], qpos[:, a:a + block])
    _, l, acc = carry
    attn = jnp.moveaxis(acc / l[..., None], 3, 1)  # (B, T, J, G, Dh)
    return attn.reshape(B, T, D).astype(q.dtype)


def _mha_scores(ck, cv, q, k, v, H, dot):
    """Multi-head scores and mixes of :func:`kv_attend_write` (every query
    head has a KV head of its own): ``(s_old (B, H, T, S), mix_old, s_new
    (B, H, T, T), mix_new)``, unscaled and unmasked."""
    B, T, D = q.shape
    S, Dh = ck.shape[1], D // H
    q4, k4, v4 = (t.reshape(B, T, H, Dh) for t in (q, k, v))
    if T == 1:
        own = jnp.eye(H, dtype=bool)
        q_diag = jnp.where(
            own[None, :, None, :], q.reshape(B, H, Dh, 1), 0
        ).reshape(B, D, H)
        s_old = dot("bsd,bdh->bhs", ck, q_diag)[:, :, None]

        def mix_old(e):  # (B, H, 1, S) -> (B, H, 1, Dh)
            every = dot("bhs,bsd->bhd", e[:, :, 0], cv).reshape(B, H, H, Dh)
            return jnp.where(own[None, :, :, None], every, 0).sum(axis=1)[:, :, None]
    else:
        s_old = dot("bthd,bshd->bhts", q4, ck.reshape(B, S, H, Dh))

        def mix_old(e):  # (B, H, T, S) -> (B, H, T, Dh)
            return dot("bhts,bshd->bhtd", e, cv.reshape(B, S, H, Dh))

    def mix_new(e):  # (B, H, T, T) -> (B, H, T, Dh)
        return dot("bhtu,buhd->bhtd", e, v4)

    return s_old, mix_old, dot("bthd,buhd->bhtu", q4, k4), mix_new


def _gqa_scores(ck, cv, q, k, v, H, J, dot):
    """Grouped-query twin of :func:`_mha_scores`: ``H`` query heads share
    ``J`` KV heads, ``G = H / J`` to a group.  ``T == 1`` lays ``q``
    block-diagonal over the leaf's own ``J x head_dim`` minor dim (idle
    products J x the MACs, no copy of a leaf); ``T > 1`` makes the group a
    batch dimension of the contractions."""
    B, T, D = q.shape
    S, Dh, G = ck.shape[1], D // H, H // J
    q5 = q.reshape(B, T, J, G, Dh)
    k4, v4 = (t.reshape(B, T, J, Dh) for t in (k, v))
    if T == 1:
        own = jnp.arange(H)[:, None] // G == jnp.arange(J)[None, :]  # (H, J)
        q_diag = jnp.where(
            own.T[None, :, None, :],
            jnp.swapaxes(q.reshape(B, H, Dh), 1, 2)[:, None], 0,
        ).reshape(B, J * Dh, H)
        s_old = dot("bsd,bdh->bhs", ck, q_diag)[:, :, None]

        def mix_old(e):  # (B, H, 1, S) -> (B, H, 1, Dh)
            every = dot("bhs,bsd->bhd", e[:, :, 0], cv).reshape(B, H, J, Dh)
            return jnp.where(own[None, :, :, None], every, 0).sum(axis=2)[:, :, None]
    else:
        ck4, cv4 = (t.reshape(B, S, J, Dh) for t in (ck, cv))
        s_old = dot("btjgd,bsjd->bjgts", q5, ck4).reshape(B, H, T, S)

        def mix_old(e):  # (B, H, T, S) -> (B, H, T, Dh)
            return dot("bjgts,bsjd->bjgtd", e.reshape(B, J, G, T, S),
                       cv4).reshape(B, H, T, Dh)

    def mix_new(e):  # (B, H, T, T) -> (B, H, T, Dh)
        return dot("bjgtu,bujd->bjgtd", e.reshape(B, J, G, T, T),
                   v4).reshape(B, H, T, Dh)

    s_new = dot("btjgd,bujd->bjgtu", q5, k4).reshape(B, H, T, T)
    return s_old, mix_old, s_new, mix_new


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    seq_axis: str = "sp"
    # KV-cache step (generation serving): T == 1 is the per-token decode
    # step, T > 1 is chunked PREFILL (the chunk attends causally in one
    # pass while filling the cache).  The cache is a static-shape pair of
    # (B, max_seq, d_model) leaves, so the generate loop is one compiled
    # program with no growing shapes; kv_attend_write is its one read
    # and its one write.
    decode: bool = False
    # continuous batching (core/slots.py): the batch rows are SLOTS, each
    # with its own write position instead of one shared scalar, so
    # independent generation streams at different depths share one batch.
    # A step writes one row block per slot in place (a joining stream
    # touches only its slot; a leaving stream's pages are reusable
    # without touching neighbors) and the causal mask is per slot, so the
    # jitted step stays shape-stable as streams churn.
    slotted: bool = False
    # False: compiled for a mesh, so the per-token read keeps the jnp form
    # (kv_attend_write); whoever compiles the program says so
    single_device: bool = True

    def _dense(self, features, name):
        from ._quant_flax import dense_or_quant

        # same explicit name -> same param path/RNG fold either way
        return dense_or_quant(self.cfg.quant, features, self.cfg.dtype, name)

    @nn.compact
    def __call__(self, x, active=None):
        cfg = self.cfg
        B, T, D = x.shape
        H = cfg.n_heads
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        qkv = self._dense(3 * D, "attn_qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        if self.decode:
            def pages():
                return jnp.zeros((B, cfg.max_seq, D), cfg.dtype)

            ck = self.variable("cache", "key", pages)
            cv = self.variable("cache", "value", pages)
            # slotted: one write position per slot; unslotted: one shared
            # scalar, broadcast, so both run the same program per row and
            # a single occupant's slotted row is bit-identical to the
            # unslotted path (row independence; pinned in tests)
            idx = self.variable(
                "cache", "index",
                lambda: jnp.zeros((B,) if self.slotted else (), jnp.int32),
            )
            pos = idx.value
            ck.value, cv.value, attn = kv_attend_write(
                ck.value, cv.value, q, k, v,
                jnp.broadcast_to(pos, (B,)), H,
                active=active, single_device=self.single_device,
            )
            # idle slots (active=0) keep writing harmlessly into their
            # frozen position but never advance
            adv = T if active is None else T * active.astype(jnp.int32)
            idx.value = pos + adv
        else:
            q, k, v = (t.reshape(B, T, H, D // H) for t in (q, k, v))
            if self.mesh is not None and self.mesh.shape.get(self.seq_axis, 1) > 1:
                from ..parallel.ulysses import sequence_attention

                attn = sequence_attention(
                    q, k, v, self.mesh, seq_axis=self.seq_axis, causal=True,
                    strategy=cfg.sp_strategy,
                )
            elif cfg.attn_impl == "flash":
                from ..ops.flash_attention import flash_attention_grad

                # differentiable wrapper: kernel forward, recompute backward
                attn = flash_attention_grad(q, k, v, True)
            else:
                attn = reference_attention(q, k, v, causal=True)
            attn = attn.reshape(B, T, D)
        x = x + self._dense(D, "attn_out")(attn)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        h = self._dense(cfg.d_ff, "mlp_up")(h)
        h = jax.nn.gelu(h)
        x = x + self._dense(D, "mlp_down")(h)
        return x


class TransformerLM(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    seq_axis: str = "sp"
    decode: bool = False
    slotted: bool = False  # per-slot cache positions (continuous batching)
    single_device: bool = True  # False: compiled for a mesh (see Block)

    @nn.compact
    def __call__(self, tokens, active=None):  # (B, T) int32
        cfg = self.cfg
        x = nn.Embed(cfg.vocab, cfg.d_model, dtype=cfg.dtype, name="embed")(tokens)
        B, T = tokens.shape
        if self.decode and self.slotted:
            # per-slot position counter: each stream advances its own
            # step; idle slots (active=0) stay frozen
            step = self.variable(
                "cache", "step", lambda: jnp.zeros((B,), jnp.int32)
            )
            positions = step.value[:, None] + jnp.arange(T)[None, :]
            adv = T if active is None else T * active.astype(jnp.int32)
            step.value = step.value + adv
        elif self.decode:
            step = self.variable(
                "cache", "step", lambda: jnp.zeros((), jnp.int32)
            )
            positions = step.value + jnp.arange(T)[None, :]
            step.value = step.value + T
        else:
            positions = jnp.arange(T)[None, :]
        pos = nn.Embed(cfg.max_seq, cfg.d_model, dtype=cfg.dtype, name="pos_embed")(
            positions
        )
        x = x + pos
        for i in range(cfg.n_layers):
            x = Block(
                cfg, self.mesh, self.seq_axis, decode=self.decode,
                slotted=self.slotted, single_device=self.single_device,
                name=f"block{i}",
            )(x, active)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab, use_bias=False, dtype=jnp.float32, name="lm_head")(
            x.astype(jnp.float32)
        )
        return logits


def _cfg_from_props(props: Dict[str, str]) -> TransformerConfig:
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        props.get("dtype", "bfloat16")
    ]
    return TransformerConfig(
        vocab=int(props.get("vocab", "256")),
        d_model=int(props.get("d_model", "128")),
        n_heads=int(props.get("heads", "4")),
        n_layers=int(props.get("layers", "2")),
        d_ff=int(props.get("d_ff", "512")),
        max_seq=int(props.get("seq", "256")),
        dtype=dt,
        sp_strategy=props.get("sp_strategy", "auto"),
        attn_impl=props.get("attn", "xla"),
        quant=props.get("quantize", "") == "int8",
    )


def config_resume_fields(cfg, props: Dict[str, str]) -> Dict[str, Any]:
    """Everything that determines the token sequence, for any family:
    EVERY field of its config dataclass and the seeds and sampling rule
    (mesh, device and slot width shape placement and latency, never
    tokens)."""
    fields = dataclasses.asdict(cfg)
    fields["dtype"] = jnp.dtype(fields["dtype"]).name
    for key in ("seed", "gen_seed", "temperature", "top_k"):
        fields["sampling_" + key] = props.get(key, "0")
    return fields


def resume_fields(props: Dict[str, str]) -> Dict[str, Any]:
    return config_resume_fields(_cfg_from_props(props), props)


def make_generate(
    cfg: TransformerConfig,
    max_new: int,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
    single_device: bool = True,
):
    """KV-cache generation: ``gen(params, prompt (B,Tp)) ->
    (B, Tp+max_new)``.  ``single_device=False``: the program is compiled
    for a mesh (:func:`kv_attend_write` then keeps its jnp form).

    ``temperature=0`` (default) is greedy argmax decoding;
    ``temperature>0`` samples from softmax(logits/temperature),
    optionally truncated to the ``top_k`` highest-probability tokens —
    deterministic for a given ``seed`` (the key is folded per step and
    per batch row).

    Expressed ON TOP of :func:`make_stream_generate`'s halves — chunked
    PREFILL (one causal pass fills the K/V cache) + ONE decode_chunk
    scan over the remaining tokens — so the one-shot and streaming paths
    share a single implementation and stay bit-equal by construction.
    The backend jit-compiles one XLA program per (B, Tp) bucket; no
    per-token Python dispatch, no growing shapes.  The serving analog of
    the reference's recurrence emulation (``tests/nnstreamer_repo_lstm``
    loops frames through tensor_repo); here the loop lives inside the
    compiled program.
    """
    prefill, decode_chunk = make_stream_generate(
        cfg, temperature=temperature, top_k=top_k, seed=seed,
        single_device=single_device,
    )

    def gen(params, prompt):  # (B, Tp) int32
        B, Tp = prompt.shape
        if Tp + max_new > cfg.max_seq:
            raise ValueError(
                f"prompt {Tp} + generate {max_new} exceeds max_seq "
                f"{cfg.max_seq}"
            )
        cache, first = prefill(params, prompt)
        if max_new <= 1:
            generated = first[:, None]
        else:
            _, _, rest = decode_chunk(params, cache, first, 1, max_new - 1)
            generated = jnp.concatenate([first[:, None], rest], axis=1)
        return jnp.concatenate([prompt, generated], axis=1)

    return gen


def _make_pick(temperature: float, top_k: int):
    """The ONE sampling rule every generation path shares (one-shot,
    streaming, slotted): greedy argmax at ``temperature<=0``, else
    softmax(logits/temperature) truncated to ``top_k``.  Factored out so
    the slotted per-slot picker provably applies the same math per row."""

    def pick(logits, key):  # (B, V) -> (B,)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            kth = jax.lax.top_k(scaled, min(top_k, scaled.shape[-1]))[0][
                :, -1:
            ]
            scaled = jnp.where(scaled >= kth, scaled, -1e30)
        return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)

    return pick


def pick_slots(pick, key0, temperature, lg, gen):
    """The slotted pick every slot model shares: ``lg`` (S, V), ``gen``
    (S,) -> (S,).  Greedy at ``temperature <= 0``; else a per-slot key
    folded at the slot's OWN generated count — the same fold the
    unslotted scan applies at global step t (vmap of a key-batched draw
    is bit-equal to the per-row loop)."""
    if temperature <= 0.0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)
    keys = jax.vmap(lambda g: jax.random.fold_in(key0, g))(gen)
    keys = jnp.where((gen == 0)[:, None], key0[None], keys)

    def one(l, k):  # (V,), key -> ()
        return pick(l[None], k)[0]

    return jax.vmap(one)(lg, keys).astype(jnp.int32)


def make_stream_generate(
    cfg: TransformerConfig,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
    single_device: bool = True,
):
    """Chunked KV-cache decoding for STREAMING serving: unlike
    :func:`make_generate` (whole completion in one traced program), this
    returns two jittable halves whose cache pytree is carried BETWEEN
    calls by the caller, so tokens can leave the pipeline while later
    chunks are still decoding:

    * ``prefill(params, prompt (B,Tp)) -> (cache, first_tok (B,))`` —
      one causal pass fills the cache and picks token 1;
    * ``decode_chunk(params, cache, tok, t0, n) -> (cache, last_tok,
      toks (B, n))`` — n more tokens via one ``lax.scan`` (compile
      buckets: one per distinct n; callers use a fixed chunk + one tail).

    ``elements/generator.py`` streams these through a pipeline.  Sampling
    semantics (greedy / temperature / top-k, per-step key folding) are
    IDENTICAL to make_generate — the streamed token sequence is
    bit-equal to the one-shot path for the same seed.
    """
    model_dec = TransformerLM(cfg, decode=True, single_device=single_device)
    pick = _make_pick(temperature, top_k)
    key0 = jax.random.PRNGKey(seed)

    def prefill(params, prompt):
        B, Tp = prompt.shape
        cache_shapes = jax.eval_shape(
            lambda: model_dec.init(
                jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32)
            )["cache"]
        )
        cache0 = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes
        )
        logits_p, upd = model_dec.apply(
            {"params": params["params"], "cache": cache0},
            prompt, mutable=["cache"],
        )
        return upd["cache"], pick(logits_p[:, -1, :], key0)

    def decode_chunk(params, cache, tok, t0, n):
        """n is static per compile bucket; t0 is traced (key folding)."""

        def step(carry, i):
            cache, tok = carry
            logits, upd = model_dec.apply(
                {"params": params["params"], "cache": cache},
                tok[:, None], mutable=["cache"],
            )
            nxt = pick(logits[:, -1, :], jax.random.fold_in(key0, t0 + i))
            return (upd["cache"], nxt), nxt

        (cache, tok), toks = jax.lax.scan(
            step, (cache, tok), jnp.arange(n)
        )
        return cache, tok, jnp.moveaxis(toks, 0, 1)  # (B, n)

    return prefill, decode_chunk


def build_stream(props: Dict[str, str], device=None):
    """Factory for the streaming-generation element: same ``custom``
    dialect (and seed semantics: ``seed`` = params, ``gen_seed`` =
    sampling) as the zoo transformer, so the streamed tokens are
    bit-equal to ``generate:<N>`` one-shot serving.  Params are committed
    to ``device`` (default: the process default device); the cache is
    created inside the jitted prefill and follows them.  Returns
    (prefill, decode_chunk, params, max_seq)."""
    from ..core.hw import default_device

    cfg = _cfg_from_props(props)
    params = jax.device_put(
        host_init(
            TransformerLM(cfg).init,
            int(props.get("seed", "0")),
            np.zeros((1, min(8, cfg.max_seq)), np.int32),
        ),
        device if device is not None else default_device(),
    )
    prefill, decode_chunk = make_stream_generate(
        cfg,
        temperature=float(props.get("temperature", "0")),
        top_k=int(props.get("top_k", "0")),
        seed=int(props.get("gen_seed", "0")),
    )
    return prefill, decode_chunk, params, cfg.max_seq


class SlotModel:
    """The jittable halves of the SLOTTED decode path (continuous
    batching, ``core/slots.py``): a fixed-width slot batch whose cache
    pytree is slot-indexed pages with PER-SLOT positions, so independent
    generation streams join/leave at token boundaries without retracing.

    Sampling semantics are IDENTICAL to :func:`make_stream_generate`:
    token 1 is picked with the raw gen_seed key, token j>=1 with
    ``fold_in(key0, j)`` — per slot, via a vmapped per-row pick (vmap of
    a key-batched draw is bit-equal to the per-row loop), so a single
    occupant's token stream is bit-identical to the seed ``generate:<N>``
    one-shot path and to the unslotted streaming path.

    * ``init_cache()`` — zeroed page pytree: per layer a K and a V leaf
      of shape ``(slots, max_seq, d_model)`` in the model dtype, plus the
      per-slot ``(slots,)`` write positions.  The leaf is LANE-DENSE on
      purpose: with all heads side by side in the minor dim a row is
      whole 128-lane tiles and the TPU keeps the leaf in its logical
      order, so a step's row scatter lands in place and the decode
      contraction reads the leaf as it lies (:func:`kv_attend_write`);
      a ``(…, heads, head_dim)`` leaf is laid out sequence-minor on the
      device and every row write re-lays the whole cache out;
    * ``reset_slot(cache, slot)`` — zero ONE slot's pages + positions (a
      join touches only its own slot; jitted once, slot is traced).  The
      cache argument is DONATED when the model's devices are not CPU,
      exactly as ``decode_fn``'s is below: the rows are zeroed in place
      and a caller must not touch the cache it passed in;
    * ``prefill_chunk(params, cache, toks (1,n), slot)`` — slice the
      slot's pages to a B=1 view, run one causal chunk (the chunked
      prefill that interleaves with decode), scatter back; returns
      ``(cache, last_logits (1,V))``.  One compile bucket per distinct
      n — callers bound them (core/slots.py LRU);
    * ``pick_first(logits (1,V))`` — token 1 (same op as the unslotted
      prefill pick);
    * ``decode_fn(k)(params, cache, tok (S,), gen (S,), active (S,))`` —
      ``k`` tokens for every active slot in ONE ``lax.scan`` dispatch
      (the same per-chunk amortization the unslotted path gets; callers
      pick ``k = min(chunk, min remaining)`` so streams complete exactly
      at scan boundaries).  Compiled once per (slot width, k) — the
      idle-slot mask keeps each bucket shape-stable as streams churn.
      The cache argument is DONATED when the model's devices are not CPU
      (the engine's cache is caller-private — PR-6 donation discipline;
      XLA ignores donation on CPU and warns, so it is gated exactly like
      ``backends/jax_xla._donation_ok``).

    Placement: with a ``mesh`` params and pages shard over it; without
    one BOTH are committed to ``device`` (default: the process default
    device), so every prefill and decode step runs there — nothing is
    left for jit to place by default.
    """

    #: ride the decode read-back: cache rows the dispatch's per-token reads
    #: covered in one leaf, and the rows that leaf holds, summed over its
    #: steps (``ops/decode_attention.py``; equal where the read is not
    #: bounded by fill)
    counter_names = ("gen_kv_rows_read", "gen_kv_rows_held")
    #: K/V rows below a position are immutable: a prefix can be cut out
    supports_prefix = True

    def __init__(self, cfg: TransformerConfig, slots: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 donate: Optional[bool] = None,
                 mesh: Optional[Mesh] = None, device=None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.cfg = cfg
        self.slots = int(slots)
        self._model = TransformerLM(
            cfg, decode=True, slotted=True, single_device=mesh is None)
        self._pick = _make_pick(temperature, top_k)
        self._temperature = temperature
        self._key0 = jax.random.PRNGKey(seed)
        # mesh-sharded decode (continuous batching past one chip): the
        # per-slot KV pages shard on HEADS along tp — a leaf is (slots,
        # max_seq, d_model) with heads contiguous in d_model, so dim 2
        # splits at head boundaries when tp divides the heads and every
        # device holds all slots' pages for its head shard; the slot batch
        # itself stays replicated (the engine's tok/gen/active vectors
        # are tiny).  GSPMD propagates the placements through the jitted
        # step, so the shape-stable bucket contract is unchanged.
        self.mesh = mesh
        self.device = None
        self._page_sharding = None
        if mesh is None:
            from ..core.hw import default_device

            self.device = device if device is not None else default_device()
            platform = self.device.platform
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            platform = next(iter(mesh.devices.flat)).platform

            tp = mesh.shape.get("tp", 1)

            def page_spec(shape):
                # shard d_model by whole heads when tp divides them; the
                # per-slot index/step vectors replicate
                if len(shape) >= 3 and cfg.n_heads % tp == 0 and tp > 1:
                    return NamedSharding(mesh, P(None, None, "tp"))
                return NamedSharding(mesh, P())

            self._page_sharding = page_spec
        if donate is None:
            donate = platform != "cpu"
        self._donate = (1,) if donate else ()
        #: compile counters — the shape-stability contract is observable
        #: (tests pin decode_compiles staying at the bucket count across
        #: join/leave churn)
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self.reset_slot = jax.jit(
            self._reset_slot, donate_argnums=(0,) if donate else ())
        self.pick_first = jax.jit(self._pick_first)

    def place_params(self, params):
        """Place a param pytree where this model runs, fully staged
        before return: tp-sharded over the mesh, else committed to the
        model's device."""
        if self.mesh is None:
            params = jax.device_put(params, self.device)
        else:
            from ..parallel.sharding import shard_params, transformer_rules

            params = shard_params(params, self.mesh, transformer_rules())
        jax.block_until_ready(params)
        return params

    # -- cache lifecycle ----------------------------------------------------
    def init_cache(self):
        shapes = jax.eval_shape(
            lambda: self._model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((self.slots, 1), jnp.int32),
            )["cache"]
        )
        if self._page_sharding is not None:
            page = self._page_sharding
            return jax.tree.map(
                lambda s: jax.device_put(
                    jnp.zeros(s.shape, s.dtype), page(s.shape)),
                shapes,
            )
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=self.device),
            shapes,
        )

    @staticmethod
    def _row_start(c, slot):
        return (slot,) + (0,) * (c.ndim - 1)

    def _reset_slot(self, cache, slot):
        def zero_row(c):
            row = jnp.zeros((1,) + c.shape[1:], c.dtype)
            return jax.lax.dynamic_update_slice(
                c, row, self._row_start(c, slot))

        return jax.tree.map(zero_row, cache)

    # -- shared-prefix page export / attach ---------------------------------
    # (core/slots.py PrefixCache): a slot's low KV pages for positions
    # [start, stop) are immutable once prefill has passed them — prefill
    # and decode only ever write FORWARD of the per-slot index — so they
    # can be published for reuse by later streams sharing the prefix.
    def export_prefix(self, cache, slot: int, start: int, stop: int):
        """COPY one slot's KV pages for positions ``[start, stop)``.

        The result is a fresh pytree (slice outputs are new buffers, and
        per-slot position counters are replaced by a placeholder), so a
        later donated prefill/decode step consuming the source cache can
        never invalidate a published entry.  Opaque to the engine —
        only :meth:`attach_prefix` interprets it."""
        n = int(stop) - int(start)

        def cut(c):
            if c.ndim < 2:
                # per-slot write positions: recomputed (= n) on attach
                return jnp.zeros((1,), c.dtype)
            return jax.lax.dynamic_slice(
                c, (slot, int(start)) + (0,) * (c.ndim - 2),
                (1, n) + tuple(c.shape[2:]))

        return jax.tree.map(cut, cache)

    def attach_prefix(self, cache, slot: int, pages_list, n: int):
        """Write published prefix pages (ordered per-grain chunks
        covering ``[0, n)``) into one freshly-reset slot and set its
        write position to ``n``.

        Bit-exactness by construction: the pages are the verbatim
        buffers a cold prefill produced at the same chunk boundaries, so
        the slot's state (pages ``[0, n)`` + zeros above + position
        ``n``) is indistinguishable from a cold run paused at
        ``prefill_pos == n`` — every subsequent prefill/decode program
        is the same XLA program on the same inputs."""

        def cat(*ps):
            if ps[0].ndim < 2:
                return ps[0]
            return ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=1)

        pages = jax.tree.map(cat, *pages_list)

        def put(c, p):
            if c.ndim < 2:
                return jax.lax.dynamic_update_slice(
                    c, jnp.full((1,), n, c.dtype), (slot,))
            return jax.lax.dynamic_update_slice(
                c, p.astype(c.dtype), (slot, 0) + (0,) * (c.ndim - 2))

        return jax.tree.map(put, cache, pages)

    # -- prefill (chunked, one slot at a time) ------------------------------
    def _prefill_chunk(self, params, cache, toks, slot):
        sl = jax.tree.map(
            lambda c: jax.lax.dynamic_slice(
                c, self._row_start(c, slot), (1,) + c.shape[1:]),
            cache,
        )
        logits, upd = self._model.apply(
            {"params": params["params"], "cache": sl},
            toks, mutable=["cache"],
        )
        cache = jax.tree.map(
            lambda c, u: jax.lax.dynamic_update_slice(
                c, u, self._row_start(c, slot)),
            cache, upd["cache"],
        )
        return cache, logits[:, -1, :]

    @staticmethod
    def prefill_counts(pos: int, n: int):
        """Nothing this model counts follows from a chunk's position."""
        return {}

    def prefill_fn(self, n: int):
        """One jitted prefill bucket for chunk length ``n`` (caller
        caches/bounds these — core/slots.py shares the LRU discipline of
        the generator element's decode buckets)."""

        def traced(params, cache, toks, slot):
            self.prefill_compiles += 1  # trace-time only
            return self._prefill_chunk(params, cache, toks, slot)

        del n  # bucketing key only; the shape specializes the jit
        return jax.jit(traced, donate_argnums=self._donate)

    def _pick_first(self, logits):  # (1, V) -> (1,)
        return self._pick(logits, self._key0)

    # -- decode (whole slot batch, k tokens per dispatch) -------------------
    def _pick_slots(self, lg, gen):  # (S, V), (S,) -> (S,)
        return pick_slots(self._pick, self._key0, self._temperature, lg, gen)

    def _decode_scan(self, k, params, cache, tok, gen, active):
        cfg = self.cfg
        leaf = jax.ShapeDtypeStruct(
            (self.slots, cfg.max_seq, cfg.d_model), cfg.dtype)

        def step(carry, _i):
            cache, tok, gen, rows = carry
            rows = rows + decode_attention.rows_read(
                decode_attention.live_rows(cache["step"], active, cfg.max_seq),
                leaf, single_device=self._model.single_device)
            logits, upd = self._model.apply(
                {"params": params["params"], "cache": cache},
                tok[:, None], mutable=["cache"], active=active,
            )
            nxt = self._pick_slots(logits[:, -1, :], gen)
            # idle slots keep their token/fold-count frozen, so the
            # scan is bit-transparent for every occupied row
            tok = jnp.where(active > 0, nxt, tok)
            gen = gen + active
            return (upd["cache"], tok, gen, rows), nxt

        (cache, tok, gen, rows), toks = jax.lax.scan(
            step, (cache, tok, gen, jnp.int32(0)), jnp.arange(k)
        )
        counts = jnp.stack([rows, jnp.int32(k * self.slots * cfg.max_seq)])
        return cache, tok, gen, jnp.moveaxis(toks, 0, 1), counts  # (S, k)

    def decode_fn(self, k: int):
        """One jitted decode bucket: ``k`` tokens for every active slot
        per dispatch (caller caches/bounds these alongside the prefill
        buckets).  Returns ``(cache, tok, gen, toks (S, k), counts)``;
        ``counts`` follows :attr:`counter_names`."""

        def traced(params, cache, tok, gen, active):
            self.decode_compiles += 1  # trace-time only
            return self._decode_scan(k, params, cache, tok, gen, active)

        return jax.jit(traced, donate_argnums=self._donate)


def build_slot_stream(props: Dict[str, str], slots: int,
                      donate: Optional[bool] = None,
                      mesh: Optional[Mesh] = None, device=None):
    """Factory for the CONTINUOUS-BATCHING generator path: same
    ``custom`` dialect and seed semantics as :func:`build_stream`
    (``seed`` = params, ``gen_seed`` = sampling), so a single occupant's
    stream is bit-equal to ``generate:<N>`` one-shot serving.  With a
    ``mesh`` the params tensor-shard on tp and the per-slot KV pages
    shard on heads along tp; without one both are committed to
    ``device`` (default: the process default device).  Params are fully
    staged before return.  The token SEQUENCE is unchanged, only its
    placement, so the stream-continuity resume signature deliberately
    excludes mesh and device.  Returns ``(SlotModel, params, max_seq)``."""
    cfg = _cfg_from_props(props)
    params = host_init(
        TransformerLM(cfg).init,
        int(props.get("seed", "0")),
        np.zeros((1, min(8, cfg.max_seq)), np.int32),
    )
    model = SlotModel(
        cfg, slots,
        temperature=float(props.get("temperature", "0")),
        top_k=int(props.get("top_k", "0")),
        seed=int(props.get("gen_seed", "0")),
        donate=donate,
        mesh=mesh,
        device=device,
    )
    return model, model.place_params(params), cfg.max_seq


def build(custom_props=None):
    """Zoo entry: fn(params, [tokens (B,T) or (T,)]) -> [logits].

    With custom prop ``generate:<N>`` the entry serves greedy KV-cache
    generation instead: tokens in -> prompt+N completion tokens out; that
    fn takes ``single_device`` from whoever compiles it (``models.
    takes_single_device``), as the ViT does.
    """
    props = custom_props or {}
    cfg = _cfg_from_props(props)
    model = TransformerLM(cfg)
    params = host_init(
        model.init,
        int(props.get("seed", "0")),
        np.zeros((1, min(8, cfg.max_seq)), np.int32),
    )
    max_new = int(props.get("generate", "0"))
    in_spec = StreamSpec((TensorSpec((None,), np.int32, "tokens"),), FORMAT_STATIC)

    if max_new > 0:
        gens = {
            one: make_generate(
                cfg,
                max_new,
                temperature=float(props.get("temperature", "0")),
                top_k=int(props.get("top_k", "0")),
                seed=int(props.get("gen_seed", "0")),
                single_device=one,
            )
            for one in (True, False)
        }

        def fn(p, inputs, single_device: bool = True):
            toks = inputs[0]
            single = toks.ndim == 1
            if single:
                toks = toks[None]
            out = gens[bool(single_device)](p, toks)
            return [out[0] if single else out]

        out_spec = StreamSpec(
            (TensorSpec((None,), np.int32, "tokens"),), FORMAT_STATIC
        )
        return fn, params, in_spec, out_spec

    def fn(p, inputs):
        toks = inputs[0]
        single = toks.ndim == 1
        if single:
            toks = toks[None]
        out = model.apply(p, toks)
        return [out[0] if single else out]

    out_spec = StreamSpec(
        (TensorSpec((None, cfg.vocab), np.float32, "logits"),), FORMAT_STATIC
    )
    return fn, params, in_spec, out_spec


# ---------------------------------------------------------------------------
# Sharded training step (dp × tp × sp)
# ---------------------------------------------------------------------------
def make_train_step(
    mesh: Mesh,
    cfg: Optional[TransformerConfig] = None,
    learning_rate: float = 1e-3,
    seq_axis: str = "sp",
):
    """Build a fully-sharded LM training step over `mesh`.

    Returns (train_step, params, opt_state, data_sharding) where
    ``train_step(params, opt_state, tokens) -> (params, opt_state, loss)``
    is jitted with NamedShardings: params tensor-parallel per
    transformer_rules, tokens sharded (dp, sp), loss replicated.
    """
    import optax

    from ..parallel.sharding import batch_sharding, shard_params, transformer_rules

    cfg = cfg or TransformerConfig()
    # init with an unsharded twin (same param structure; ring attention needs
    # shard-divisible shapes the tiny init batch doesn't have)
    params = host_init(
        TransformerLM(cfg).init, 0, np.zeros((1, 8), np.int32)
    )
    model = TransformerLM(cfg, mesh=mesh, seq_axis=seq_axis)
    tx = optax.adamw(learning_rate)

    rules = transformer_rules(tp_axis="tp")
    params = shard_params(params, mesh, rules)
    opt_state = tx.init(params)
    # optimizer moments mirror the param shardings automatically (they are
    # tree_map'ed from params), so no separate annotation pass is needed.
    data_sh = batch_sharding(mesh, "dp", seq_axis)

    def loss_fn(p, tokens):
        # next-token LM loss on the full (sp-divisible) sequence; targets are
        # tokens rolled left, with the wrapped final position masked out.
        logits = model.apply(p, tokens)
        targets = jnp.roll(tokens, -1, axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = jnp.ones_like(ll).at[:, -1].set(0.0)
        return -(ll * mask).sum() / mask.sum()

    def _step(p, opt, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens)
        updates, opt = tx.update(grads, opt, p)
        p = optax.apply_updates(p, updates)
        return p, opt, loss

    # donate params+opt_state: XLA reuses their HBM for the updated copies
    # (without this, peak memory is ~2x params+optimizer every step)
    train_step = jax.jit(_step, donate_argnums=(0, 1))
    return train_step, params, opt_state, data_sh
