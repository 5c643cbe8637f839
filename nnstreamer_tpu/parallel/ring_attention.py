"""Ring attention: exact attention over a sequence sharded across devices.

The reference has no long-context story (SURVEY §5.7: "absent ... net-new
design").  This is that net-new design: the sequence axis is sharded over a
mesh axis (``sp``); each device holds a local Q/K/V block, and K/V blocks
rotate around the ring via ``lax.ppermute`` while a streaming (online)
softmax accumulates exact results — attention memory stays O(T_local) and
the permute overlaps with the block matmuls (XLA schedules the ppermute
DMA concurrently; each hop is neighbor-to-neighbor on ICI).

Pattern references: Liu et al., "Ring Attention with Blockwise Transformers
for Near-Infinite Context" (PAPERS.md); flash-attention online softmax.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

def _block_attn(q, k, v, q_pos, k_pos, causal: bool, scale: float):
    """One (q-block × kv-block) attention contribution.

    q: (B, Tq, H, D), k/v: (B, Tk, H, D); returns (scores-max m, partial
    numerator o, partial denominator l) for online-softmax merging.
    """
    # f32 accumulation on the MXU regardless of input dtype (bf16-safe)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale  # (B,H,Tq,Tk) f32
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # (Tq,Tk)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # (B,H,Tq)
    # fully-masked rows: exp(-inf - -inf) guards via where
    p = jnp.exp(s - jnp.where(jnp.isinf(m), 0.0, m)[..., None])
    p = jnp.where(jnp.isinf(s), 0.0, p)
    l = jnp.sum(p, axis=-1)  # (B,H,Tq) f32
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )  # (B,Tq,H,D) f32
    return m, o, l


def _merge(m1, o1, l1, m2, o2, l2):
    """Merge two online-softmax partials (flash-attention recurrence)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(jnp.where(jnp.isinf(m1), -jnp.inf, m1) - m)
    a2 = jnp.exp(jnp.where(jnp.isinf(m2), -jnp.inf, m2) - m)
    a1 = jnp.where(jnp.isinf(m1) & (m1 < 0), 0.0, a1)
    a2 = jnp.where(jnp.isinf(m2) & (m2 < 0), 0.0, a2)
    o = o1 * a1.transpose(0, 2, 1)[..., None] + o2 * a2.transpose(0, 2, 1)[..., None]
    l = l1 * a1 + l2 * a2
    return m, o, l


def _ring_flash_local(q, k, v, *, axis_name: str, causal: bool,
                      interpret: bool, ring_size: int):
    """Per-shard body with the Pallas flash kernel as the block primitive.

    Each ring hop holds one remote K/V block; the block's attention runs
    as ONE flash-attention kernel call (``ops/flash_attention.py``
    ``with_lse``), and partials merge across hops by the exact
    (out, lse) recurrence.  Hop cases under causal masking:

    * hop 0 — the device's own block: intra-block causal (kernel
      ``causal=True``; local positions are aligned, no offset needed);
    * source block strictly BEFORE mine: fully visible
      (``causal=False``);
    * source block AFTER mine: fully masked — the kernel still runs
      (same cost shape as the jnp path, which masks everything to -inf)
      but its contribution is zeroed via lse = -inf before the merge.

    The hop loop is a Python unroll over the STATIC ``ring_size`` (the
    mesh axis length), so each hop keeps a static kernel configuration;
    visibility of later hops depends on the traced device index and is
    applied as a select on lse.
    """
    from ..ops.flash_attention import _NEG_INF, flash_attention_lse

    my_idx = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    # this body runs under check_vma=False (the pallas interpreter emits
    # constants without vma, tripping strict varying-axes typing), so the
    # accumulators need no varying mark
    lse_acc = jnp.full((B, H, T), _NEG_INF, jnp.float32)
    o_acc = jnp.zeros(q.shape, jnp.float32)
    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]
    k_cur, v_cur = k, v
    for i in range(ring_size):
        src = (my_idx - i) % ring_size  # traced; block owner of k_cur
        o_b, lse_b = flash_attention_lse(
            q, k_cur, v_cur, causal=(causal and i == 0),
            interpret=interpret,
        )
        if causal and i > 0:
            visible = src < my_idx  # traced whole-block visibility
            lse_b = jnp.where(visible, lse_b, _NEG_INF)
        # exact two-partial merge (the kernel's online-softmax recurrence
        # lifted to whole blocks)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        a_acc = jnp.exp(lse_acc - lse_new)
        a_b = jnp.exp(lse_b - lse_new)
        o_acc = (
            o_acc * a_acc.transpose(0, 2, 1)[..., None]
            + o_b.astype(jnp.float32) * a_b.transpose(0, 2, 1)[..., None]
        )
        lse_acc = lse_new
        if i + 1 < ring_size:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    return o_acc.astype(q.dtype)


def _ring_attn_local(q, k, v, *, axis_name: str, all_axes, causal: bool):
    """Per-shard body (runs under shard_map): local Q stays put, K/V blocks
    ring-rotate `axis_size` times."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = 1.0 / (D**0.5)
    q_pos = my_idx * T + jnp.arange(T)

    # constants entering the scan carry must be marked device-varying over
    # the manual mesh axes (shard_map vma typing)
    def varying(x):
        return lax.pcast(x, all_axes, to="varying")

    m0 = varying(jnp.full((B, H, T), -jnp.inf, jnp.float32))
    o0 = varying(jnp.zeros(q.shape, jnp.float32))
    l0 = varying(jnp.zeros((B, H, T), jnp.float32))
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(carry, i):
        m, o, l, k_cur, v_cur = carry
        src = (my_idx - i) % axis_size  # whose kv block we currently hold
        k_pos = src * T + jnp.arange(T)
        m2, o2, l2 = _block_attn(q, k_cur, v_cur, q_pos, k_pos, causal, scale)
        m, o, l = _merge(m, o, l, m2, o2, l2)
        # rotate kv to the next device (neighbor hop on the ring)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, o, l, k_nxt, v_nxt), None

    (m, o, l, _, _), _ = lax.scan(
        step, (m0, o0, l0, k, v), jnp.arange(axis_size)
    )
    # normalize; fully-masked rows (can't happen causally: diag always valid)
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    seq_axis: str = "sp",
    batch_axes=("dp",),
    causal: bool = True,
    use_flash: bool = False,
    interpret: bool = False,
):
    """Exact multi-head attention with the sequence dim sharded on
    ``seq_axis`` and batch on ``batch_axes``.

    q/k/v: (B, T, H, D) global shapes; T must divide by mesh[seq_axis].
    Returns (B, T, H, D) with the same sharding.

    ``use_flash=True`` runs each ring hop's block product as ONE Pallas
    flash-attention kernel call (ring-flash composition: VMEM-streamed
    scores inside the hop, exact (out, lse) merge across hops) — the
    long-context configuration on real TPU.  ``interpret`` forces the
    kernel interpreter (CPU tests).
    """
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    batch_spec = (
        None
        if not batch_axes
        else (batch_axes[0] if len(batch_axes) == 1 else batch_axes)
    )
    spec = P(batch_spec, seq_axis, None, None)
    all_axes = tuple(batch_axes) + (seq_axis,)
    if use_flash:
        body = functools.partial(
            _ring_flash_local, axis_name=seq_axis,
            causal=causal, interpret=interpret,
            ring_size=mesh.shape[seq_axis],
        )
    else:
        body = functools.partial(
            _ring_attn_local, axis_name=seq_axis, all_axes=all_axes,
            causal=causal,
        )
    # the pallas interpreter/lowering emits internal constants without
    # vma; jax's documented workaround is to disable the check for this
    # body (the jnp ring keeps strict typing)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not use_flash,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, causal: bool = True):
    """Unsharded exact attention (test oracle)."""
    B, T, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (D**0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
