"""Ulysses-style sequence parallelism: all-to-all head redistribution.

The second long-context strategy next to :mod:`ring_attention` (SURVEY
§5.7 is net-new design; pattern reference: DeepSpeed-Ulysses, Jacobs et
al. 2023, PAPERS.md).  Where ring attention keeps the sequence sharded and
rotates K/V blocks around the ring, Ulysses re-shards with two
all-to-alls:

    in:  (B, T/sp, H,    D)   sequence-sharded
    a2a: (B, T,    H/sp, D)   head-sharded  -> plain local attention
    a2a: (B, T/sp, H,    D)   back to sequence-sharded

Exact attention, two collectives per layer (vs sp-1 ppermute hops for
ring), but heads must divide by the ``sp`` axis.  On TPU the all-to-all
rides ICI; pick Ulysses when H % sp == 0 and T_local x T attention fits
HBM, ring otherwise — :func:`sequence_attention` makes that choice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import reference_attention, ring_attention


def _local_attention(q, k, v, causal: bool):
    """Plain exact attention on local (full-sequence, head-sharded) blocks."""
    D = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / (D**0.5)
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", (p / jnp.sum(p, axis=-1, keepdims=True)).astype(v.dtype),
        v, preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _ulysses_local(q, k, v, *, seq_axis: str, causal: bool):
    # (B, T_local, H, D) -> all-to-all -> (B, T, H_local, D)
    def scatter_heads(x):
        return lax.all_to_all(
            x, seq_axis, split_axis=2, concat_axis=1, tiled=True
        )

    def gather_heads(x):
        return lax.all_to_all(
            x, seq_axis, split_axis=1, concat_axis=2, tiled=True
        )

    q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = _local_attention(q, k, v, causal)
    return gather_heads(out)


def ulysses_attention(
    q, k, v, mesh: Mesh, *, seq_axis: str = "sp", batch_axes=("dp",),
    causal: bool = True,
):
    """Exact attention, sequence sharded on ``seq_axis``, via two
    all-to-alls.  q/k/v: (B, T, H, D) global; H must divide by
    mesh.shape[seq_axis]."""
    sp = mesh.shape[seq_axis]
    if q.shape[2] % sp:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by "
            f"{seq_axis}={sp}; use ring_attention instead"
        )
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    batch_spec = (
        None
        if not batch_axes
        else (batch_axes[0] if len(batch_axes) == 1 else batch_axes)
    )
    spec = P(batch_spec, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, seq_axis=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def sequence_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "sp",
                       batch_axes=("dp",), causal: bool = True,
                       strategy: str = "auto", use_flash: bool = False,
                       interpret: bool = False):
    """Pick a sequence-parallel attention strategy.

    ``auto``: Ulysses when the head count divides the ``sp`` axis (two
    ICI all-to-alls), else ring (sp-1 neighbor ppermutes).  Both exact.
    ``ring-flash`` (or ``use_flash=True`` with ring) runs each ring hop
    as one Pallas flash-attention kernel call.
    """
    sp = mesh.shape.get(seq_axis, 1)
    if strategy == "ring-flash":
        strategy, use_flash = "ring", True
    if strategy == "auto":
        # an explicit flash request pins the ring path: auto-resolving to
        # ulysses would silently drop it and re-materialize the full
        # (T x T_local) score matrix the caller opted out of
        strategy = (
            "ring" if use_flash
            else "ulysses" if sp > 1 and q.shape[2] % sp == 0
            else "ring"
        )
    elif strategy == "ulysses" and use_flash:
        raise ValueError(
            "use_flash applies to the ring path; pass strategy='ring' or "
            "'ring-flash' (ulysses has no per-hop kernel)"
        )
    if strategy == "ulysses":
        return ulysses_attention(
            q, k, v, mesh, seq_axis=seq_axis, batch_axes=batch_axes,
            causal=causal,
        )
    if strategy == "ring":
        return ring_attention(
            q, k, v, mesh, seq_axis=seq_axis, batch_axes=batch_axes,
            causal=causal, use_flash=use_flash, interpret=interpret,
        )
    raise ValueError(
        f"unknown strategy {strategy!r} (auto|ulysses|ring|ring-flash)"
    )


__all__ = [
    "ulysses_attention",
    "sequence_attention",
    "reference_attention",
]
