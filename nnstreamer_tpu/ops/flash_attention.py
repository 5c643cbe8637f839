"""Flash attention as a Pallas TPU kernel (single-device block).

The MXU-native attention inner loop for the transformer family: Q blocks
stream over K/V blocks with an online softmax, so the (Tq x Tk) score
matrix never materializes in HBM — scores live in VMEM one block at a
time, accumulation in f32.  Pattern references: Dao et al. FlashAttention;
the public jax pallas attention examples (PAPERS.md / SNIPPETS.md).

This is the intra-device complement of the sequence-parallel layers:
``parallel/ring_attention.py`` shards T across chips and rotates K/V;
each device's local block product is exactly what this kernel computes.

``flash_attention(q, k, v)`` takes (B, T, H, D) like the rest of the
stack.  A program lowered for a TPU runs the kernel; every other
platform lowers the fused-XLA reference (``lax.platform_dependent``: the
choice follows the device the program is compiled for, and nothing on a
TPU declines the kernel quietly — a shape it cannot take raises).
``interpret=True`` (tests only) runs the kernel in the Pallas interpreter
instead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # large-negative instead of -inf: avoids NaN in exp-diff
_LANES = 128      # running max / sum live lane-replicated in VMEM scratch


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q: int,
                  block_k: int, causal: bool, scale: float, seq_len: int,
                  valid_len: int, with_lse: bool):
    """One (batch*head, q-block, kv-block) program of the online softmax.

    The kv-block axis is the innermost, sequential grid axis: the running
    max ``m``, sum ``l`` (both (block_q, 128), lane-replicated) and the
    f32 accumulator persist in VMEM scratch across it; the first kv step
    initializes them, the last one normalizes into ``o_ref``.  q_ref
    (block_q, D); k_ref/v_ref (block_k, D) — only one K/V block is ever
    resident, so T is bounded by HBM, not VMEM.  ``valid_len`` < seq_len
    marks wrapper padding: K columns at or past it are masked out (static
    python int — the mask compiles to constants).
    """
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step():
        k = k_ref[...]
        v = v_ref[...]
        # q . k^T on the MXU in the input dtype, f32 accumulation
        s = lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal or valid_len < seq_len:
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        if valid_len < seq_len:
            s = jnp.where(k_pos < valid_len, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        # kv blocks strictly above the diagonal contribute nothing
        pl.when(ki * block_k <= qi * block_q + (block_q - 1))(_step)
    else:
        _step()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)
        if with_lse:
            # per-row log-sum-exp of the (masked) scores: the cross-block
            # merge statistic for ring attention (sequence parallelism);
            # fully-masked rows keep a large-negative lse (l == 0)
            lse_ref[...] = jnp.where(
                l > 0.0, m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30)),
                _NEG_INF)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret",
                     "valid_len", "with_lse"),
)
def _flash_bh(qf, kf, vf, causal: bool, block_q: int, block_k: int,
              interpret: bool, valid_len: int, with_lse: bool = False):
    """(BH, Tq, D) + (BH, Tk, D) K/V -> (BH, Tq, D) [+ (BH, Tq, 1) f32
    lse]; grid over (BH, Tq/block_q, Tk/block_k).  Tk may differ from Tq
    (ring hops / partial-key calls) — causal requires Tq == Tk (aligned
    positions)."""
    BH, Tq, D = qf.shape
    Tk = kf.shape[1]
    if causal and Tq != Tk:
        # ValueError, not assert: survives python -O — a misaligned direct
        # caller must fail loud, never silently mis-mask
        raise ValueError(
            f"causal flash needs aligned q/k positions (Tq={Tq}, Tk={Tk})"
        )
    if Tq % block_q or Tk % block_k:
        raise ValueError(
            f"flash blocks ({block_q}, {block_k}) do not tile (Tq={Tq}, "
            f"Tk={Tk})")
    kern = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=1.0 / (D**0.5), seq_len=Tk, valid_len=valid_len,
        with_lse=with_lse,
    )
    # under shard_map (ring hops) outputs must declare their varying
    # mesh axes (vma typing); inherit from the traced input
    vma = getattr(qf.aval, "vma", None) or frozenset()

    out_shape = [jax.ShapeDtypeStruct((BH, Tq, D), qf.dtype, vma=vma)]
    # None squeezes the batch*head dim out of the kernel refs
    out_specs = [pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0))]
    if with_lse:
        # trailing length-1 lane dim keeps the ref 2-D for Mosaic tiling
        out_shape.append(
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32, vma=vma))
        out_specs.append(
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)))
    res = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=(BH, Tq // block_q, Tk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, D), jnp.float32),       # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return res if with_lse else res[0]


def _blocks(T: int, block_q: int, block_k: int):
    """(T_pad, bq, bk): the padded length and the blocks that tile it.  A
    sequence shorter than a block becomes ONE block, rounded up to the
    sublane tile; a longer one pads to a multiple of both blocks."""
    if T <= min(block_q, block_k):
        T_pad = -(-T // 16) * 16
        return T_pad, T_pad, T_pad
    blk = math.lcm(block_q, block_k)
    T_pad = -(-T // blk) * blk
    return T_pad, block_q, block_k


def _to_heads(x):
    # (B, T, H, D) -> (B*H, T, D): each (batch, head) is one independent
    # attention problem
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Exact attention, (B, T, H, D) -> (B, T, H, D).

    Lowered for a TPU: the Pallas kernel.  Lowered for anything else: the
    fused-XLA reference path (same numerics contract), unless
    ``interpret=True`` asks for the kernel in the Pallas interpreter —
    TESTS only, orders of magnitude slower than XLA.
    """
    B, T, H, D = q.shape
    # non-divisible T (e.g. ViT's (S/p)^2 + 1 tokens): pad K/V/Q up to a
    # multiple of BOTH block sizes; padded K columns are masked inside the
    # kernel via the static valid_len, padded Q rows are sliced off below
    T_pad, bq, bk = _blocks(T, block_q, block_k)

    def kernel(q, k, v):
        qf, kf, vf = _to_heads(q), _to_heads(k), _to_heads(v)
        if T_pad != T:
            pad = ((0, 0), (0, T_pad - T), (0, 0))
            qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)
        out = _flash_bh(
            qf, kf, vf, causal, bq, bk, bool(interpret), valid_len=T)
        return out[:, :T].reshape(B, H, T, D).transpose(0, 2, 1, 3)

    def reference(q, k, v):
        from ..parallel.ring_attention import reference_attention

        return reference_attention(q, k, v, causal=causal).astype(q.dtype)

    if interpret:
        return kernel(q, k, v)
    return lax.platform_dependent(q, k, v, tpu=kernel, default=reference)


def flash_attention_lse(q, k, v, *, causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """Exact attention + per-row log-sum-exp: (B, T, H, D) ->
    ((B, T, H, D), (B, H, T) f32).

    The lse is the cross-block merge statistic: two attention partials
    over disjoint key sets combine exactly as

        lse = logaddexp(lse1, lse2)
        out = out1 * exp(lse1 - lse) + out2 * exp(lse2 - lse)

    which is how ``parallel/ring_attention.py`` composes this kernel
    across the ``sp`` ring (each hop's K/V block -> one kernel call).
    Ring blocks are uniform, so there is no padding path: blocks that do
    not tile the sequence raise.  Platform choice as in
    :func:`flash_attention`.
    """
    B, T, H, D = q.shape
    Tk = k.shape[1]
    bq, bk = min(block_q, T), min(block_k, Tk)

    def kernel(q, k, v):
        out, lse = _flash_bh(
            _to_heads(q), _to_heads(k), _to_heads(v), causal, bq, bk,
            bool(interpret), valid_len=Tk, with_lse=True,
        )
        return (
            out.reshape(B, H, T, D).transpose(0, 2, 1, 3),
            lse.reshape(B, H, T),
        )

    if interpret:
        return kernel(q, k, v)
    return lax.platform_dependent(
        q, k, v, tpu=kernel,
        default=functools.partial(reference_attention_lse, causal=causal))


def reference_attention_lse(q, k, v, causal: bool = True):
    """Unsharded exact attention + lse (kernel-free contract twin)."""
    B, T, H, D = q.shape
    Tk = k.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / (D**0.5)
    if causal:
        assert T == Tk, "causal reference needs aligned q/k positions"
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)  # (B,H,T); -inf on fully-masked rows
    p = jnp.exp(s - jnp.where(jnp.isinf(lse), 0.0, lse)[..., None])
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    lse = jnp.where(jnp.isinf(lse), jnp.float32(_NEG_INF), lse)
    return out, lse.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Differentiable wrapper: kernel forward, recompute backward.
#
# The Pallas kernel defines no VJP; a hand-written backward kernel is the
# eventual optimization, but the standard interim pattern is forward-fast /
# backward-recompute: the forward saves only (q, k, v) as residuals, and
# the backward re-derives gradients through an f32-accumulated XLA
# reference attention.  NOTE the O(T) memory property is the FORWARD's:
# the recompute backward still materializes the (B,H,T,T) score matrix
# under XLA autodiff, so training peak memory stays O(T^2) per layer
# until a backward kernel lands (long-context training shards T via
# parallel/ring_attention.py instead).  The model zoo's flash branches
# call this entry point; inference-only code may call flash_attention.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_grad(q, k, v, causal: bool = True, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False):
    """Differentiable flash attention: (B, T, H, D) -> (B, T, H, D).

    Forward runs the Pallas kernel (or its documented fallbacks);
    backward recomputes through ``reference_attention`` under XLA
    autodiff — same numerics contract, no score matrix saved between
    passes."""
    return flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out, (q, k, v)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v = res

    def ref(q_, k_, v_):
        # f32 score accumulation + f32 softmax, matching the kernel's
        # forward numerics — a bf16 recompute would round the softmax
        # row-sums and skew gradients ~2% at T=128 (growing with T)
        out, _ = reference_attention_lse(q_, k_, v_, causal=causal)
        return out.astype(q_.dtype)

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention_grad.defvjp(_fa_fwd, _fa_bwd)
