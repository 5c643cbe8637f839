"""Flash attention as a Pallas TPU kernel (single-device block).

The MXU-native attention inner loop for the transformer family: the
(Tq x Tk) score matrix never goes to HBM — scores live in VMEM, float32,
and only Q, K, V and the output cross the memory bus.  Pattern references:
Dao et al. FlashAttention; the public jax pallas attention examples
(PAPERS.md / SNIPPETS.md).

ONE kernel, its form read from the shape (``_blocks``):

* **short sequences** (all keys of a head fit in VMEM beside their
  scores: ViT's 197 or 577 tokens): the key axis of the grid has one
  step and the softmax is computed once — max, ``exp``, sum, value
  product — with no running-max rescale;
* **long sequences**: Q blocks stream over 128-wide K/V blocks with the
  online-softmax recurrence, the running max, sum and accumulator in VMEM
  scratch across the sequential key axis.

Q, K and V are read where the projections left them, ``(B, T, H*D)`` rows
(or the packed ``(B, T, 3*H*D)`` of a fused qkv projection, each third
addressed by its column blocks), and the output is written in
``(B, T, H*D)``: no transpose and no pad in HBM.  A program takes as many
heads as fill whole 128-lane tiles (two heads of 64) and tells them apart
by lane masks: a 128-deep MXU pass costs the same with 64 live lanes as a
64-deep one, and nothing is shuffled across lanes.  A sequence that does
not fill its last block overhangs the array; the rows past the end are
zeroed and their columns masked inside the program (static lengths).

This is the intra-device complement of the sequence-parallel layers:
``parallel/ring_attention.py`` shards T across chips and rotates K/V;
each device's local block product is exactly what this kernel computes.

``flash_attention(q, k, v)`` takes (B, T, H, D) like the rest of the
stack; ``flash_attention_qkv(qkv, n_heads)`` takes the packed projection.
A program lowered for a TPU runs the kernel; every other platform lowers
the fused-XLA reference (``lax.platform_dependent``: the choice follows
the device the program is compiled for, and nothing on a TPU declines the
kernel quietly — a shape it cannot take raises).  ``interpret=True``
(tests only) runs the kernel in the Pallas interpreter instead.
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
_LANES = 128      # a lane tile; running max / sum live lane-replicated
# One pass while all keys of a head sit in VMEM beside a (block_q, keys)
# float32 score block, its exp and the bf16 copy of it: 512 x 1024 scores
# are 5 MB of them, inside the 16 MiB a program may use on every TPU so far
# (ViT-L/16-384: all 577 -> 640 rows x 640 keys in one block).
_ONE_PASS_KEYS = 1024
_ONE_PASS_SCORES = 512 * 1024


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, heads: int,
                  head_dim: int, block_q: int, block_k: int, kv_len: int,
                  causal: bool, scale: float, fold_scale: bool,
                  one_pass: bool, with_lse: bool):
    """One (batch, head group, q-block, kv-block) program.

    q_ref/o_ref (block_q, W) and k_ref/v_ref (block_k, W) hold the W =
    heads * head_dim columns of this program's heads.  ``one_pass``: the
    kv axis has one step, the softmax is taken whole.  Otherwise the kv
    axis is the innermost, sequential grid axis: per head the running max
    ``m`` and sum ``l`` (both (block_q, 128), lane-replicated) and the
    shared f32 accumulator persist in VMEM scratch across it; the first kv
    step initializes them, the last one normalizes into ``o_ref``.
    ``kv_len`` is the true key count (static): where it does not fill the
    last block, that block's rows past it are zeroed and their columns
    masked.
    """
    lse_ref = rest[0] if with_lse else None
    qi, ki = pl.program_id(2), pl.program_id(3)
    W = heads * head_dim
    ragged = kv_len % block_k != 0
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def in_head(h):
        return (lane >= h * head_dim) & (lane < (h + 1) * head_dim)

    def scores():
        """Per head: the masked float32 scores and the value block."""
        k, v = k_ref[...], v_ref[...]
        if ragged:
            # past the array's end the buffer holds anything: 0 * NaN
            row = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            k = jnp.where(row < kv_len, k, jnp.zeros_like(k))
            v = jnp.where(row < kv_len, v, jnp.zeros_like(v))
        q = q_ref[...]
        if fold_scale:  # 1/sqrt(D) on (block_q, W), not on the scores
            q = (q * scale).astype(q.dtype)
        if causal or ragged:
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
        if causal:
            visible = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0) >= k_pos
        if ragged:
            pad_bias = jnp.where(k_pos < kv_len, 0.0, _NEG_INF)
        for h in range(heads):
            qh = jnp.where(in_head(h), q, jnp.zeros_like(q)) if heads > 1 else q
            # q . k^T on the MXU in the input dtype, f32 accumulation
            s = lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (block_q, block_k)
            if not fold_scale:
                s = s * scale
            if causal:
                s = jnp.where(visible, s, _NEG_INF)
            if ragged:
                s = s + pad_bias
            yield h, s, v

    def put(whole, h, part):
        """``whole`` with head h's lanes taken from ``part``."""
        return part if whole is None else jnp.where(in_head(h), part, whole)

    if one_pass:
        out = None
        for h, s, v in scores():
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)  # >= 1: the max's own exp
            # normalized after the value product: (block_q, W) multiplies
            out = put(out, h, jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            ) * (1.0 / l))
            if with_lse:
                lse_ref[h] = m + jnp.log(l)
        o_ref[...] = out.astype(o_ref.dtype)
        return

    m_scr, l_scr, acc_scr = rest[-3:]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _step():
        acc = acc_scr[...]
        new = None
        for h, s, v in scores():
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            m_scr[h] = m_new
            new = put(new, h, acc * alpha[:, :1] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32))
        acc_scr[...] = new

    if causal:
        # kv blocks strictly above the diagonal contribute nothing
        pl.when(ki * block_k <= qi * block_q + (block_q - 1))(_step)
    else:
        _step()

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        acc = acc_scr[...]
        out = None
        for h in range(heads):
            l = l_scr[h][:, :1]
            out = put(out, h, acc * (1.0 / jnp.maximum(l, 1e-30)))
            if with_lse:
                # per-row log-sum-exp of the (masked) scores: the cross-block
                # merge statistic for ring attention (sequence parallelism);
                # fully-masked rows keep a large-negative lse (l == 0)
                lse_ref[h] = jnp.where(
                    l > 0.0,
                    m_scr[h][:, :1] + jnp.log(jnp.maximum(l, 1e-30)),
                    _NEG_INF)
        o_ref[...] = out.astype(o_ref.dtype)


def _head_group(n_heads: int, head_dim: int) -> int:
    """Heads per program: the fewest whose columns fill whole 128-lane
    tiles (two heads of 64); where no count does, all of them — a block
    as wide as the array is legal at any width."""
    for g in range(1, n_heads + 1):
        if n_heads % g == 0 and (g * head_dim) % _LANES == 0:
            return g
    return n_heads


def _blocks(Tq: int, Tk: int, causal: bool, block_q=None, block_k=None):
    """(block_q, block_k) from the sequence lengths; a block given by the
    caller is kept.  Keys: up to ``_ONE_PASS_KEYS`` of them (rounded up to
    whole lane tiles) are ONE block — the one-pass form — and a longer
    sequence, or a causal one (whose blocks above the diagonal are
    skipped), streams 128-wide blocks.  Rows beside resident keys: as many
    as keep the score block within ``_ONE_PASS_SCORES``, the whole sequence
    if it fits (measured on a v5e at 577 tokens and batch 128: 2.7 ms a
    layer with all rows in one block, 3.3 in two, nearly twice in five);
    128 otherwise.  A block never exceeds its rounded sequence; the last
    block may overhang the array."""
    def up(n, to):
        return -(-n // to) * to

    # keys are the scores' lanes: whole lane tiles; rows need only sublanes
    keys = up(Tk, _LANES) if Tk > _LANES else up(Tk, 16)
    if block_k is None:
        block_k = keys if not causal and keys <= _ONE_PASS_KEYS else _LANES
    block_k = min(block_k, keys)
    if block_q is None:
        block_q = _LANES
        if block_k >= Tk:
            block_q = max(
                _LANES, _ONE_PASS_SCORES // block_k // _LANES * _LANES)
    return min(block_q, up(Tq, 16)), block_k


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "head_dim", "cols", "causal", "block_q",
                     "block_k", "with_lse", "interpret"),
)
def _flash_call(q, k, v, *, n_heads: int, head_dim: int, cols=(0, 0, 0),
                causal: bool, block_q=None, block_k=None,
                with_lse: bool = False, interpret: bool = False):
    """(B, Tq, ·), (B, Tk, ·), (B, Tk, ·) -> (B, Tq, H*D) [+ (B, H, Tq, 1)
    f32 lse]; grid over (B, head groups, q blocks, kv blocks).

    Head h of q lives in columns ``(cols[0] * H + h) * D`` on (``cols`` in
    units of H*D: (0, 0, 0) for three arrays of width H*D, (0, 1, 2) for
    one packed qkv array passed three times).  Tk may differ from Tq (ring
    hops / partial-key calls) — causal requires Tq == Tk (aligned
    positions).  Jitted so that a model's layers, which call it with the
    same shapes, trace and lower the kernel once a program and not once a
    layer (24 of them cost ViT-L a second a program before any compile)."""
    B, Tq = q.shape[:2]
    Tk = k.shape[1]
    if causal and Tq != Tk:
        # ValueError, not assert: survives python -O — a misaligned direct
        # caller must fail loud, never silently mis-mask
        raise ValueError(
            f"causal flash needs aligned q/k positions (Tq={Tq}, Tk={Tk})"
        )
    G = _head_group(n_heads, head_dim)
    W, HG = G * head_dim, n_heads // G
    bq, bk = _blocks(Tq, Tk, causal, block_q, block_k)
    nq, nk = -(-Tq // bq), -(-Tk // bk)
    scale = 1.0 / math.sqrt(head_dim)
    kern = functools.partial(
        _flash_kernel, heads=G, head_dim=head_dim, block_q=bq, block_k=bk,
        kv_len=Tk, causal=causal, scale=scale,
        # folded into q only where that rounds nothing: a power of two, or
        # float32 operands
        fold_scale=(math.frexp(scale)[0] == 0.5
                    or q.dtype == jnp.float32),
        one_pass=nk == 1, with_lse=with_lse,
    )
    # under shard_map (ring hops) outputs must declare their varying
    # mesh axes (vma typing); inherit from the traced input
    vma = getattr(q.aval, "vma", None) or frozenset()
    cq, ck, cv = (c * HG for c in cols)

    out_shape = [jax.ShapeDtypeStruct(
        (B, Tq, n_heads * head_dim), q.dtype, vma=vma)]
    # None squeezes the batch dim out of the kernel refs
    out_specs = [pl.BlockSpec((None, bq, W), lambda b, g, i, j: (b, i, g))]
    if with_lse:
        # trailing length-1 lane dim keeps the ref's minor dims 2-D
        out_shape.append(jax.ShapeDtypeStruct(
            (B, n_heads, Tq, 1), jnp.float32, vma=vma))
        out_specs.append(
            pl.BlockSpec((None, G, bq, 1), lambda b, g, i, j: (b, g, i, 0)))
    scratch = [] if nk == 1 else [
        pltpu.VMEM((G, bq, _LANES), jnp.float32),  # running max, per head
        pltpu.VMEM((G, bq, _LANES), jnp.float32),  # running sum, per head
        pltpu.VMEM((bq, W), jnp.float32),          # accumulator
    ]
    res = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=(B, HG, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, W), lambda b, g, i, j: (b, i, cq + g)),
            pl.BlockSpec((None, bk, W), lambda b, g, i, j: (b, j, ck + g)),
            pl.BlockSpec((None, bk, W), lambda b, g, i, j: (b, j, cv + g)),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="nns_flash_attention",
    )(q, k, v)
    return res if with_lse else res[0]


def _rows(x):
    # (B, T, H, D) -> (B, T, H*D): the projection's own rows, a bitcast
    return x.reshape(x.shape[0], x.shape[1], -1)


def _reference(q, k, v, causal):
    from ..parallel.ring_attention import reference_attention

    return reference_attention(q, k, v, causal=causal).astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q=None,
                    block_k=None, interpret: bool = False):
    """Exact attention, (B, T, H, D) -> (B, T, H, D).

    Lowered for a TPU: the Pallas kernel, its blocks from the shape
    (``_blocks``) unless given.  Lowered for anything else: the fused-XLA
    reference path (same numerics contract), unless ``interpret=True``
    asks for the kernel in the Pallas interpreter — TESTS only, orders of
    magnitude slower than XLA.
    """
    B, T, H, D = q.shape

    def kernel(q, k, v):
        return _flash_call(
            _rows(q), _rows(k), _rows(v), n_heads=H, head_dim=D,
            causal=causal, block_q=block_q, block_k=block_k,
            interpret=bool(interpret)).reshape(B, T, H, D)

    if interpret:
        return kernel(q, k, v)
    return lax.platform_dependent(
        q, k, v, tpu=kernel,
        default=functools.partial(_reference, causal=causal))


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
    Platform choice as in :func:`flash_attention`.
    """
    B, T, H, D = q.shape

    def kernel(q, k, v):
        out, lse = _flash_call(
            _rows(q), _rows(k), _rows(v), n_heads=H, head_dim=D,
            causal=causal, block_q=block_q, block_k=block_k,
            with_lse=True, interpret=bool(interpret))
        return out.reshape(B, T, H, D), lse.reshape(B, H, T)

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
# parallel/ring_attention.py instead).  The model zoo calls these entry
# points (``flash_attention_grad``, ``flash_attention_qkv``);
# inference-only code may call flash_attention.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_grad(q, k, v, causal: bool = True, block_q=None,
                         block_k=None, interpret: bool = False):
    """Differentiable flash attention: (B, T, H, D) -> (B, T, H, D).

    Forward runs the Pallas kernel (or its documented fallbacks);
    backward recomputes through ``reference_attention`` under XLA
    autodiff — same numerics contract, no score matrix saved between
    passes."""
    return flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _fa_fwd(q, k, v, *static):
    return flash_attention_grad(q, k, v, *static), (q, k, v)


def _recompute(q, k, v, causal):
    # f32 score accumulation + f32 softmax, matching the kernel's forward
    # numerics — a bf16 recompute would round the softmax row-sums and
    # skew gradients ~2% at T=128 (growing with T)
    return reference_attention_lse(q, k, v, causal=causal)[0].astype(q.dtype)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    return jax.vjp(functools.partial(_recompute, causal=causal), *res)[1](g)


flash_attention_grad.defvjp(_fa_fwd, _fa_bwd)


def _split_heads(qkv, n_heads):
    B, T, C = qkv.shape
    return [x.reshape(B, T, n_heads, C // (3 * n_heads))
            for x in jnp.split(qkv, 3, axis=-1)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def flash_attention_qkv(qkv, n_heads: int, causal: bool = False,
                        single_device: bool = True, interpret: bool = False):
    """Attention over a packed projection: (B, T, 3*H*D) -> (B, T, H*D),
    differentiable (recompute backward, as ``flash_attention_grad``).

    The kernel reads q, k and v out of ``qkv`` by column blocks, so the
    projection's output is never split or copied in HBM (where a head
    group is not whole lane tiles the thirds are split first).
    ``single_device=False`` — the caller compiles for a mesh, where a
    Mosaic call cannot be partitioned — keeps to the reference on every
    platform."""
    B, T, C = qkv.shape
    D = C // (3 * n_heads)

    def kernel(qkv):
        kw = dict(n_heads=n_heads, head_dim=D, causal=causal,
                  interpret=bool(interpret))
        if (_head_group(n_heads, D) * D) % _LANES == 0:
            return _flash_call(qkv, qkv, qkv, cols=(0, 1, 2), **kw)
        return _flash_call(*jnp.split(qkv, 3, axis=-1), **kw)

    def reference(qkv):
        return _reference(*_split_heads(qkv, n_heads), causal).reshape(
            B, T, C // 3)

    if interpret:
        return kernel(qkv)
    if not single_device:
        return reference(qkv)
    return lax.platform_dependent(qkv, tpu=kernel, default=reference)


def _fa_qkv_fwd(qkv, *static):
    return flash_attention_qkv(qkv, *static), qkv


def _fa_qkv_bwd(n_heads, causal, single_device, interpret, qkv, g):
    def ref(x):
        return _recompute(*_split_heads(x, n_heads), causal).reshape(g.shape)

    return jax.vjp(ref, qkv)[1](g)


flash_attention_qkv.defvjp(_fa_qkv_fwd, _fa_qkv_bwd)
