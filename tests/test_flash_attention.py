"""Flash attention Pallas kernel vs the exact-attention oracle.

Runs the real kernel in Pallas interpret mode on CPU (same kernel code
the TPU compiles); the driver's TPU bench exercises the compiled path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _env_capabilities

from nnstreamer_tpu.ops.flash_attention import flash_attention
from nnstreamer_tpu.parallel.ring_attention import reference_attention


def _qkv(B=2, T=128, H=2, D=32, dtype=jnp.float32, seed=0):
    rng = jax.random.PRNGKey(seed)
    return tuple(
        jax.random.normal(r, (B, T, H, D), dtype)
        for r in jax.random.split(rng, 3)
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
        )
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5
        )

    def test_uneven_q_k_blocks(self):
        # block_q != block_k exercises the causal diagonal-crossing blocks
        q, k, v = _qkv(T=192, seed=1)
        out = flash_attention(
            q, k, v, causal=True, block_q=64, block_k=32, interpret=True
        )
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)

    def test_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16, seed=2)
        out = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, interpret=True
        )
        ref = reference_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal=True
        )
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=0.08
        )

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize(
        "bq,bk", [(64, 64), (128, 64), (64, 128), (96, 64)]
    )
    def test_indivisible_seq_pads_and_masks(self, causal, bq, bk):
        """T not divisible by the block (incl. MIXED block sizes with T
        below the larger one): the wrapper pads K/V/Q to a common block
        multiple and the kernel masks padded columns via static
        valid_len — results must equal the reference exactly (padding
        must never leak into the softmax, and no K columns / Q rows may
        be silently dropped)."""
        q, k, v = _qkv(T=100)
        out = flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True
        )
        ref = reference_attention(q, k, v, causal=causal)
        assert out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_transformer_attn_prop(self):
        from nnstreamer_tpu.models import build

        fn, params, _, _ = build(
            "transformer",
            {"dtype": "float32", "vocab": "64", "d_model": "32",
             "heads": "2", "layers": "1", "seq": "64", "attn": "flash"},
        )
        toks = np.arange(64, dtype=np.int32) % 64
        out = np.asarray(fn(params, [toks])[0])
        assert out.shape == (64, 64) and np.isfinite(out).all()


def _forms(T):
    """How the kernel is asked to run at T tokens: by the shape rule (one
    pass for these lengths) or with streamed blocks forced."""
    blk = 128 if T > 128 else 16
    return {"one_pass": {}, "streamed": {"block_q": blk, "block_k": blk}}


class TestShortSequenceForms:
    """ViT's shapes, non-causal: all keys of a head resident and ONE pass
    (the shape rule), the streamed recurrence forced on the same data, and
    the packed-qkv entry the ViT calls — each against the float32
    ``highest`` reference."""

    @pytest.mark.parametrize("form", ["one_pass", "streamed", "packed"])
    @pytest.mark.parametrize("shape,dtype", [
        ((1, 577, 4, 64), jnp.bfloat16),   # the stream cell's tokens, B = 1
        ((2, 197, 3, 64), jnp.float32),    # ViT-224: 192 lanes, no head pair
        ((1, 17, 2, 16), jnp.float32),     # a test-sized ViT
    ])
    def test_matches_float32_reference(self, shape, dtype, form):
        from nnstreamer_tpu.ops.flash_attention import (
            _blocks, flash_attention_qkv)

        B, T, H, D = shape
        q, k, v = _qkv(B, T, H, D, dtype, seed=T)
        if form == "packed":
            qkv = jnp.concatenate(
                [x.reshape(B, T, H * D) for x in (q, k, v)], axis=-1)
            out = flash_attention_qkv(qkv, H, False, True, True).reshape(shape)
        else:
            kw = _forms(T)[form]
            nk = -(-T // _blocks(T, T, False, **kw)[1])
            assert (nk == 1) == (form == "one_pass")
            out = flash_attention(q, k, v, causal=False, interpret=True, **kw)
        assert out.dtype == dtype and out.shape == shape
        with jax.default_matmul_precision("highest"):
            ref = reference_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)), causal=False)
        # bf16: the output's own rounding (2^-9 of values up to ~1)
        tol = 8e-3 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), atol=tol)

    @pytest.mark.parametrize("form", ["one_pass", "streamed"])
    def test_padded_columns_carry_no_weight(self, form):
        """577 keys in a 640-wide block: with every value 1 a row's output
        is the sum of its probabilities over the TRUE keys over the sum
        over ALL columns — exactly 1 only if the overhang weighs nothing;
        and the lse is the log-sum-exp over the 577."""
        from nnstreamer_tpu.ops.flash_attention import flash_attention_lse

        q, k, _ = _qkv(1, 577, 2, 64, seed=7)
        v = jnp.ones_like(q)
        kw = _forms(577)[form] or {"block_q": None, "block_k": None}
        out, lse = flash_attention_lse(
            q, k, v, causal=False, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(out), 1.0, atol=1e-6)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / 8.0
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(s, axis=-1)),
            atol=2e-5)

    @pytest.mark.parametrize("args,want", [
        ((577, 577, False), (592, 640)),      # ViT-L/16-384: one block
        ((197, 197, False), (208, 256)),
        ((17, 17, False), (32, 32)),
        ((1024, 1024, False), (512, 1024)),   # rows bounded by the scores
        ((1025, 1025, False), (128, 128)),    # past VMEM: streamed
        ((577, 577, True), (128, 128)),       # causal keeps its blocks
        ((32, 577, False), (32, 640)),        # few rows, resident keys
        ((100, 100, False, 96, 64), (96, 64)),  # a given block is kept
        ((64, 32, False, 128, 128), (64, 32)),  # never past the sequence
    ])
    def test_blocks_follow_the_shape(self, args, want):
        from nnstreamer_tpu.ops.flash_attention import _blocks

        assert _blocks(*args) == want

    def test_heads_are_taken_in_whole_lane_tiles(self):
        from nnstreamer_tpu.ops.flash_attention import _head_group

        assert _head_group(16, 64) == 2     # ViT-L: pairs, 128 lanes
        assert _head_group(8, 128) == 1
        assert _head_group(4, 32) == 4
        assert _head_group(3, 64) == 3      # 192 lanes: the whole width


class TestFlashAttentionLse:
    """flash_attention_lse: the (out, lse) pair whose exact two-partial
    merge composes the kernel across ring hops (sequence parallelism)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_lse(self, causal):
        from nnstreamer_tpu.ops.flash_attention import (
            flash_attention_lse,
            reference_attention_lse,
        )

        q, k, v = _qkv(T=64, seed=3)
        out, lse = flash_attention_lse(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
        )
        ref_out, ref_lse = reference_attention_lse(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_out), atol=3e-5
        )
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(ref_lse), atol=3e-5
        )

    def test_split_key_merge_is_exact(self):
        """Two disjoint-key partials merged by the (out, lse) recurrence
        must equal attention over the concatenated keys — the ring-hop
        contract in isolation."""
        from nnstreamer_tpu.ops.flash_attention import (
            flash_attention_lse,
            reference_attention_lse,
        )

        q, k, v = _qkv(T=64, seed=4)
        k1, k2 = k[:, :32], k[:, 32:]
        v1, v2 = v[:, :32], v[:, 32:]
        o1, l1 = flash_attention_lse(q, k1, v1, causal=False,
                                     block_q=32, block_k=32, interpret=True)
        o2, l2 = flash_attention_lse(q, k2, v2, causal=False,
                                     block_q=32, block_k=32, interpret=True)
        lse = jnp.logaddexp(l1, l2)
        a1 = jnp.exp(l1 - lse).transpose(0, 2, 1)[..., None]
        a2 = jnp.exp(l2 - lse).transpose(0, 2, 1)[..., None]
        merged = o1.astype(jnp.float32) * a1 + o2.astype(jnp.float32) * a2
        want, _ = reference_attention_lse(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(merged), np.asarray(want), atol=3e-5
        )


@pytest.mark.skipif(
    not _env_capabilities.spmd_stack_ok(),
    reason="jax lacks the shard_map feature set (check_vma/pvary/pallas "
    "replication rule) the mesh ring composition needs",
)
class TestRingFlash:
    """ring_attention(use_flash=True): the Pallas kernel as the per-hop
    block primitive, exact across the sp ring (long-context composition)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_on_mesh(self, causal):
        from jax.sharding import Mesh

        from nnstreamer_tpu.parallel.ring_attention import ring_attention

        devs = np.array(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("dp", "sp"))
        q, k, v = _qkv(B=2, T=32, H=2, D=8, seed=5)
        out = ring_attention(
            q, k, v, mesh, causal=causal, use_flash=True, interpret=True
        )
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5
        )

    def test_flash_and_jnp_rings_agree_bf16(self):
        from jax.sharding import Mesh

        from nnstreamer_tpu.parallel.ring_attention import ring_attention

        devs = np.array(jax.devices()[:4]).reshape(1, 4)
        mesh = Mesh(devs, ("dp", "sp"))
        q, k, v = _qkv(B=1, T=32, H=2, D=8, dtype=jnp.bfloat16, seed=6)
        a = ring_attention(q, k, v, mesh, causal=True, use_flash=True,
                           interpret=True)
        b = ring_attention(q, k, v, mesh, causal=True, use_flash=False)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-2, rtol=2e-2,
        )


class TestFlashAttentionGrad:
    """flash_attention_grad: kernel forward, recompute backward — grads
    must match full XLA autodiff through the reference."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        from nnstreamer_tpu.ops.flash_attention import flash_attention_grad

        q, k, v = _qkv(B=1, T=32, H=2, D=8, seed=9)

        def loss_flash(q, k, v):
            o = flash_attention_grad(q, k, v, causal, 16, 16, True)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = reference_attention(q, k, v, causal=causal).astype(q.dtype)
            return jnp.sum(o * o)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5
            )

    def test_forward_value_is_kernel_output(self):
        from nnstreamer_tpu.ops.flash_attention import (
            flash_attention,
            flash_attention_grad,
        )

        q, k, v = _qkv(B=1, T=32, H=2, D=8, seed=10)
        a = flash_attention_grad(q, k, v, True, 16, 16, True)
        b = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                            interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
