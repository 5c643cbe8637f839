"""The fill-bounded decode-attention kernel (ops/decode_attention.py) in the
Pallas interpreter: against the dense float32 oracle of
``test_slot_cache_path.py`` and against the jnp form of ``kv_attend_write``
it stands in for, over per-slot fills at every edge of a row block, bf16 and
float32 leaves, multi-head and grouped-query shapes.  (The Mosaic lowering at
the cells' widths is compiled for a described v5e in ``test_hybrid_lm.py``,
the file that holds the libtpu fixture.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models.transformer import kv_attend_write
from nnstreamer_tpu.ops import decode_attention as da
from test_slot_cache_path import _dense_attention

S = 384           # three row blocks of 128 at the widths below
SHAPES = {"mha": (4, 4, 32), "gqa": (8, 2, 64)}  # H, J, Dh: W = 128 lanes
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def _operands(rng, B, H, J, Dh, dtype):
    mk = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    W, D = J * Dh, H * Dh
    return mk(B, S, W), mk(B, S, W), mk(B, 1, D), mk(B, 1, W), mk(B, 1, W)


def _oracle(ck, cv, q, k, v, n, H, J):
    """The dense float32 attention, every KV head repeated for its group."""
    def wide(a):
        B, T, W = a.shape
        a = np.asarray(a, np.float32).reshape(B, T, J, W // J)
        return np.repeat(a, H // J, axis=2).reshape(B, T, -1)

    return _dense_attention(wide(ck), wide(cv), q, wide(k), wide(v), n, H)


def _xla_form(ck, cv, q, k, v, pos, H, J, active=None):
    # off a TPU, and not forced, kv_attend_write IS the jnp form
    return kv_attend_write(ck, cv, q, k, v, jnp.asarray(pos), H,
                           n_kv_heads=J, active=active)[2]


def test_the_block_follows_the_leaf_and_odd_shapes_are_declined():
    assert da.block_rows(S, 128, 2) == 128            # 384 = 3 x 128
    assert da.block_rows(1024, 1280, 2) == 128        # the dense cell's leaf
    assert da.block_rows(4096, 256, 2) == 512         # the hybrid cell's
    assert da.block_rows(1024, 96, 2) is None         # not whole lane tiles
    assert da.block_rows(200, 128, 2) is None         # no block divides it
    with pytest.raises(ValueError, match="does not take"):
        z = jnp.zeros((1, 200, 128))
        da.decode_attention(z, z, z[:, :1], z[:, :1], z[:, :1],
                            jnp.zeros((1,), jnp.int32), n_heads=1,
                            interpret=True)


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_every_fill_matches_the_dense_oracle_and_the_xla_form(rng, shape, dtype):
    """Fills 0, 1, block - 1, block, block + 1, max_seq - 1 and max_seq side
    by side: float32 in, so nothing but the leaf's own dtype is rounded."""
    H, J, Dh = shape
    block = da.block_rows(S, J * Dh, jnp.dtype(dtype).itemsize)
    n = np.asarray([0, 1, block - 1, block, block + 1, S - 1, S], np.int32)
    ck, cv, q, k, v = _operands(rng, len(n), H, J, Dh, dtype)
    f32 = lambda a: a.astype(jnp.float32)
    got = da.decode_attention(ck, cv, f32(q), f32(k), f32(v), jnp.asarray(n),
                              n_heads=H, interpret=True)
    assert got.shape == q.shape and got.dtype == jnp.float32
    want = _oracle(ck, cv, q, k, v, n, H, J)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=5e-6)
    xla = _xla_form(ck, cv, f32(q), f32(k), f32(v), n, H, J)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla), rtol=0, atol=5e-6)


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_the_output_is_rounded_once_to_the_cache_dtype(rng, shape, dtype):
    """q, k and v in the cache dtype, as the models pass them."""
    H, J, Dh = shape
    n = np.asarray([5, 200, S], np.int32)
    ck, cv, q, k, v = _operands(rng, len(n), H, J, Dh, dtype)
    got = da.decode_attention(ck, cv, q, k, v, jnp.asarray(n), n_heads=H,
                              interpret=True)
    assert got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-6
    np.testing.assert_allclose(
        np.asarray(got, np.float32), _oracle(ck, cv, q, k, v, n, H, J),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_an_idle_slot_beside_live_ones_reads_nothing(rng, shape, monkeypatch):
    """Through ``kv_attend_write`` with the kernel forced: slot 1 is filled
    to 300 and NOT active.  The live slots read as if it were not there
    (the oracle and the jnp form agree); its own output is the new row's
    value alone and is not compared with anything that reads its rows."""
    H, J, Dh = shape
    pos = np.asarray([130, 300, 17, 0], np.int32)
    active = jnp.asarray([1, 0, 1, 1], jnp.int32)
    ck, cv, q, k, v = _operands(rng, 4, H, J, Dh, jnp.bfloat16)
    xla = _xla_form(ck, cv, q, k, v, pos, H, J, active)
    monkeypatch.setattr(da, "INTERPRET", True)
    nk, nv, got = kv_attend_write(ck, cv, q, k, v, jnp.asarray(pos), H,
                                  n_kv_heads=J, active=active)
    live = np.asarray(active) > 0
    got, want = np.asarray(got, np.float32), _oracle(ck, cv, q, k, v, pos, H, J)
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        got[live], np.asarray(xla, np.float32)[live], rtol=2e-2, atol=2e-2)
    alone = _oracle(ck, cv, q, k, v, np.zeros(4, np.int32), H, J)
    np.testing.assert_allclose(got[1], alone[1], rtol=2e-2, atol=2e-2)
    # the write is the same row scatter: the idle slot's frozen row too
    for new, rows in ((nk, k), (nv, v)):
        for b in range(4):
            np.testing.assert_array_equal(
                np.asarray(new[b, pos[b]], np.float32),
                np.asarray(rows[b, 0], np.float32))


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_rows_above_the_fill_are_never_read(rng, shape):
    """Every row at or above ``n[b]`` is NaN in K and in V, in the slot's
    last block and in the blocks past it: no NaN reaches an output, and
    the outputs are the oracle's over the rows below."""
    H, J, Dh = shape
    n = np.asarray([0, 1, 127, 128, 129, 300, S], np.int32)
    ck, cv, q, k, v = _operands(rng, len(n), H, J, Dh, jnp.bfloat16)
    above = jnp.arange(S)[None, :, None] >= jnp.asarray(n)[:, None, None]
    ck_nan, cv_nan = (jnp.where(above, jnp.nan, c) for c in (ck, cv))
    got = da.decode_attention(ck_nan, cv_nan, q, k, v, jnp.asarray(n),
                              n_heads=H, interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got, _oracle(ck, cv, q, k, v, n, H, J), rtol=2e-2, atol=2e-2)


def test_the_rows_a_step_covers_are_whole_blocks_of_live_slots():
    leaf = jax.ShapeDtypeStruct((4, S, 128), jnp.bfloat16)
    pos = jnp.asarray([0, 1, 129, 5000], jnp.int32)
    n = da.live_rows(pos, jnp.asarray([1, 1, 1, 1]), S)
    assert list(np.asarray(n)) == [0, 1, 129, S]
    n = da.live_rows(pos, jnp.asarray([1, 1, 0, 1]), S)
    assert list(np.asarray(n)) == [0, 1, 0, S]
    # off a TPU the read is not bounded: every row of the leaf
    assert int(da.rows_read(n, leaf)) == 4 * S
    odd = jax.ShapeDtypeStruct((4, 200, 128), jnp.bfloat16)
    assert int(da.rows_read(n, odd)) == 4 * 200
