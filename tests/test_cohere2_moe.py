"""The ``cohere2_moe`` family on the slotted generation path (models/
hybrid_lm.py: parallel attention + expert blocks, window layers whose K/V
leaves hold a window written round beside a global layer that holds the
context, rotary positions on the window layers alone, gated experts, shared
experts averaged, a tied head) against the plain float32 reference
(benchmark/configs/ref_cohere2_moe.py), at a tiny size: window 8, seq 64.

Oracles: the reference's full forward over one sequence (no cache, no ring,
no chunking) for chunked prefill and slotted decode across wraps; the
reference's rotation and the shift of all positions for the rotary layers;
the uncut reference layer for the sum of eight expert shares; the whole-leaf
form of ``kv_attend_write`` for the blocked one; counts by hand for the
cache-row counters.  The kernels run in the Pallas interpreter; their v5e
compiles live in tests/test_hybrid_lm.py beside the fixture that loads libtpu.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import ref_cohere2_moe as ref
from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.continuity import resume_signature
from nnstreamer_tpu.core.slots import PrefixCache, SlotEngine, SlotModelProtocol
from nnstreamer_tpu.models import hybrid_lm as H
from nnstreamer_tpu.models import transformer as T
from nnstreamer_tpu.models.transformer import config_resume_fields
from nnstreamer_tpu.ops import decode_attention
from nnstreamer_tpu.pipeline import parse_pipeline

VOCAB, SEED, WINDOW, SEQ = 97, 5, 8, 64
#: the reference's configuration, under the published key names
REF = {
    "hidden_size": 64, "vocab_size": VOCAB, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": WINDOW, "rope_theta": 50000, "intermediate_size": 32,
    "num_experts": 8, "router_experts": 8, "expert_offset": 0, "num_experts_per_tok": 2,
    "num_shared_experts": 4, "layer_norm_eps": 1e-5, "logit_scale": 1,
}


def props(**over):
    """The same configuration in the generator's ``custom=`` dialect."""
    p = {
        "arch": "cohere2_moe", "layers": "(WE)(WE)(WE)(*E)", "vocab": VOCAB, "d_model": 64,
        "heads": 4, "kv_heads": 2, "head_dim": 16, "window": WINDOW, "rope_theta": 50000,
        "experts": 8, "experts_held": 8, "expert_offset": 0, "experts_per_tok": 2,
        "d_expert": 32, "shared_experts": 4, "d_shared": 32, "eps": 1e-5, "seq": SEQ,
        "dtype": "float32", "seed": SEED,
    }
    p.update(over)
    return {k: str(v) for k, v in p.items()}


def custom(**over):
    return ",".join(f"{k}:{v}" for k, v in props(**over).items())


@pytest.fixture(scope="module")
def served():
    model, params, max_seq = H.build_slot_stream(props(), 4)
    return model, params, max_seq


def flat(tree):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


# ---------------------------------------------------------------------------
# the dialect, the parameters, the state
# ---------------------------------------------------------------------------
def test_the_arch_alone_decides_what_only_the_family_decides():
    cfg = H.cfg_from_props(props())
    assert (cfg.norm, cfg.expert_act, cfg.shared_combine) == (
        "layer", "silu_gated", "average")
    assert cfg.tied_head and not cfg.router_bias and cfg.routed_scale == 1.0
    assert cfg.groups == ("WE", "WE", "WE", "*E")
    assert cfg.blocks[:2] == ((("mixer0", "W", "0"), ("mixer1", "E", "1")),
                              (("mixer0", "W", "2"), ("mixer1", "E", "3")))
    # no key of the dialect reaches them: no mix of two families can be asked for
    assert H.cfg_from_props(props(
        tied_head=0, norm="rms", expert_act="relu2", router_bias=1,
        shared_combine="sum")) == cfg
    # the nemotron_h dialect reads as it did: one mixer a block, its own defaults
    old = H.cfg_from_props({"arch": "nemotron_h"})
    assert old.groups == tuple("MEM*EME") and old.router_bias and old.norm == "rms"
    assert old.blocks[3] == (("mixer", "*", "3"),) and len(old.blocks) == 7


@pytest.mark.parametrize("bad,why", [
    ({"layers": "(WE"}, "one pair of parentheses"), ({"layers": "(W(E))"}, "one of M, E"),
    ({"layers": "()"}, "one of M, E"), ({"layers": "WXE"}, "one of M, E"),
    ({"window": 0}, "window >= 1"),
])
def test_a_bad_pattern_is_refused_by_name(bad, why):
    with pytest.raises(ValueError, match=why):
        H.cfg_from_props(props(**bad))


@pytest.mark.parametrize("field,value", [
    ("norm", "batch"), ("expert_act", "gelu"), ("shared_combine", "max")])
def test_a_config_with_an_unknown_name_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=f"{field}:{value}"):
        dataclasses.replace(H.cfg_from_props(props()), **{field: value})


def test_the_reference_makes_the_programs_weights_without_the_program(served):
    _, params, _ = served
    assert set(params) == {"embed", "blocks", "norm_f"}       # the head is the embedding
    for i in range(4):
        mine, theirs = flat(ref.part(REF, SEED, i)), flat(params["blocks"][i])
        assert mine.keys() == theirs.keys()
        for k, a in mine.items():
            b = theirs[k]
            if "experts" in k:   # the program pads an expert's width to whole lane tiles
                b = b[:, :a.shape[1]] if "down" in k else b[:, :, :a.shape[2]]
            assert np.array_equal(a, b), (i, k)
    for name in ("embed", "norm_f"):
        mine, theirs = flat(ref.part(REF, SEED, name)), flat(params[name])
        assert all(np.array_equal(mine[k], theirs[k]) for k in mine), name


def test_a_window_layer_holds_its_window_and_the_global_layer_the_context(served):
    model, _, _ = served
    shapes = {k: {n: tuple(leaf.shape) for n, leaf in v.items()}
              for k, v in model.init_cache()["layers"].items()}
    window, whole = (4, WINDOW, 32), (4, SEQ, 32)
    assert shapes == {"0": {"k": window, "v": window}, "2": {"k": window, "v": window},
                      "4": {"k": window, "v": window}, "6": {"k": whole, "v": whole}}
    assert model.counter_names == H.COUNTER_NAMES + H.KV_COUNTER_NAMES
    assert isinstance(model, SlotModelProtocol) and not model.supports_prefix


# ---------------------------------------------------------------------------
# logits: chunked prefill, then slotted decode, against the full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt,chunk", [
    (5, 4),    # ends inside the window
    (8, 4),    # exactly at it
    (8, 8),    # one chunk as long as the window
    (21, 8),   # past two wraps, a ragged last chunk
    (27, 4),   # past three
])
def test_chunked_prefill_then_slotted_decode_match_the_full_forward_across_wraps(
        served, rng, n_prompt, chunk):
    model, params, _ = served
    steps, slot = 12, 2       # 12 steps wrap a window of 8 while DECODING
    seq = rng.integers(0, VOCAB, (n_prompt + steps,)).astype(np.int32)
    want = np.asarray(ref.forward(ref.make_params(REF, SEED), seq, REF))
    cache = model.reset_slot(model.init_cache(), np.int32(slot))
    for a in range(0, n_prompt, chunk):
        piece = seq[None, a:min(a + chunk, n_prompt)]
        cache, logits = model.prefill_fn(piece.shape[1])(
            params, cache, piece, np.int32(slot))
        at = a + piece.shape[1] - 1
        np.testing.assert_allclose(np.asarray(logits)[0], want[at], atol=1e-4)
    active = np.zeros(4, np.int32)
    active[slot] = 1
    step = jax.jit(model.step_logits)
    for j in range(steps):
        tok = np.zeros(4, np.int32)
        tok[slot] = seq[n_prompt + j]
        cache, logits = step(params, cache, tok, active)
        np.testing.assert_allclose(
            np.asarray(logits)[slot], want[n_prompt + j], atol=1e-4)
    assert int(cache["pos"][slot]) == n_prompt + steps
    assert not np.asarray(cache["pos"])[[0, 1, 3]].any()


def test_a_chunk_longer_than_the_window_is_refused_by_name(served):
    model, params, _ = served
    toks = np.zeros((1, WINDOW + 1), np.int32)
    with pytest.raises(ValueError, match="round leaf"):
        model.prefill_fn(WINDOW + 1)(params, model.init_cache(), toks, np.int32(0))


# ---------------------------------------------------------------------------
# rotary positions: on window layers, before the cache write, and nowhere else
# ---------------------------------------------------------------------------
def test_the_rotation_is_the_references_and_scores_depend_on_the_distance_alone(rng):
    x = jnp.asarray(rng.standard_normal((1, 6, 4 * 16)), jnp.float32)
    got = H.rotary(x, jnp.array([0]), 4, 50000.0)
    want = ref.rotary(x[0].reshape(6, 4, 16), 50000.0).reshape(6, 64)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want), atol=1e-6)
    q = jnp.asarray(rng.standard_normal((1, 6, 64)), jnp.float32)

    def scores(shift):
        qr = H.rotary(q, jnp.array([shift]), 4, 50000.0).reshape(6, 4, 16)
        kr = H.rotary(x, jnp.array([shift]), 4, 50000.0).reshape(6, 4, 16)
        return np.asarray(jnp.einsum("qhd,khd->hqk", qr, kr))

    np.testing.assert_allclose(scores(0), scores(37), atol=1e-4)
    assert np.abs(scores(0) - np.asarray(jnp.einsum(
        "qhd,khd->hqk", q.reshape(6, 4, 16), x.reshape(6, 4, 16)))).max() > 1e-2


def test_a_window_layers_keys_are_turned_before_the_write_and_a_global_layers_never(
        served, rng):
    """What a chunk at positions 11..15 leaves in the leaves: the window
    layer's K rows are ``h W_k`` turned by the ABSOLUTE position, at rows
    ``p mod window``; the global layer's are ``h W_k`` as they are, at rows
    ``p`` (no positional encoding: the agreement with the reference above
    holds it to that for the outputs too)."""
    model, params, _ = served
    h = jnp.asarray(rng.standard_normal((1, 5, 64)), jnp.float32)
    for block, window, rows in ((0, True, WINDOW), (3, False, SEQ)):
        p = params["blocks"][block]["mixer0"]
        k = np.asarray(jnp.matmul(h, p["k_proj"]["kernel"]))[0]
        zero = jnp.zeros((1, rows, 32), jnp.float32)
        _, ck, cv = H.attn_mix(p, h, zero, zero, jnp.array([11]), model.cfg, window=window)
        at = (11 + np.arange(5)) % rows
        if window:
            turned = np.asarray(ref.rotary(
                jnp.pad(jnp.asarray(k), ((11, 0), (0, 0))).reshape(16, 2, 16), 50000.0))
            np.testing.assert_allclose(np.asarray(ck)[0, at], turned[11:].reshape(5, 32),
                                       atol=1e-5)
            assert np.abs(np.asarray(ck)[0, at] - k).max() > 1e-2
        else:
            np.testing.assert_array_equal(np.asarray(ck)[0, at], k)
        # V is never turned
        np.testing.assert_array_equal(
            np.asarray(cv)[0, at], np.asarray(jnp.matmul(h, p["v_proj"]["kernel"]))[0])


@pytest.mark.parametrize("ring,S,pos", [(False, 1100, [0, 7, 600, 1100]),
                                        (True, 1024, [0, 7, 1024, 2900])])
def test_the_blocked_chunk_attention_is_the_whole_leaf_form(rng, ring, S, pos, monkeypatch):
    """``_attend_blocked`` (bounded by fill, blocks of 512 rows, the last of
    a ragged leaf overlapping) against one softmax over every key with the
    mask written by position."""
    B, T_, H_, J, Dh = len(pos), 5, 4, 2, 16
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    ck, cv = mk(B, S, J * Dh), mk(B, S, J * Dh)
    q, k, v = mk(B, T_, H_ * Dh), mk(B, T_, J * Dh), mk(B, T_, J * Dh)
    pos = jnp.asarray(pos, jnp.int32)
    got = np.asarray(T._attend_blocked(ck, cv, q, k, v, pos, H_, J, ring))
    # by hand: every key with its position, one softmax
    for b in range(B):
        p0 = int(pos[b])
        rows = np.arange(S)
        kpos = (p0 - 1 - (p0 - 1 - rows) % S) if ring else np.where(rows < p0, rows, -1)
        keys = np.concatenate([np.asarray(ck[b]), np.asarray(k[b])]).reshape(-1, J, Dh)
        vals = np.concatenate([np.asarray(cv[b]), np.asarray(v[b])]).reshape(-1, J, Dh)
        kpos = np.concatenate([kpos, p0 + np.arange(T_)])
        for i in range(T_):
            see = (kpos >= 0) & (kpos <= p0 + i)
            if ring:
                see &= kpos > p0 + i - S
            for h in range(H_):
                s = keys[see, h // 2] @ np.asarray(q[b, i]).reshape(H_, Dh)[h] / 4.0
                w = np.exp(s - s.max())
                want = (w / w.sum()) @ vals[see, h // 2]
                np.testing.assert_allclose(
                    got[b, i].reshape(H_, Dh)[h], want, atol=2e-5)
    if not ring:   # and it is what the whole-leaf form gives, chosen by shape alone
        whole = T.kv_attend_write(ck, cv, q, k, v, pos, H_, n_kv_heads=J)[2]
        np.testing.assert_allclose(got, np.asarray(whole), atol=2e-5)
        monkeypatch.setattr(T, "_SCORES_BYTES", 1)
        blocked = T.kv_attend_write(ck, cv, q, k, v, pos, H_, n_kv_heads=J)[2]
        np.testing.assert_array_equal(np.asarray(blocked), got)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("ring,S,pos", [(False, 1024, [0, 7, 600, 1024]),
                                        (True, 512, [0, 7, 512, 2900])])
def test_the_chunk_kernel_is_the_blocked_form_with_its_scores_in_vmem(
        rng, ring, S, pos, dtype, tol, monkeypatch):
    """ops/chunk_attention.py in the Pallas interpreter (a TPU lowers it in
    the blocked jnp form's place): leaf blocks up to the fill, then the
    chunk's own, skipped blocks and all; chosen by ``kv_attend_write`` by
    shape, and a shape it does not take keeps the jnp form."""
    from nnstreamer_tpu.ops.chunk_attention import blocks, chunk_attention

    B, T_, H_, J, Dh = len(pos), 256, 4, 2, 128
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32).astype(dtype)
    ck, cv = mk(B, S, J * Dh), mk(B, S, J * Dh)
    q, k, v = mk(B, T_, H_ * Dh), mk(B, T_, J * Dh), mk(B, T_, J * Dh)
    pos = jnp.asarray(pos, jnp.int32)
    want = np.asarray(T._attend_blocked(ck, cv, q, k, v, pos, H_, J, ring), np.float32)
    got = chunk_attention(ck, cv, q, k, v, pos, n_heads=H_, ring=ring, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol)
    assert blocks(T_, S, J * Dh, J) == (256, 256) and blocks(1024, 16384, 1024, 8) == (256, 512)
    assert blocks(200, S, J * Dh, J) is None and blocks(T_, S, 64, J) is None
    monkeypatch.setattr(decode_attention, "INTERPRET", True)
    monkeypatch.setattr(T, "_SCORES_BYTES", 1)
    through = T.kv_attend_write(ck, cv, q, k, v, pos, H_, n_kv_heads=J, ring=ring)[2]
    np.testing.assert_array_equal(np.asarray(through, np.float32), np.asarray(got, np.float32))


@pytest.mark.parametrize("pos", [[0, 5, 128, 129, 300, 1000]])
def test_the_per_token_read_of_a_round_leaf_through_the_kernel(rng, pos, monkeypatch):
    """ops/decode_attention.py in the Pallas interpreter on a leaf written
    round: ``min(pos, rows)`` rows read, the row about to be overwritten left
    out of a full leaf, an idle slot none; against the jnp form and by hand."""
    B, S, H_, J, Dh = len(pos), 128, 4, 2, 64
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    ck, cv = mk(B, S, J * Dh), mk(B, S, J * Dh)
    q, k, v = mk(B, 1, H_ * Dh), mk(B, 1, J * Dh), mk(B, 1, J * Dh)
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.ones((B,), jnp.int32).at[1].set(0)
    plain = T.kv_attend_write(ck, cv, q, k, v, pos, H_, n_kv_heads=J, active=active,
                              ring=True)
    monkeypatch.setattr(decode_attention, "INTERPRET", True)
    kernel = T.kv_attend_write(ck, cv, q, k, v, pos, H_, n_kv_heads=J, active=active,
                               ring=True)
    live = np.asarray(active) > 0
    np.testing.assert_allclose(np.asarray(kernel[2])[live], np.asarray(plain[2])[live],
                               atol=2e-5)
    for a, b in zip(kernel[:2], plain[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the writes: row pos mod S of a live slot, nothing of an idle one
    for b in range(B):
        changed = np.flatnonzero((np.asarray(kernel[0][b]) != np.asarray(ck[b])).any(-1))
        assert changed.tolist() == ([] if b == 1 else [int(pos[b]) % S])
    # slot 4 (pos 300, full): by hand, the row of position 300 - 128 left out
    b, p0 = 4, 300
    kpos = p0 - 1 - (p0 - 1 - np.arange(S)) % S
    see = kpos > p0 - S
    assert see.sum() == S - 1 and not see[p0 % S]
    keys = np.concatenate([np.asarray(ck[b])[see], np.asarray(k[b])]).reshape(-1, J, Dh)
    vals = np.concatenate([np.asarray(cv[b])[see], np.asarray(v[b])]).reshape(-1, J, Dh)
    for h in range(H_):
        s = keys[:, h // 2] @ np.asarray(q[b, 0]).reshape(H_, Dh)[h] / 8.0
        w = np.exp(s - s.max())
        np.testing.assert_allclose(np.asarray(kernel[2])[b, 0].reshape(H_, Dh)[h],
                                   (w / w.sum()) @ vals[:, h // 2], atol=2e-5)


# ---------------------------------------------------------------------------
# slots: idle rows, joins, counters, compiles
# ---------------------------------------------------------------------------
def test_a_join_zeroes_both_kinds_and_an_idle_slot_is_bit_equal_across_a_dispatch(
        served, rng):
    model, params, _ = served
    cache = model.init_cache()
    for slot, n in ((1, 11), (3, 7)):      # slot 1 has wrapped its windows, slot 3 has not
        p = rng.integers(0, VOCAB, (1, n)).astype(np.int32)
        for a in range(0, n, 8):
            cache, _ = model.prefill_fn(len(p[0, a:a + 8]))(
                params, cache, p[:, a:a + 8], np.int32(slot))

    def state(cache, slot):
        out = {"pos": np.array(cache["pos"])[slot]}
        for key, leaves in cache["layers"].items():
            out.update({f"{key}.{n}": np.array(leaf)[slot] for n, leaf in leaves.items()})
        return out

    idle = state(cache, 3)
    assert all(r.any() for r in idle.values())
    tok = rng.integers(0, VOCAB, (4,)).astype(np.int32)
    cache, _tok, gen, toks, counts = model.decode_fn(3)(
        params, cache, tok, np.zeros(4, np.int32), np.array([0, 1, 0, 0], np.int32))
    # an idle slot's window leaves come out bit-equal, every row of them; its
    # global leaves below its position (the row AT it is rewritten harmlessly,
    # as in every family, and overwritten by its next token)
    for name, after in state(cache, 3).items():
        rows = slice(0, 7) if name.startswith("6.") else ...
        np.testing.assert_array_equal(idle[name][rows], after[rows], err_msg=name)
    assert int(cache["pos"][1]) == 14 and int(gen[1]) == 3 and toks.shape == (4, 3)
    named = dict(zip(model.counter_names, np.asarray(counts).tolist()))
    # 3 steps and 3 chunks, 4 expert layers each; handed over, then zero
    assert named["gen_moe_layer_steps"] == 24 and not np.asarray(cache["counts"]).any()
    # the decode steps' rows by hand: slot 1 alone is live, at positions 11, 12, 13:
    # a full window needs window - 1 older rows, the global layer all of them
    assert named["gen_kv_rows_need"] == 3 * 3 * (WINDOW - 1) + (11 + 12 + 13)
    # off the TPU every step reads every row it holds
    held = 3 * 4 * (3 * WINDOW + SEQ)
    assert named["gen_kv_rows_read"] == named["gen_kv_rows_held"] == held
    # the chunks' keys are the engine's to add (``prefill_counts``), no program's
    assert named["gen_kv_prefill_rows_need"] == 0
    cache = model.reset_slot(cache, np.int32(3))
    assert not any(r.any() for r in state(cache, 3).values())
    assert all(r.any() for r in state(cache, 1).values())


def test_the_need_is_min_of_position_and_window_summed_by_hand(served, rng):
    """``gen_kv_rows_need`` over a scan of slots at different fills, one idle."""
    model, params, _ = served
    cache = model.init_cache()
    cache["pos"] = jnp.asarray([3, 20, 8, 40], jnp.int32)
    active = np.array([1, 1, 1, 0], np.int32)
    _, _, _, _, counts = model.decode_fn(2)(
        params, cache, np.zeros(4, np.int32), np.zeros(4, np.int32), active)
    named = dict(zip(model.counter_names, np.asarray(counts).tolist()))
    want = sum(3 * min(p + s, WINDOW - 1) + min(p + s, SEQ)
               for p in (3, 20, 8) for s in (0, 1))
    assert named["gen_kv_rows_need"] == want


@pytest.mark.parametrize("pos,n", [(0, 8), (8, 3), (0, 7), (5, 8), (40, 8), (0, 1)])
def test_a_chunks_keys_are_counted_by_position_and_window_by_hand(served, pos, n):
    """``gen_kv_prefill_rows_need``: every query's own row counted, a window
    layer's query never more than its window."""
    by_hand = sum(3 * min(p + 1, WINDOW) + p + 1 for p in range(pos, pos + n))
    assert served[0].prefill_counts(pos, n) == {"gen_kv_prefill_rows_need": by_hand}


def test_a_prompt_whose_keys_pass_32_bits_is_counted_whole():
    """One prompt of 65536 tokens sees 2^31 + 32768 keys on a global layer:
    the count is Python's, so no counter of the device wraps."""
    seq = 65536
    assert H.keys_seen(0, seq, seq) == seq * (seq + 1) // 2 == 2 ** 31 + 32768
    chunks = sum(H.keys_seen(p, 1024, seq) for p in range(0, seq, 1024))
    assert chunks == 2 ** 31 + 32768
    # a window of 4096: 4096 * 4097 / 2 while it fills, then 4096 a query
    assert H.keys_seen(0, seq, 4096) == 4096 * 4097 // 2 + (seq - 4096) * 4096
    model = H.HybridSlotModel(H.cfg_from_props(props(seq=seq, window=4096)), 1)
    assert model.prefill_counts(0, seq)["gen_kv_prefill_rows_need"] == (
        2 ** 31 + 32768 + 3 * (4096 * 4097 // 2 + (seq - 4096) * 4096))
    old = H.HybridSlotModel(H.cfg_from_props({"arch": "nemotron_h"}), 1)
    assert old.prefill_counts(0, 8) == {}


def _serve(eng, prompts, max_new, timeout=180.0):
    for p in prompts:
        eng.submit(TensorFrame([p], meta={}), p, max_new=max_new, chunk=4)
    frames, deadline = [], time.monotonic() + timeout
    while sum(1 for f in frames if f.meta["final"]) < len(prompts):
        assert time.monotonic() < deadline, "engine drain timed out"
        frames += [f for _pad, f in eng.pop_ready()]
        eng.wait_progress(0.02)
    out = {}
    for f in sorted(frames, key=lambda f: (f.meta["stream_seq"], f.meta["chunk_index"])):
        out.setdefault(f.meta["stream_seq"], []).extend(
            np.asarray(f.tensors[0]).reshape(-1).tolist() if f.tensors else [])
    return [np.asarray(out[k], np.int32) for k in sorted(out)]


def test_the_engine_compiles_once_per_scan_length_and_chunk_length(rng):
    model, params, max_seq = H.build_slot_stream(props(), 3)
    eng = SlotEngine(model, params, max_seq=max_seq, chunk=4, prefill_chunk=8, name="cmda")
    eng.start()
    try:
        prompts = [rng.integers(0, VOCAB, (1, n)).astype(np.int32)
                   for n in (8, 16, 24, 8, 16, 24, 8)]     # chunks of 8 only
        first = _serve(eng, prompts[:3], 9)
        compiles = (model.decode_compiles, model.prefill_compiles)
        again = _serve(eng, prompts, 9)
        # 9 tokens: token 1 from the prefill, then scans of 4 and 4
        assert compiles == (1, 1)
        assert (model.decode_compiles, model.prefill_compiles) == compiles
        for a, b in zip(first, again[:3]):   # alone or under churn, the same tokens
            np.testing.assert_array_equal(a, b)
        snap = eng.snapshot()
        assert snap["gen_prefill_tokens"] == sum(p.shape[1] for p in prompts[:3] + prompts)
        assert 0 < snap["gen_kv_rows_need"] < snap["gen_kv_rows_held"]
        # every chunk's keys reached the snapshot, own rows counted
        assert snap["gen_kv_prefill_rows_need"] == sum(
            3 * min(q + 1, WINDOW) + q + 1
            for p in prompts[:3] + prompts for q in range(p.shape[1]))
    finally:
        eng.stop()
    handle = ref.make_params(REF, SEED)
    for p, got in zip(prompts[:3], first):   # past the window while decoding
        seq = np.concatenate([p[0], got])
        logits = np.asarray(ref.forward(handle, seq, REF))[p.shape[1] - 1:-1]
        assert np.all(logits.max(-1) - logits[np.arange(len(got)), got] <= 1e-4)


# ---------------------------------------------------------------------------
# the expert share (model-configs guide, section 4)
# ---------------------------------------------------------------------------
def test_eight_shares_with_attention_and_the_shared_experts_once_add_up_to_the_uncut_layer(
        rng):
    """A 16-expert layer over 8 chips, 2 experts each: every chip computes
    the residual, the attention and the shared experts alike, so they count
    once; the routed parts add up to the uncut reference layer."""
    whole = {**REF, "num_hidden_layers": 1, "layer_types": ["sliding_attention"],
             "num_experts": 16, "router_experts": 16, "num_experts_per_tok": 4}
    tokens = rng.integers(0, VOCAB, (1, 7)).astype(np.int32)
    x = ref.part(whole, SEED, "embed")["embedding"][jnp.asarray(tokens[0])]
    want = np.asarray(ref._layer(x, ref.part(whole, SEED, 0), True, whole, "f32"))
    total, local, alike = 0.0, 0, None
    for offset in range(0, 16, 2):
        cfg = H.cfg_from_props(props(layers="(WE)", experts=16, experts_held=2,
                                     expert_offset=offset, experts_per_tok=4))
        model = H.HybridSlotModel(cfg, 1)
        params = H.init_params(cfg, SEED)
        hidden, _, counts, _ = H.forward_rows(
            params, model._slotted(model.init_cache()), jnp.asarray(tokens), cfg)
        total = total + np.asarray(hidden)[0]
        local += int(counts[0])
        if alike is None:   # what every chip computes alike: a share that holds no expert
            p = params["blocks"][0]
            h = H._normed(x[None], p["norm"]["scale"], cfg)
            zero = jnp.zeros((1, WINDOW, 32))
            attn, _, _ = H.attn_mix(p["mixer0"], h, zero, zero, jnp.array([0]), cfg,
                                    window=True)
            sh = ref.shared(np.asarray(h)[0], ref.part(whole, SEED, 0)["mixer1"], whole)
            alike = np.asarray(x) + np.asarray(attn)[0] + np.asarray(sh)
    np.testing.assert_allclose(total - 7 * alike, want, atol=1e-4)
    assert local == 7 * 4      # every choice fell on exactly one share


def test_every_token_on_one_held_expert_and_nothing_is_dropped(rng):
    """A router that scores every expert alike picks experts 0 and 1 for
    every token: 64 tokens on each, both held, none dropped."""
    cfg = H.cfg_from_props(props(experts_held=4))
    p = H.init_params(cfg, SEED)["blocks"][0]["mixer1"]
    p = {**p, "router": {"kernel": jnp.zeros_like(p["router"]["kernel"])}}
    x = rng.standard_normal((4, 16, 64)).astype(np.float32)
    out, counts = H.moe_mix(p, jnp.asarray(x), cfg)
    assert np.asarray(counts).tolist() == [128, 2, 64, 1]
    ref_cfg = {**REF, "num_experts": 4}
    p_ref = jax.tree.map(jnp.asarray, ref.part(ref_cfg, SEED, 0)["mixer1"])
    p_ref["router"]["kernel"] = jnp.zeros_like(p_ref["router"]["kernel"])
    flat_x = jnp.asarray(x.reshape(64, 64))
    want = ref.routed(flat_x, p_ref, ref_cfg) + ref.shared(flat_x, p_ref, ref_cfg)
    np.testing.assert_allclose(np.asarray(out).reshape(64, 64), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("case", ["spread", "no_held_expert", "two_blocks"])
def test_the_gated_kernel_matches_the_reference_loop_over_held_experts(rng, case):
    """ops/expert_ffn.py with a third matrix per expert, in the Pallas
    interpreter: the touched experts only, every token through each."""
    from nnstreamer_tpu.ops.expert_ffn import touched_experts_ffn

    offset = 0 if case == "no_held_expert" else 4
    cfg = H.cfg_from_props(props(experts_held=4, expert_offset=offset))
    p = H.init_params(cfg, SEED)["blocks"][0]["mixer1"]
    rows = 300 if case == "two_blocks" else 21
    x = jnp.asarray(rng.standard_normal((rows, 64)).astype(np.float32))
    ids, w = H.route(p, x, cfg)
    if case == "no_held_expert":       # every choice on an absent expert
        ids = jnp.full_like(ids, 7)
    held = (ids[:, :, None] - offset) == jnp.arange(4)[None, None, :]
    gates = jnp.sum(jnp.where(held, w[:, :, None], 0.0), axis=1)
    ex = p["experts"]
    got = touched_experts_ffn(x, gates, ex["up"], ex["down"], ex["gate"], interpret=True)
    ref_cfg = {**REF, "num_experts": 4, "expert_offset": offset}
    p_ref = ref.part(ref_cfg, SEED, 0)["mixer1"]
    want = 0.0 if case == "no_held_expert" else np.asarray(ref.routed(x, p_ref, ref_cfg))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


# ---------------------------------------------------------------------------
# the element: selection, refusals, the resume signature
# ---------------------------------------------------------------------------
def test_the_generator_serves_the_family_by_custom_alone(rng):
    pipe = parse_pipeline(
        f"appsrc name=src ! tensor_generator name=gen slots=2 custom={custom()} "
        "max-new=6 chunk=3 prefill-chunk=8 ! tensor_sink name=out max-stored=64")
    frames = []
    pipe["out"].connect_new_data(frames.append)
    pipe.start()
    try:
        prompt = rng.integers(0, VOCAB, (1, 13)).astype(np.int32)
        pipe["src"].push(prompt)
        deadline = time.monotonic() + 120
        while not any(f.meta.get("final") for f in frames):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        health = pipe.health()["gen"]
    finally:
        pipe.stop()
    got = np.concatenate([np.asarray(f.tensors[0]).reshape(-1) for f in frames if f.tensors])
    seq = np.concatenate([prompt[0], got])
    logits = np.asarray(ref.forward(ref.make_params(REF, SEED), seq, REF))[12:-1]
    assert len(got) == 6 and np.all(logits.max(-1) - logits[np.arange(6), got] <= 1e-4)
    for name in H.COUNTER_NAMES + H.KV_COUNTER_NAMES:   # always on: tracing is off here
        # (a 13-token prompt's chunks are far under the grouped kernel's rows)
        assert health[name] > 0 or name.startswith("gen_moe_grouped"), name
    assert health["gen_prefill_tokens"] == 13


@pytest.mark.parametrize("line,why", [
    ("slots=2 prefix-cache=on", "window leaf written round"),
    ("slots=2 mesh=tp:2", "mesh= is not served for arch:cohere2_moe"),
    ("slots=0", "arch:cohere2_moe needs slots >= 1"),
])
def test_what_the_family_does_not_serve_is_refused_by_name(line, why):
    pipe = parse_pipeline(
        f"appsrc name=src ! tensor_generator {line} custom={custom()} ! tensor_sink name=out")
    with pytest.raises(Exception, match=why):
        pipe.start()
    pipe.stop()


def test_the_engine_and_the_factory_refuse_the_pool_and_the_mesh_by_name(served):
    model, params, max_seq = served
    with pytest.raises(ValueError, match="cannot be cut by position"):
        SlotEngine(model, params, max_seq=max_seq, prefill_chunk=8,
                   prefix_cache=PrefixCache(grain=8))
    with pytest.raises(NotImplementedError, match="leaf written round"):
        model.export_prefix(None, 0, 0, 8)
    with pytest.raises(ValueError, match="arch:cohere2_moe does not shard over mesh="):
        H.build_slot_stream(props(), 2, mesh=object())


def test_the_resume_signature_covers_the_family_and_every_new_field():
    def sig(family, fields):
        return resume_signature(family, max_new=8, **fields)

    base = sig("cohere2_moe", H.resume_fields(props()))
    assert base == sig("cohere2_moe", H.resume_fields(props()))
    assert base != sig("nemotron_h", H.resume_fields(props()))
    for key, value in (("window", 16), ("rope_theta", 10000), ("shared_experts", 2),
                       ("layers", "(WE)(*E)"), ("expert_offset", 4)):
        over = {key: value, **({"experts_held": 4} if key == "expert_offset" else {})}
        assert sig("cohere2_moe", H.resume_fields(props(**over))) != base, key
    # what the family alone decides is in it too, field by field
    cfg = H.cfg_from_props(props())
    for field, value in (("norm", "rms"), ("expert_act", "relu2"), ("shared_combine", "sum"),
                         ("router_bias", True), ("tied_head", False)):
        other = config_resume_fields(dataclasses.replace(cfg, **{field: value}), props())
        assert sig("cohere2_moe", other) != base, field


def test_the_programs_carry_the_familys_name(served):
    model, _, _ = served
    assert model.decode_fn(4).__name__ == "nns_cohere2_moe_decode"
    assert model.prefill_fn(8).__name__ == "nns_cohere2_moe_prefill"
    old, _, _ = H.build_slot_stream(
        {"arch": "nemotron_h", "layers": "E*", "seq": "16", "dtype": "float32"}, 1)
    assert old.decode_fn(4).__name__ == "nns_hybrid_decode"
    assert old.prefill_fn(8).__name__ == "nns_hybrid_prefill"
    assert old.counter_names == H.COUNTER_NAMES
