"""The ``lfm2_moe`` family on the slotted generation path (models/hybrid_lm.py:
two sequential blocks a layer, the gated short convolution whose slot state is
a conv window alone, rotary QK-normed grouped-query attention on a GLOBAL
leaf, a dense gated MLP as a block of its own, routed experts with a bias and
no shared expert, a tied head) against the plain float32 reference
(benchmark/configs/ref_lfm2_moe.py), at a tiny size.

Oracles: the reference's full forward over one sequence (no cache, no slots,
no chunking) for chunked prefill and slotted decode; the reference's plain sum
over three shifted copies for the convolution's two forms; the reference's
rotation and the shift of all positions for the rotary layers; the reference's
loop over the experts for the expert layer; counts by hand for the counters;
the published ``layer_types`` for the tie between the 12-layer cut and the
model.  The v5e compiles of the cell's programs live in tests/test_hybrid_lm.py
beside the fixture that loads libtpu.
"""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import ref_lfm2_moe as ref
from nnstreamer_tpu.core.continuity import resume_signature
from nnstreamer_tpu.core.slots import PrefixCache, SlotEngine, SlotModelProtocol
from nnstreamer_tpu.models import hybrid_lm as H
from nnstreamer_tpu.models.transformer import config_resume_fields
from nnstreamer_tpu.pipeline import parse_pipeline

VOCAB, SEED, SEQ = 97, 5, 64
#: the published model's layer kinds: five periods ``c c A c`` and the
#: irregular last one ``c A c c``
PUBLISHED = ["conv", "conv", "full_attention", "conv"] * 5 + [
    "conv", "full_attention", "conv", "conv"]
#: the reference's configuration, under the published key names: one period
#: with both leading dense layers (the first 4 of the 24 layers)
REF = {
    "hidden_size": 64, "vocab_size": VOCAB, "num_hidden_layers": 4,
    "layer_types": PUBLISHED[:4], "num_dense_layers": 2, "conv_L_cache": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "rope_theta": 1000000,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "routed_scaling_factor": 1, "norm_eps": 1e-5,
}
WHOLE = {**REF, "num_hidden_layers": 24, "layer_types": PUBLISHED}


def props(**over):
    """The same configuration in the generator's ``custom=`` dialect."""
    p = {
        "arch": "lfm2_moe", "layers": ref.pattern(REF), "vocab": VOCAB, "d_model": 64,
        "heads": 4, "kv_heads": 2, "head_dim": 16, "conv": 3, "d_ff": 96,
        "rope_theta": 1000000, "experts": 8, "experts_per_tok": 2, "d_expert": 32,
        "routed_scale": 1, "eps": 1e-5, "seq": SEQ, "dtype": "float32", "seed": SEED,
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


def serve_and_compare(model, params, want, seq, n_prompt, chunk, slot, atol):
    """Chunked prefill of ``seq[:n_prompt]`` into ``slot``, then one slotted
    decode step per remaining token: every logit row against ``want``."""
    cache = model.reset_slot(model.init_cache(), np.int32(slot))
    for a in range(0, n_prompt, chunk):
        piece = seq[None, a:min(a + chunk, n_prompt)]
        cache, logits = model.prefill_fn(piece.shape[1])(
            params, cache, piece, np.int32(slot))
        np.testing.assert_allclose(
            np.asarray(logits)[0], want[a + piece.shape[1] - 1], atol=atol)
    active = np.zeros(model.slots, np.int32)
    active[slot] = 1
    step = jax.jit(model.step_logits)
    for j in range(n_prompt, len(seq)):
        tok = np.zeros(model.slots, np.int32)
        tok[slot] = seq[j]
        cache, logits = step(params, cache, tok, active)
        np.testing.assert_allclose(np.asarray(logits)[slot], want[j], atol=atol)
    return cache


# ---------------------------------------------------------------------------
# the dialect, the parameters, the state
# ---------------------------------------------------------------------------
def test_the_arch_alone_decides_what_only_the_family_decides():
    cfg = H.cfg_from_props(props())
    assert (cfg.norm, cfg.expert_act, cfg.rope_pairs) == ("rms", "silu_gated", "half")
    assert cfg.tied_head and cfg.router_bias and cfg.qk_norm and cfg.rope_global
    assert cfg.kv_counters and cfg.route_eps == 1e-6 and cfg.shared_experts == 0
    assert (cfg.conv_kernel, cfg.d_ff, cfg.routed_scale) == (3, 96, 1.0)
    assert cfg.groups == tuple("CDCD*ECE")
    assert cfg.blocks[:3] == ((("mixer", "C", "0"),), (("mixer", "D", "1"),),
                              (("mixer", "C", "2"),))
    # no key of the dialect reaches them: no mix of two families can be asked for
    assert H.cfg_from_props(props(
        qk_norm=0, rope_pairs="interleaved", rope_global=0, route_eps=0, kv_counters=0,
        tied_head=0, router_bias=0, expert_act="relu2")) == cfg
    # the other two dialects read as they did: no QK norm, no rotary on a global
    # layer, interleaved pairs, no epsilon, a shared expert
    for arch in ("nemotron_h", "cohere2_moe"):
        old = H.cfg_from_props({"arch": arch, "window": "8"})
        assert not (old.qk_norm or old.rope_global or old.route_eps or old.kv_counters)
        assert old.rope_pairs == "interleaved" and old.shared_experts >= 1 and old.d_ff == 0


@pytest.mark.parametrize("bad,why", [
    ({"d_ff": 0}, "d_ff >= 1"), ({"conv": 1}, "conv >= 2"),
    ({"layers": "CDXE"}, "one of M, E"), ({"head_dim": 15}, "even head_dim"),
    ({"shared_experts": -1}, "shared_experts:-1"),
])
def test_a_bad_configuration_is_refused_by_name(bad, why):
    with pytest.raises(ValueError, match=why):
        H.cfg_from_props(props(**bad))


def test_an_unknown_pair_layout_is_refused_by_name():
    with pytest.raises(ValueError, match="rope_pairs:quarter"):
        dataclasses.replace(H.cfg_from_props(props()), rope_pairs="quarter")


def test_the_reference_makes_the_programs_weights_without_the_program(served):
    _, params, _ = served
    assert set(params) == {"embed", "blocks", "norm_f"}       # the head is the embedding
    for i in range(len(ref.pattern(REF))):
        mine, theirs = flat(ref.part(REF, SEED, i)), flat(params["blocks"][i])
        assert mine.keys() == theirs.keys(), i
        for k, a in mine.items():
            b = theirs[k]
            if "experts" in k:   # the program pads an expert's width to whole lane tiles
                b = b[:, :a.shape[1]] if "down" in k else b[:, :, :a.shape[2]]
            assert np.array_equal(a, b), (i, k)
    for name in ("embed", "norm_f"):
        mine, theirs = flat(ref.part(REF, SEED, name)), flat(params[name])
        assert all(np.array_equal(mine[k], theirs[k]) for k in mine), name


def test_an_expert_layer_without_a_shared_expert_has_no_shared_leaves(served):
    _, params, _ = served
    moe = params["blocks"][5]["mixer"]
    assert set(moe) == {"router", "experts"} and set(moe["router"]) == {"kernel", "bias"}
    assert set(moe["experts"]) == {"gate", "up", "down"}
    # and a family with one keeps them
    old = H.init_params(H.cfg_from_props({"arch": "nemotron_h", "layers": "E"}), 0)
    assert {"shared_up", "shared_down"} <= set(old["blocks"][0]["mixer"])


def test_a_conv_layer_holds_a_window_alone_and_the_attention_layer_the_context(served):
    model, _, _ = served
    shapes = {k: {n: tuple(leaf.shape) for n, leaf in v.items()}
              for k, v in model.init_cache()["layers"].items()}
    window, kv = {"conv": (4, 2, 64)}, (4, SEQ, 32)
    # the dense MLPs and the expert layers (keys 1, 3, 5, 7) hold nothing
    assert shapes == {"0": window, "2": window, "4": {"k": kv, "v": kv}, "6": window}
    assert model.counter_names == H.COUNTER_NAMES + H.KV_COUNTER_NAMES
    assert isinstance(model, SlotModelProtocol) and not model.supports_prefix


# ---------------------------------------------------------------------------
# logits: chunked prefill, then slotted decode, against the full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt,chunk", [
    (13, 5),   # every chunk starts inside the window the last one left
    (9, 1),    # chunks shorter than the window: it is shifted, not replaced
    (8, 2),    # chunks as long as the window
    (16, 8),   # whole chunks
    (3, 8),    # one ragged chunk, shorter than the taps reach back
])
def test_chunked_prefill_then_slotted_decode_match_the_full_forward(
        served, rng, n_prompt, chunk):
    """float32 against float32 ``highest``: 1e-4 is a hundred times the
    rounding of a 64-wide model's sums and a thousandth of the logits'
    spread; a wrong tap, window row, pair or norm reads 1e-2 or more."""
    model, params, _ = served
    seq = rng.integers(0, VOCAB, (n_prompt + 6,)).astype(np.int32)
    want = np.asarray(ref.forward(ref.make_params(REF, SEED), seq, REF))
    cache = serve_and_compare(model, params, want, seq, n_prompt, chunk, 2, 1e-4)
    assert int(cache["pos"][2]) == n_prompt + 6
    assert not np.asarray(cache["pos"])[[0, 1, 3]].any()


def test_the_program_serves_the_whole_24_layer_pattern(rng):
    """The tie between the cut and the model, in the form a depth-only cut
    has: all six periods, the irregular last one, both dense layers."""
    pattern = ref.pattern(WHOLE)
    assert pattern == "CDCD*ECE" + "CECE*ECE" * 4 + "CE*ECECE" and len(pattern) == 48
    model, params, _ = H.build_slot_stream(props(layers=pattern), 2)
    seq = rng.integers(0, VOCAB, (17,)).astype(np.int32)
    want = np.asarray(ref.forward(ref.make_params(WHOLE, SEED), seq, WHOLE))
    serve_and_compare(model, params, want, seq, 11, 4, 1, 2e-4)


def test_the_cells_pattern_is_the_prefix_of_the_published_models():
    """``lfm2_8b_a1b_pp2``: 12 layers, letter for letter the first 24 letters
    of the pattern the published ``layer_types`` and ``num_dense_layers``
    give; the family's default pattern is the cell's."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark", "configs",
                        "lfm2_8b_a1b_pp2.json")
    cfg = json.load(open(path))
    whole = {**cfg, **{k: cfg["published"][k] for k in cfg["reduced"]}}
    assert whole["layer_types"] == PUBLISHED and whole["num_hidden_layers"] == 24
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert ref.pattern(whole)[:24] == ref.pattern(cfg) == cfg["pattern"]
    assert cfg["pattern"] == H.FAMILIES["lfm2_moe"]["fields"]["pattern"]
    assert cfg["layer_types"] == PUBLISHED[:12] and cfg["num_hidden_layers"] == 12
    # three whole periods: the published 1 attention layer in 4
    assert cfg["pattern"].count("*") == 3 and cfg["pattern"].count("C") == 9
    assert cfg["pattern"].count("D") == cfg["num_dense_layers"] == 2


# ---------------------------------------------------------------------------
# the short convolution: two forms and the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cut", [1, 2, 3, 7])
def test_the_convs_chunk_form_its_one_step_form_and_the_reference_agree(served, rng, cut):
    """One sequence through ``conv_mix`` whole, as two chunks cut at ``cut``
    (the second starts from the window the first left), and one step at a
    time: all three are the reference's sum over three shifted copies."""
    model, params, _ = served
    p = params["blocks"][0]["mixer"]
    x = jnp.asarray(rng.standard_normal((1, 9, 64)), jnp.float32)
    want = np.asarray(ref.short_conv(x[0], ref.part(REF, SEED, 0)["mixer"], REF))
    zero = jnp.zeros((1, 2, 64), jnp.float32)
    whole, left = H.conv_mix(p, x, zero, model.cfg)
    np.testing.assert_allclose(np.asarray(whole)[0], want, atol=1e-5)
    a, mid = H.conv_mix(p, x[:, :cut], zero, model.cfg)
    b, end = H.conv_mix(p, x[:, cut:], mid, model.cfg)
    np.testing.assert_allclose(np.concatenate([a, b], axis=1)[0], want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(end), np.asarray(left))
    state, outs = zero, []
    for t in range(9):
        out, state = H.conv_mix(p, x[:, t:t + 1], state, model.cfg)
        outs.append(np.asarray(out)[0, 0])
    np.testing.assert_allclose(np.stack(outs), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(left))
    # the window is the gated input's last two rows, B * u, nothing else
    b_, _, u_ = np.split(np.asarray(x[0] @ p["in_proj"]["kernel"]), 3, axis=-1)
    np.testing.assert_allclose(np.asarray(left)[0], (b_ * u_)[-2:], atol=1e-6)


def test_a_join_zeroes_the_window_and_an_idle_slots_window_is_bit_equal(served, rng):
    model, params, _ = served
    cache = model.init_cache()
    for slot, n in ((1, 11), (3, 7)):
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
    # an idle slot's windows come out bit-equal, both rows; its K/V leaves below
    # its position (the row AT it is rewritten harmlessly, as in every family)
    for name, after in state(cache, 3).items():
        rows = slice(0, 7) if name.startswith("4.") else ...
        np.testing.assert_array_equal(idle[name][rows], after[rows], err_msg=name)
    assert int(cache["pos"][1]) == 14 and int(gen[1]) == 3 and toks.shape == (4, 3)
    named = dict(zip(model.counter_names, np.asarray(counts).tolist()))
    # 3 steps and 3 chunks, 2 expert layers each; handed over, then zero
    assert named["gen_moe_layer_steps"] == 12 and not np.asarray(cache["counts"]).any()
    # every expert is held: every choice of a live row is local (2 a token and layer)
    assert named["gen_moe_local"] == 3 * 2 * 2 + (11 + 7) * 2 * 2
    # one attention layer; slot 1 alone is live, at positions 11, 12, 13
    assert named["gen_kv_rows_need"] == 11 + 12 + 13
    assert named["gen_kv_rows_read"] == named["gen_kv_rows_held"] == 3 * 4 * SEQ
    assert named["gen_kv_prefill_rows_need"] == 0     # the engine's to add
    cache = model.reset_slot(cache, np.int32(3))
    assert not any(r.any() for r in state(cache, 3).values())
    assert all(r.any() for r in state(cache, 1).values())


@pytest.mark.parametrize("pos,n", [(0, 8), (8, 3), (40, 8), (0, 1)])
def test_a_chunks_keys_are_counted_by_position_by_hand(served, pos, n):
    by_hand = sum(p + 1 for p in range(pos, pos + n))    # one attention layer
    assert served[0].prefill_counts(pos, n) == {"gen_kv_prefill_rows_need": by_hand}


# ---------------------------------------------------------------------------
# rotary positions on a global layer, half-split pairs, QK norm
# ---------------------------------------------------------------------------
def test_the_rotation_is_the_references_half_split_form(rng):
    x = jnp.asarray(rng.standard_normal((1, 6, 4 * 16)), jnp.float32)
    got = H.rotary(x, jnp.array([0]), 4, 1e6, "half")
    want = ref.rotary(x[0].reshape(6, 4, 16), 1e6).reshape(6, 64)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want), atol=1e-6)
    # by hand at position 3: lane i with lane i + 8, angle 3 theta^(-2i/16)
    h = np.asarray(x)[0, 3, :16]
    ang = 3.0 * 1e6 ** (-np.arange(8) * 2 / 16)
    np.testing.assert_allclose(np.asarray(got)[0, 3, :8],
                               h[:8] * np.cos(ang) - h[8:] * np.sin(ang), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got)[0, 3, 8:16],
                               h[8:] * np.cos(ang) + h[:8] * np.sin(ang), atol=1e-5)
    # and it is not the interleaved form the window layers of cohere2_moe take
    other = H.rotary(x, jnp.array([0]), 4, 1e6)
    assert np.abs(np.asarray(other) - np.asarray(got)).max() > 1e-2


@pytest.mark.parametrize("pairs", ["half", "interleaved"])
def test_scores_depend_on_the_distance_alone(rng, pairs):
    q = jnp.asarray(rng.standard_normal((1, 6, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 6, 64)), jnp.float32)

    def scores(shift):
        qr = H.rotary(q, jnp.array([shift]), 4, 1e4, pairs).reshape(6, 4, 16)
        kr = H.rotary(k, jnp.array([shift]), 4, 1e4, pairs).reshape(6, 4, 16)
        return np.asarray(jnp.einsum("qhd,khd->hqk", qr, kr))

    np.testing.assert_allclose(scores(0), scores(37), atol=1e-4)
    assert np.abs(scores(0) - np.asarray(jnp.einsum(
        "qhd,khd->hqk", q.reshape(6, 4, 16), k.reshape(6, 4, 16)))).max() > 1e-2


def test_a_global_layers_keys_are_normed_and_turned_before_the_write(served, rng):
    """What a chunk at positions 11..15 leaves in the K leaf: ``h W_k``,
    RMS-normed a head with the learned weight, turned by the ABSOLUTE
    position, at rows ``p``; V as it is.  The weights are READ: other weights,
    other rows."""
    model, params, _ = served
    p = dict(params["blocks"][4]["mixer"])
    p["q_norm"] = {"scale": jnp.asarray(rng.uniform(0.5, 1.5, (16,)), jnp.float32)}
    p["k_norm"] = {"scale": jnp.asarray(rng.uniform(0.5, 1.5, (16,)), jnp.float32)}
    h = jnp.asarray(rng.standard_normal((1, 5, 64)), jnp.float32)
    zero = jnp.zeros((1, SEQ, 32), jnp.float32)
    out, ck, cv = H.attn_mix(p, h, zero, zero, jnp.array([11]), model.cfg)
    k = np.asarray(h[0] @ p["k_proj"]["kernel"]).reshape(5, 2, 16)
    normed = k / np.sqrt((k * k).mean(-1, keepdims=True) + 1e-5) * np.asarray(p["k_norm"]["scale"])
    turned = np.asarray(ref.rotary(jnp.pad(jnp.asarray(normed), ((11, 0), (0, 0), (0, 0))), 1e6))
    np.testing.assert_allclose(np.asarray(ck)[0, 11:16], turned[11:].reshape(5, 32), atol=1e-5)
    assert not np.asarray(ck)[0, :11].any() and not np.asarray(ck)[0, 16:].any()
    np.testing.assert_array_equal(
        np.asarray(cv)[0, 11:16], np.asarray(jnp.matmul(h, p["v_proj"]["kernel"]))[0])
    # against the reference's attention with the same weights (positions 0..4)
    out0, _, _ = H.attn_mix(p, h, zero, zero, jnp.array([0]), model.cfg)
    want = ref.attention(h[0], p, {**REF, "head_dim": 16})
    np.testing.assert_allclose(np.asarray(out0)[0], np.asarray(want), atol=1e-5)
    ones = {**p, "q_norm": {"scale": jnp.ones(16)}, "k_norm": {"scale": jnp.ones(16)}}
    plain, _, _ = H.attn_mix(ones, h, zero, zero, jnp.array([0]), model.cfg)
    assert np.abs(np.asarray(plain) - np.asarray(out0)).max() > 1e-3


# ---------------------------------------------------------------------------
# the expert layer: every expert held, a bias, an epsilon, no shared expert
# ---------------------------------------------------------------------------
def test_the_expert_layer_is_the_references_loop_and_its_epsilon_is_the_published(
        served, rng):
    model, params, _ = served
    p = params["blocks"][3 + 2]["mixer"]
    x = jnp.asarray(rng.standard_normal((3, 7, 64)), jnp.float32)
    out, counts = H.moe_mix(p, x, model.cfg)
    p_ref = ref.part(REF, SEED, 5)["mixer"]
    want = ref.routed(x.reshape(21, 64), p_ref, REF)
    np.testing.assert_allclose(np.asarray(out).reshape(21, 64), np.asarray(want), atol=1e-5)
    assert int(counts[0]) == 21 * 2 and int(counts[3]) == 1    # every choice local
    # the chosen follow score + bias, the weights the scores alone, over sum + 1e-6
    ids, w = H.route(p, x.reshape(21, 64), model.cfg)
    s = np.asarray(jax.nn.sigmoid(x.reshape(21, 64) @ p["router"]["kernel"]))
    top = np.argsort(-(s + np.asarray(p["router"]["bias"])), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1), np.sort(top, -1))
    chosen = np.take_along_axis(s, np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(w), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)
    assert np.all(np.asarray(w).sum(-1) < 1.0)
    no_eps, _ = H.route(p, x.reshape(21, 64), dataclasses.replace(model.cfg, route_eps=0.0))
    np.testing.assert_array_equal(np.asarray(no_eps), np.asarray(ids))


def test_top_4_of_32_with_every_token_on_one_expert_drops_nothing(rng):
    """A router that scores every expert alike, with a bias that lifts four:
    every token picks experts 3, 9, 17 and 30, 64 tokens on each, none
    dropped, and the layer is the reference's loop."""
    cfg_ref = {**REF, "num_experts": 32, "num_experts_per_tok": 4,
               "layer_types": ["conv"], "num_dense_layers": 0}       # the pattern CE
    cfg = H.cfg_from_props(props(layers="CE", experts=32, experts_per_tok=4))
    p = H.init_params(cfg, SEED)["blocks"][1]["mixer"]
    bias = jnp.zeros((32,)).at[jnp.array([3, 9, 17, 30])].set(1.0)
    router = {"kernel": jnp.zeros_like(p["router"]["kernel"]), "bias": bias}
    x = rng.standard_normal((4, 16, 64)).astype(np.float32)
    out, counts = H.moe_mix({**p, "router": router}, jnp.asarray(x), cfg)
    assert np.asarray(counts).tolist() == [256, 4, 64, 1]
    p_ref = {**ref.part(cfg_ref, SEED, 1)["mixer"], "router": router}
    want = ref.routed(jnp.asarray(x.reshape(64, 64)), p_ref, cfg_ref)
    np.testing.assert_allclose(np.asarray(out).reshape(64, 64), np.asarray(want), atol=1e-5)
    # every weight is 0.5 / (4 x 0.5 + 1e-6)
    _, w = H.route({**p, "router": router}, jnp.asarray(x.reshape(64, 64)), cfg)
    np.testing.assert_allclose(np.asarray(w), 0.5 / (2.0 + 1e-6), rtol=1e-6)


@pytest.mark.parametrize("rows", [21, 300])
def test_the_gated_kernel_takes_every_expert_held_and_matches_the_loop(rng, rows):
    """ops/expert_ffn.py in the Pallas interpreter with ALL of the router's
    experts held (every pick local), one block and two."""
    from nnstreamer_tpu.ops.expert_ffn import touched_experts_ffn

    cfg = H.cfg_from_props(props())
    p = H.init_params(cfg, SEED)["blocks"][5]["mixer"]
    x = jnp.asarray(rng.standard_normal((rows, 64)).astype(np.float32))
    ids, w = H.route(p, x, cfg)
    gates = jnp.sum(jnp.where(ids[:, :, None] == jnp.arange(8)[None, None, :],
                              w[:, :, None], 0.0), axis=1)
    ex = p["experts"]
    got = touched_experts_ffn(x, gates, ex["up"], ex["down"], ex["gate"], interpret=True)
    want = ref.routed(x, ref.part(REF, SEED, 5)["mixer"], REF)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# ---------------------------------------------------------------------------
# the element: selection, refusals, the resume signature, the programs' names
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
    # one attention layer, chunks of 8 and 5: keys 1..13, own rows counted
    assert health["gen_kv_prefill_rows_need"] == 13 * 14 // 2


@pytest.mark.parametrize("line,why", [
    ("slots=2 prefix-cache=on", "recurrent state cannot be cut"),
    ("slots=2 mesh=tp:2", "mesh= is not served for arch:lfm2_moe"),
    ("slots=0", "arch:lfm2_moe needs slots >= 1"),
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
    with pytest.raises(NotImplementedError, match="lfm2_moe: a recurrent state"):
        model.export_prefix(None, 0, 0, 8)
    with pytest.raises(ValueError, match="arch:lfm2_moe does not shard over mesh="):
        H.build_slot_stream(props(), 2, mesh=object())


def test_the_resume_signature_covers_the_family_and_every_new_field():
    def sig(family, fields):
        return resume_signature(family, max_new=8, **fields)

    base = sig("lfm2_moe", H.resume_fields(props()))
    assert base == sig("lfm2_moe", H.resume_fields(props()))
    assert base != sig("cohere2_moe", H.resume_fields(props()))
    for key, value in (("d_ff", 64), ("conv", 4), ("rope_theta", 10000),
                       ("layers", "CD*E"), ("experts_per_tok", 3)):
        assert sig("lfm2_moe", H.resume_fields(props(**{key: value}))) != base, key
    # what the family alone decides is in it too, field by field
    cfg = H.cfg_from_props(props())
    for field, value in (("qk_norm", False), ("rope_global", False),
                         ("rope_pairs", "interleaved"), ("route_eps", 0.0),
                         ("kv_counters", False), ("shared_experts", 1)):
        other = config_resume_fields(dataclasses.replace(cfg, **{field: value}), props())
        assert sig("lfm2_moe", other) != base, field


def test_the_programs_carry_the_familys_name(served):
    model, _, _ = served
    assert model.decode_fn(4).__name__ == "nns_lfm2_moe_decode"
    assert model.prefill_fn(8).__name__ == "nns_lfm2_moe_prefill"
    assert H.FAMILIES["lfm2_moe"]["stem"] == "lfm2_moe"
