"""The hybrid decoder family (models/hybrid_lm.py: Mamba-2, grouped-query
attention and routed experts chosen by a pattern string) on the slotted
generation path, against the plain float32 reference
(benchmark/configs/ref_nemotron_h.py), at a tiny size with every layer kind.

Oracles: the reference's full forward over one sequence (no cache, no slots,
no chunking) for chunked prefill and slotted decode; the step recurrence for
the chunked scan; a stream served alone for a stream served under churn; the
uncut reference layer for the sum of two expert shares; plain attention for
the grouped-query cache step.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.configs import ref_nemotron_h as ref
from nnstreamer_tpu.core.buffer import TensorFrame
from nnstreamer_tpu.core.continuity import resume_signature
from nnstreamer_tpu.core.slots import (
    PrefixCache, SimSlotModel, SlotEngine, SlotModelProtocol,
)
from nnstreamer_tpu.models import hybrid_lm as H
from nnstreamer_tpu.models.transformer import (
    build_slot_stream as build_dense,
    kv_attend_write,
    resume_fields as dense_fields,
)
from nnstreamer_tpu.pipeline import parse_pipeline

VOCAB, SEED = 97, 5
#: the reference's configuration, under the published key names
REF = {
    "hybrid_override_pattern": "MEM*EME", "hidden_size": 64, "vocab_size": VOCAB,
    "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "router_experts": 8, "n_routed_experts": 8, "expert_offset": 0,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "routed_scaling_factor": 2.5,
    "norm_eps": 1e-5, "time_step_min": 0.001, "time_step_max": 0.1,
    "time_step_floor": 1e-4,
}


def props(**over):
    """The same configuration in the generator's ``custom=`` dialect."""
    p = {
        "arch": "nemotron_h", "layers": REF["hybrid_override_pattern"],
        "vocab": VOCAB, "d_model": 64, "ssm_heads": 4, "ssm_head_dim": 16,
        "ssm_groups": 2, "ssm_state": 16, "conv": 4, "scan_chunk": 8, "heads": 4,
        "kv_heads": 2, "head_dim": 16, "experts": 8, "experts_held": 8,
        "expert_offset": 0, "experts_per_tok": 2, "d_expert": 32, "d_shared": 64,
        "routed_scale": 2.5, "eps": 1e-5, "seq": 96, "dtype": "float32", "seed": SEED,
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
# parameters
# ---------------------------------------------------------------------------
def test_the_reference_makes_the_programs_weights_without_the_program(served):
    _, params, _ = served
    for i, kind in enumerate(REF["hybrid_override_pattern"]):
        mine, theirs = flat(ref.part(REF, SEED, i)), flat(params["blocks"][i])
        assert mine.keys() == theirs.keys()
        for k in mine:
            a, b = mine[k], theirs[k]
            if kind == "E" and "experts" in k:   # the program pads an expert's width
                b = b[:, :, :a.shape[2]] if "up" in k else b[:, :a.shape[1]]
            assert np.array_equal(a, b), (i, k)
    for name in ("embed", "norm_f", "lm_head"):
        mine, theirs = flat(ref.part(REF, SEED, name)), flat(params[name])
        assert all(np.array_equal(mine[k], theirs[k]) for k in mine), name


def test_served_weights_are_cast_on_the_device_and_small_ones_stay_float32():
    cfg = H.cfg_from_props(props(dtype="bfloat16"))
    params = H.init_params(cfg, SEED)
    for path, leaf in flat(params).items():
        keep = any(n in path for n in ("scale", "A_log", "'D'", "dt_bias", "router"))
        assert leaf.dtype == (np.float32 if keep else jnp.bfloat16), path
    # the padding of an expert's width holds zeros
    up = np.asarray(params["blocks"][1]["mixer"]["experts"]["up"], np.float32)
    assert up.shape == (8, 64, 128) and not up[:, :, 32:].any() and up[:, :, :32].any()


def test_two_seeds_share_one_init_program_per_layer_kind():
    cfg = H.cfg_from_props(props())
    a, b = H.init_params(cfg, 1), H.init_params(cfg, 2)
    assert not np.array_equal(a["blocks"][0]["mixer"]["in_proj"]["kernel"],
                              b["blocks"][0]["mixer"]["in_proj"]["kernel"])
    # blocks 0, 2, 5 are all 'M': one program, three different keys
    m = [np.asarray(a["blocks"][i]["mixer"]["A_log"]) for i in (0, 2, 5)]
    assert not np.array_equal(m[0], m[1]) and not np.array_equal(m[1], m[2])


# ---------------------------------------------------------------------------
# logits: chunked prefill, then slotted decode, against the full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt,chunk", [(16, 8), (21, 8), (12, 6), (23, 6)])
def test_chunked_prefill_then_slotted_decode_match_the_full_forward(
        served, rng, n_prompt, chunk):
    model, params, _ = served
    steps, slot = 5, 2
    seq = rng.integers(0, VOCAB, (n_prompt + steps,)).astype(np.int32)
    want = np.asarray(ref.forward(ref.make_params(REF, SEED), seq, REF))
    cache = model.reset_slot(model.init_cache(), np.int32(slot))
    for a in range(0, n_prompt, chunk):
        piece = seq[None, a:min(a + chunk, n_prompt)]
        cache, logits = model.prefill_fn(piece.shape[1])(
            params, cache, piece, np.int32(slot))
        # every chunk's last position, so state crosses chunk boundaries
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


@pytest.mark.parametrize("T,chunk", [(16, 8), (19, 8), (5, 8), (24, 6), (25, 6)])
def test_chunked_scan_matches_the_step_recurrence(rng, T, chunk):
    B, G, Hg, P, N = 2, 2, 2, 8, 16
    u = rng.standard_normal((B, T, G, Hg, P)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (B, T, G, Hg)).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, (G, Hg)).astype(np.float32)
    bm = rng.standard_normal((B, T, G, N)).astype(np.float32)
    cm = rng.standard_normal((B, T, G, N)).astype(np.float32)
    h0 = rng.standard_normal((B, G, Hg, P, N)).astype(np.float32)
    h, ys = jnp.asarray(h0), []
    for t in range(T):
        h, y = H.ssm_step(h, u[:, t], dt[:, t], a, bm[:, t], cm[:, t])
        ys.append(y)
    h2, y2 = H.ssm_chunked(jnp.asarray(h0), u, dt, a, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y2), np.stack(ys, 1), atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), atol=2e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# slots
# ---------------------------------------------------------------------------
def _engine(model, params, max_seq, **kw):
    eng = SlotEngine(model, params, max_seq=max_seq, chunk=4, prefill_chunk=8,
                     name="hybrid", **kw)
    eng.start()
    return eng


def _serve(eng, prompts, max_new, gap_s=0.0, timeout=120.0):
    """Tokens of every prompt, by submission order."""
    for p in prompts:
        eng.submit(TensorFrame([p], meta={}), p, max_new=max_new, chunk=4)
        time.sleep(gap_s)
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


#: the dense family at a size the churn below serves in seconds
DENSE = {k: str(v) for k, v in {
    "dtype": "float32", "vocab": VOCAB, "d_model": 32, "heads": 2, "layers": 2,
    "d_ff": 64, "seq": 96, "seed": SEED}.items()}


@pytest.mark.parametrize("family,donate", [
    ("nemotron_h", None), ("dense", None), ("dense", True)],
    ids=["nemotron_h", "dense", "dense-donated"])
def test_streams_under_churn_get_the_tokens_they_get_alone(rng, family, donate):
    """Both families through one engine; ``dense-donated``: every program
    that takes the cache, the join too, consumes the one it was passed (this
    jax deletes a donated buffer on the CPU as on the chip), so an engine
    that held an old cache across a join or a step would fail here, and the
    tokens are those of the model that copies."""
    def build(**kw):
        if family == "dense":
            return build_dense(DENSE, 4, **kw)
        return H.build_slot_stream(props(), 4, **kw)

    model, params, max_seq = build(donate=donate)
    prompts = [rng.integers(0, VOCAB, (1, n)).astype(np.int32)
               for n in (5, 17, 9, 24, 3, 12, 20, 8, 16, 7)]
    lens = [6, 13, 9, 13, 6, 9, 13, 6, 9, 13]
    eng = _engine(model, params, max_seq)
    try:
        alone = [_serve(eng, [p], n)[0] for p, n in zip(prompts, lens)]
        buckets = model.decode_compiles
        churn = []
        for n in sorted(set(lens)):  # 10 streams through 4 slots, staggered joins
            batch = [p for p, m in zip(prompts, lens) if m == n]
            for got, p in zip(_serve(eng, batch, n, gap_s=0.03), batch):
                churn.append((p, got))
        for p, got in churn:
            want = next(a for q, a in zip(prompts, alone) if q is p)
            np.testing.assert_array_equal(got, want)
        # join/leave churn compiles nothing: the buckets the lone streams made
        assert model.decode_compiles == buckets <= 4
        snap = eng.snapshot()
        assert snap["gen_decode_compiles"] == buckets
        assert snap["gen_oom_sheds"] == 0     # no cache died under the engine
        if family == "nemotron_h":
            # the routing counters are always on, and add up
            assert snap["gen_moe_layer_steps"] > 0
            assert 0 < snap["gen_moe_prefill_local"] < snap["gen_moe_local"]
            assert snap["gen_moe_expert_reads"] <= 8 * snap["gen_moe_layer_steps"]
            assert snap["gen_moe_max_load"] >= snap["gen_moe_layer_steps"]
    finally:
        eng.stop()
    if donate:
        plain, plain_params, _ = build(donate=False)
        eng = _engine(plain, plain_params, max_seq)
        try:
            for p, n, got in zip(prompts, lens, alone):
                np.testing.assert_array_equal(_serve(eng, [p], n)[0], got)
        finally:
            eng.stop()
    if family != "nemotron_h":
        return
    # and each stream is what the reference's full forward would pick
    handle = ref.make_params(REF, SEED)
    for p, got in list(zip(prompts, alone))[:3]:
        seq = np.concatenate([p[0], got])
        logits = np.asarray(ref.forward(handle, seq, REF))[p.shape[1] - 1:-1]
        best = logits.max(-1)
        assert np.all(best - logits[np.arange(len(got)), got] <= 1e-4)


def test_a_reset_slot_is_zero_and_an_idle_slot_is_bit_equal_across_a_dispatch(served, rng):
    model, params, _ = served
    cache = model.init_cache()
    for slot, n in ((1, 11), (3, 7)):
        p = rng.integers(0, VOCAB, (1, n)).astype(np.int32)
        cache, _ = model.prefill_fn(n)(params, cache, p, np.int32(slot))

    def rows(cache, slot, filled=None):
        """One slot's state; K/V rows only below ``filled`` (the row AT an
        idle slot's frozen position is rewritten harmlessly by every step,
        as in the dense model, and overwritten by its next real token)."""
        out = [np.array(cache["pos"])[slot]]
        for leaves in cache["layers"].values():
            for name, leaf in leaves.items():
                row = np.array(leaf)[slot]
                out.append(row[:filled] if name in "kv" and filled is not None else row)
        return out

    idle_before = rows(cache, 3, filled=7)
    assert any(r.any() for r in idle_before)
    tok = rng.integers(0, VOCAB, (4,)).astype(np.int32)
    cache, _tok, gen, toks, counts = model.decode_fn(3)(
        params, cache, tok, np.zeros(4, np.int32), np.array([0, 1, 0, 0], np.int32))
    # slot 3 holds state and did not decode: conv window, scan state, filled
    # K/V rows and position come out bit-equal
    for b, a in zip(idle_before, rows(cache, 3, filled=7)):
        np.testing.assert_array_equal(b, a)
    assert int(cache["pos"][1]) == 14 and int(gen[1]) == 3 and toks.shape == (4, 3)
    # 3 steps and 2 prefill chunks, 3 expert layers each; handed over, then zero
    assert np.asarray(counts).tolist()[3] == 15 and not np.asarray(cache["counts"]).any()
    cache = model.reset_slot(cache, np.int32(3))
    assert not any(r.any() for r in rows(cache, 3))
    assert any(r.any() for r in rows(cache, 1))


def test_an_idle_rows_token_routes_nowhere(served, rng):
    """An idle slot's row computes (the batch is fixed) but touches no
    expert and counts nothing."""
    model, params, _ = served
    x = jnp.asarray(rng.standard_normal((4, 1, 64)), jnp.float32)
    p = params["blocks"][1]["mixer"]
    _, all_live = H.moe_mix(p, x, model.cfg, jnp.ones(4, bool))
    _, one_live = H.moe_mix(p, x, model.cfg, jnp.array([False, True, False, False]))
    assert int(all_live[0]) == 8 and int(one_live[0]) == 2 and int(one_live[1]) == 2


# ---------------------------------------------------------------------------
# the expert share (model-configs guide, section 4)
# ---------------------------------------------------------------------------
def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(rng):
    x = rng.standard_normal((2, 9, 64)).astype(np.float32)
    p_ref = ref.part(REF, SEED, 1)["mixer"]
    flat_x = jnp.asarray(x.reshape(18, 64))
    want = np.asarray(ref.routed(flat_x, p_ref, REF) + ref.shared(flat_x, p_ref))
    total, local = 0.0, 0
    for offset in (0, 4):
        cfg = H.cfg_from_props(props(experts_held=4, expert_offset=offset))
        p = H.init_params(cfg, SEED)["blocks"][1]["mixer"]
        out, counts = H.moe_mix(p, jnp.asarray(x), cfg)
        total = total + np.asarray(out).reshape(18, 64)
        local += int(counts[0])
    total = total - np.asarray(ref.shared(flat_x, p_ref))    # computed by both shares
    np.testing.assert_allclose(total, want, atol=1e-4)
    assert local == 18 * 2          # every choice fell on exactly one share
    # a share alone is NOT the layer (what the absent experts add is left out)
    assert np.abs(np.asarray(out).reshape(18, 64) - want).max() > 1e-2


def test_every_token_on_one_expert_and_nothing_is_dropped(rng):
    cfg = H.cfg_from_props(props(experts_held=4, expert_offset=0))
    p = H.init_params(cfg, SEED)["blocks"][1]["mixer"]
    # the bias puts expert 2 (held) and expert 6 (absent) first for every token
    bias = jnp.zeros((8,)).at[2].set(50.0).at[6].set(40.0)
    p = {**p, "router": {**p["router"], "bias": bias}}
    x = rng.standard_normal((4, 16, 64)).astype(np.float32)
    out, counts = H.moe_mix(p, jnp.asarray(x), cfg)
    assert np.asarray(counts).tolist() == [64, 1, 64, 1]   # 64 tokens, all on expert 2
    ref_cfg = {**REF, "n_routed_experts": 4}
    p_ref = jax.tree.map(jnp.asarray, ref.part(ref_cfg, SEED, 1)["mixer"])
    p_ref["router"]["bias"] = bias
    flat_x = jnp.asarray(x.reshape(64, 64))
    want = ref.routed(flat_x, p_ref, ref_cfg) + ref.shared(flat_x, p_ref)
    np.testing.assert_allclose(np.asarray(out).reshape(64, 64), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("case", ["spread", "one_expert", "no_held_expert", "two_blocks"])
def test_the_small_batch_kernel_matches_the_reference_loop_over_held_experts(rng, case):
    """ops/expert_ffn.py in the Pallas interpreter (a TPU lowers it in
    ``moe_mix``'s place), by its one entry: a small batch streams the touched
    experts only, every token through each, weighed by its gate; nothing
    dropped under skew, and zeros where no token chose a held expert.
    ``two_blocks`` (300 rows, two of the blocks such a batch used to be cut
    into) is past the small-batch kernel's rows and meets the grouped call."""
    from nnstreamer_tpu.ops.expert_ffn import MAX_TOKENS, held_experts_ffn

    cfg = H.cfg_from_props(props(experts_held=4, expert_offset=4))
    p = H.init_params(cfg, SEED)["blocks"][1]["mixer"]
    rows = 300 if case == "two_blocks" else 21
    assert (rows > MAX_TOKENS) == (case == "two_blocks")
    bias = {"spread": p["router"]["bias"], "two_blocks": p["router"]["bias"],
            "one_expert": jnp.zeros((8,)).at[5].set(50.0).at[1].set(40.0),
            "no_held_expert": jnp.zeros((8,)).at[0].set(50.0).at[1].set(40.0)}[case]
    p = {**p, "router": {**p["router"], "bias": bias}}
    x = jnp.asarray(rng.standard_normal((rows, 64)).astype(np.float32))
    ids, w = H.route(p, x, cfg)
    local = (ids >= cfg.expert_offset) & (ids < cfg.expert_offset + 4)
    lid = jnp.where(local, ids - cfg.expert_offset, 4)
    got = held_experts_ffn(x, lid, jnp.where(local, w, 0.0), p["experts"]["up"],
                           p["experts"]["down"], interpret=True)
    ref_cfg = {**REF, "n_routed_experts": 4, "expert_offset": 4}
    p_ref = jax.tree.map(jnp.asarray, ref.part(ref_cfg, SEED, 1)["mixer"])
    p_ref["router"]["bias"] = bias
    want = np.asarray(ref.routed(x, p_ref, ref_cfg))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    touched = {"one_expert": 1, "no_held_expert": 0}.get(case)
    if touched is not None:
        assert len(set(np.asarray(lid).reshape(-1).tolist()) - {4}) == touched
    if case == "no_held_expert":
        assert not np.asarray(got).any()


def _expert_loop(x, lid, w, up, down, gate_w):
    """The held experts one after another over every row, float32: the
    reference the grouped kernel is held to."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(up.shape[0]):
        hid = jnp.matmul(x, up[e], precision="highest")
        hid = jnp.square(jax.nn.relu(hid)) if gate_w is None else jax.nn.silu(
            jnp.matmul(x, gate_w[e], precision="highest")) * hid
        gate = jnp.sum(jnp.where(lid == e, w, 0.0), axis=1, keepdims=True)
        out = out + gate * jnp.matmul(hid, down[e], precision="highest")
    return out


def _picks_of(rng, score, held, k):
    """Top-``k`` of ``score`` (rows, experts) as the op takes them: each
    pick's held expert (``held``: none) and its gate (0 there)."""
    ids = np.argsort(-score, axis=1)[:, :k]
    return (jnp.asarray(np.where(ids < held, ids, held), jnp.int32),
            jnp.asarray(np.where(ids < held, rng.random(ids.shape), 0.0), jnp.float32))


def _experts_of(rng, held, gated=False):
    """``(x -> rows, up, down, gate_w)`` of ``held`` experts of 64 x 128."""
    up, gate_w = (jnp.asarray(rng.standard_normal((held, 64, 128)) / 8, jnp.float32)
                  for _ in range(2))
    down = jnp.asarray(rng.standard_normal((held, 128, 64)) / 11, jnp.float32)
    return up, down, gate_w if gated else None


#: case: rows, held experts, the router's experts, picks a token
GROUPED_CASES = {
    "spread": (300, 4, 8, 2), "one_expert": (300, 4, 8, 2), "no_held_expert": (300, 4, 8, 2),
    "all_local": (260, 32, 32, 4),   # every pick held, top-4 of 32
    "ragged_rows": (333, 4, 8, 2),   # no multiple of a row tile, nor of a sublane tile
}


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_the_grouped_kernel_matches_the_reference_loop_over_held_experts(rng, case, gated):
    """``nns_grouped_experts_ffn`` in the Pallas interpreter: each held
    expert over its own rows only, both activations, float32 back to the
    tokens; no pick dropped when every token chooses one expert, zeros where
    none is held, and the tiles it walks are the ones ``tiled_rows`` counts."""
    from nnstreamer_tpu.ops import expert_ffn

    rows, held, total, k = GROUPED_CASES[case]
    score = rng.standard_normal((rows, total))
    if case == "one_expert":
        score[:, 2] += 50.0
    elif case == "no_held_expert":
        score[:, held:held + k] += 50.0
    lid, w = _picks_of(rng, score, held, k)
    x = jnp.asarray(rng.standard_normal((rows, 64)), jnp.float32)
    up, down, gate_w = _experts_of(rng, held, gated)
    got = expert_ffn.held_experts_ffn(x, lid, w, up, down, gate_w, interpret=True)
    want = _expert_loop(x, lid, w, up, down, gate_w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    sizes = np.bincount(np.asarray(lid).reshape(-1), minlength=held + 1)[:held]
    tm = expert_ffn.TILE_ROWS
    assert int(expert_ffn.tiled_rows(lid, held, 64)) == int((-(-sizes // tm) * tm).sum())
    if case == "one_expert":  # every token's first pick: a group of several tiles
        assert sizes[2] == rows > tm
    if case == "no_held_expert":
        assert not sizes.any() and not np.asarray(got).any()


def test_a_batch_past_what_vmem_holds_goes_in_blocks_with_layouts_of_their_own(
        rng, monkeypatch):
    """The batch and its float32 result stay in VMEM: a batch past that room
    is cut into equal blocks inside the ONE call, each with its own layout,
    and ``tiled_rows`` counts every block's tiles."""
    from nnstreamer_tpu.ops import expert_ffn

    monkeypatch.setattr(expert_ffn, "_RESIDENT_BYTES", 6 * 64 * 300)
    rows, held, k = 601, 4, 2
    assert expert_ffn._blocks(rows, 64) == (3, 208)
    lid, w = _picks_of(rng, rng.standard_normal((rows, 8)), held, k)
    x = jnp.asarray(rng.standard_normal((rows, 64)), jnp.float32)
    up, down, _ = _experts_of(rng, held)
    got = expert_ffn.held_experts_ffn(x, lid, w, up, down, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_expert_loop(x, lid, w, up, down, None)), atol=2e-5)
    tm, blocks = expert_ffn.TILE_ROWS, np.asarray(jnp.pad(
        lid, ((0, 3 * 208 - rows), (0, 0)), constant_values=held)).reshape(3, -1)
    by_hand = sum(int((-(-np.bincount(b, minlength=held + 1)[:held] // tm) * tm).sum())
                  for b in blocks)
    assert int(expert_ffn.tiled_rows(lid, held, 64)) == by_hand


@pytest.fixture(scope="module")
def v5e():
    """A described (not attached) host of four v5e chips: the TPU compiler
    is installed here, so Mosaic lowerings are checked at no chip time.
    (The one place of the test suite that loads libtpu: the ViT's compile
    tests below live in this file for that reason.)"""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


def _picks(one_chip, tokens, k):
    """Shapes of a batch's picks and their gates on the described chip."""
    return (jax.ShapeDtypeStruct((tokens, k), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((tokens, k), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("tokens", [32, 128, 512])
def test_the_small_batch_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, tokens):
    """Interpret mode cannot see tiling or VMEM limits: compile the op for
    the chip at the benchmark cell's shapes (32 slots a decode step, a
    128-token prefill chunk; 64 held experts of 2688 x 1920 in bf16) and for
    a chunk past the small-batch kernel's rows, which is the grouped call
    with relu2 experts."""
    from nnstreamer_tpu.ops.expert_ffn import held_experts_ffn

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(held_experts_ffn).lower(
        arg((tokens, 2688), jnp.bfloat16), *_picks(one_chip, tokens, 6),
        arg((64, 2688, 1920), jnp.bfloat16), arg((64, 1920, 2688), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    assert ("nns_grouped_experts_ffn" if tokens > 256 else "nns_touched_experts_ffn") in text
    assert "tpu_custom_call" in text
    # the weights are streamed through VMEM, never copied whole in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _instructions(text):
    """Instructions of a compiled program, every computation counted: the
    number the structure tests pin (the parent's, from an ahead-of-time v5e
    compile of the same program: a change that adds, drops or splits a
    device operation of a program it should not touch moves it)."""
    return len(re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ [\w\-]+\(", text, re.M))


def _hybrid_programs(cfg, slots, chunk, one_chip):
    """A hybrid cell's decode scan (``k = 8``) and prefill chunk lowered on
    shapes placed on the described chip: ``(decode, prefill)``."""
    model = H.HybridSlotModel(cfg, slots, device=one_chip._device, donate=True)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    def born(spec):
        return jax.eval_shape(lambda: H._cast(
            H._Tree(spec).init(jax.random.PRNGKey(0))["params"], cfg.dtype))

    params = {
        "embed": born((("embedding", ((cfg.vocab, cfg.d_model), jax.nn.initializers.zeros)),)),
        "blocks": [born(H.block_spec(cfg, group)) for group in cfg.groups],
        "norm_f": born(H._norm(cfg.d_model))}
    if not cfg.tied_head:
        params["lm_head"] = born(H._dense(cfg.d_model, cfg.vocab))
    params = on_chip(params)
    cache = on_chip(jax.eval_shape(lambda: {
        "pos": jnp.zeros((slots,), jnp.int32),
        "counts": jnp.zeros((len(model.counter_names),), jnp.int32),
        "layers": {i: {n: jnp.zeros(*sd) for n, sd in leaves.items()}
                   for i, leaves in model._layer_shapes().items()}}))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    toks = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one_chip)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return (model.decode_fn(8).lower(params, cache, vec, vec, vec),
            model.prefill_fn(chunk).lower(params, cache, toks, slot))


@pytest.fixture(scope="module")
def nemotron_programs(one_chip):
    cfg = H.HybridConfig(
        pattern="MEMEM*EMEMEM*EME", vocab=65536, d_model=2688, ssm_heads=64,
        ssm_head_dim=64, ssm_groups=8, ssm_state=128, n_heads=32, n_kv_heads=2,
        head_dim=128, experts=128, experts_held=64, top_k=6, d_expert=1856,
        d_shared=3712, max_seq=4096)
    return _hybrid_programs(cfg, 32, 128, one_chip)


def test_the_cells_decode_program_compiles_for_a_v5e_and_fits_its_memory(nemotron_programs):
    """The benchmark cell's decode scan (16 blocks at the published widths,
    32 slots, 4096 positions) through the chip's compiler: the kernel stands
    in every expert layer, XLA's grouped product in none, and parameters,
    slot state and temporaries fit one chip's 16 GiB."""
    compiled = nemotron_programs[0].compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(set(re.findall(r"%nns_touched_experts_ffn[.\d]* =", text))) == 7
    assert "ragged" not in text
    # the two attention layers read their K/V rows through the fill-bounded
    # kernel, and nothing but the in-place row writes makes a leaf
    assert len(set(re.findall(r"%nns_decode_attention[.\d]* =", text))) == 2
    assert _leaf_makers(text, "bf16[32,4096,256]") == {"scatter": 4}
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13 << 30
    assert mem.temp_size_in_bytes < 256 << 20   # no whole-stack copy of the experts
    # the program PR 32 left, operation for operation (PR 33 grew the family
    # a window layer, a parallel block and gated experts around it)
    assert _instructions(text) == 5766 and mem.temp_size_in_bytes == 19143680


def test_the_cells_prefill_program_is_the_one_it_was(nemotron_programs):
    """``nemotron3n_ep2_chat_closed32``'s 128-token chunk: its attention
    keeps the whole-leaf form (67 MB of scores), its experts the relu2 call."""
    compiled = nemotron_programs[1].compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%nns_touched_experts_ffn[.\d]* =", text))) == 7
    # the program PR 33 left but for the cache's counts vector, two entries
    # longer since PR 37 (the grouped kernel's rows, which a chunk of 128 rows
    # never counts): the pad that gives them their zeros is the 4 instructions
    # over PR 36's 6333; the temporaries are the same bytes
    assert _instructions(text) == 6337
    assert compiled.memory_analysis().temp_size_in_bytes == 36683264


@pytest.fixture(scope="module")
def cmdaplus_programs(one_chip):
    """``cmdaplus_ep8_docs_closed16``: one period of command-a-plus at the
    published widths, 16 of 128 experts, 16 slots, 16384 positions, chunks of
    1024."""
    cfg = H.HybridConfig(**{**H.FAMILIES["cohere2_moe"]["fields"], **dict(
        vocab=32768, d_model=4096, n_heads=128, n_kv_heads=8, head_dim=128, window=4096,
        rope_theta=50000.0, experts=128, experts_held=16, top_k=8, d_expert=4096,
        shared_experts=4, d_shared=4096, max_seq=16384)})
    return _hybrid_programs(cfg, 16, 1024, one_chip)


def test_the_window_cells_decode_program_reads_both_kinds_of_leaf_through_the_kernel(
        cmdaplus_programs):
    compiled = cmdaplus_programs[0].compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(set(re.findall(r"%nns_touched_experts_ffn[.\d]* =", text))) == 4
    assert len(set(re.findall(r"%nns_decode_attention[.\d]* =", text))) == 4
    assert "ragged" not in text
    # a window layer's leaves hold the window, the global layer's the context,
    # and nothing but the in-place row writes makes either
    assert _leaf_makers(text, "bf16[16,4096,1024]") == {"scatter": 6}
    assert _leaf_makers(text, "bf16[16,16384,1024]") == {"scatter": 2}
    # 9.47 GB of parameters and 1.88 GB of slot state (268 MB a slot would
    # be 4.3 GB with the context reserved for all four layers)
    assert 11.3e9 < mem.argument_size_in_bytes < 11.4e9
    assert mem.temp_size_in_bytes < 64 << 20
    # the program PR 33 left, operation for operation (PR 36 grew the family a
    # conv state, a dense block, QK norm and rotary on a global layer around it)
    assert _instructions(text) == 3019 and mem.temp_size_in_bytes == 5560832


def test_the_window_cells_prefill_chunk_is_bounded_by_fill_and_window(cmdaplus_programs):
    """A 1024-token chunk at 16384 positions: every layer's attention is one
    ``nns_chunk_attention`` call bounded by fill and window, its scores in
    VMEM (no (128, 1024, 16384) scores: 8.6 GB; nor the blocked jnp loop's
    (128, 1024, 256) a block), each layer's experts are ONE grouped call over
    the rows routed to them (PR 37: they were sixteen small-batch calls, four
    blocks of MAX_TOKENS rows a layer), and the chunk's temporaries stay
    under 1 GB."""
    compiled = cmdaplus_programs[1].compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(set(re.findall(r"%nns_grouped_experts_ffn[.\d]* =", text))) == 4
    assert "nns_touched_experts_ffn" not in text
    assert "ragged" not in text and "f32[1,8,16,1024,16384]" not in text
    assert len(set(re.findall(r"%nns_chunk_attention[.\d]* =", text))) == 4
    assert "f32[1,8,16,1024,128]" not in text       # the jnp loop's accumulator
    assert mem.temp_size_in_bytes < 1 << 30
    # PR 36 pinned 3770 instructions and 612 244 480 B with the sixteen
    # small-batch calls; four grouped calls, each with its layout (a running
    # count of the picks by expert, the tiles' experts), are 3907 and 11.6 MB more
    assert _instructions(text) == 3907 and mem.temp_size_in_bytes == 623821824


@pytest.fixture(scope="module")
def lfm2_programs(one_chip):
    """``lfm2moe_pp2_rag_closed32``: the first 12 layers of LFM2-8B-A1B at the
    published widths (the family's default pattern), all 32 experts held, 32
    slots, 8192 positions, chunks of 1024."""
    cfg = H.HybridConfig(**{**H.FAMILIES["lfm2_moe"]["fields"], **dict(
        vocab=65536, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64, d_ff=7168,
        experts=32, experts_held=32, top_k=4, d_expert=1792, max_seq=8192)})
    return _hybrid_programs(cfg, 32, 1024, one_chip)


def test_the_conv_cells_decode_program_holds_a_window_and_reads_its_rows_through_the_kernel(
        lfm2_programs):
    compiled = lfm2_programs[0].compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # ten expert layers with every expert held, three attention layers of 64-wide heads
    assert len(set(re.findall(r"%nns_touched_experts_ffn[.\d]* =", text))) == 10
    assert len(set(re.findall(r"%nns_decode_attention[.\d]* =", text))) == 3
    assert "ragged" not in text
    # nothing but the in-place row writes makes a K/V leaf, and a conv layer's
    # whole slot state is two rows of the width
    assert _leaf_makers(text, "bf16[32,8192,512]") == {"scatter": 6}
    assert "bf16[32,2,2048]" in text
    # 7.86 GB of parameters, 1.61 GB of K/V and 2.4 MB of windows
    assert 9.46e9 < mem.argument_size_in_bytes < 9.48e9
    assert mem.temp_size_in_bytes < 64 << 20


def test_the_conv_cells_prefill_chunk_streams_its_experts_and_blocks_its_attention(
        lfm2_programs):
    """A 1024-token chunk: ten expert layers, each ONE grouped call over the
    rows routed to its experts (PR 37: forty small-batch calls before, four
    blocks of MAX_TOKENS rows a layer, each streaming up to all 32 experts);
    heads of 64 are half a lane tile, so the chunk's attention is the
    blocked jnp form (no ``nns_chunk_attention``), bounded by fill: no (32,
    1024, 8192) scores (1.07 GB a layer), temporaries under 256 MB."""
    compiled = lfm2_programs[1].compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert len(set(re.findall(r"%nns_grouped_experts_ffn[.\d]* =", text))) == 10
    assert "nns_touched_experts_ffn" not in text
    assert "ragged" not in text and "nns_chunk_attention" not in text
    assert "f32[1,8,4,1024,8192]" not in text and "f32[1,32,1024,8192]" not in text
    assert mem.temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("ring,rows", [(False, 16384), (True, 4096)], ids=["global", "window"])
def test_the_chunk_attention_kernel_compiles_for_a_v5e_at_the_window_cells_leaves(
        one_chip, ring, rows):
    """A 1024-row chunk of 128 query heads on 8 KV heads of 128 against one
    slot's rows of a global leaf and of a round window leaf: Mosaic takes it
    and nothing the size of the scores stands beside it."""
    from nnstreamer_tpu.ops.chunk_attention import chunk_attention

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf, new = arg((1, rows, 1024)), arg((1, 1024, 1024))
    compiled = chunk_attention.lower(
        leaf, leaf, arg((1, 1024, 16384)), new, new, arg((1,), jnp.int32),
        n_heads=128, ring=ring).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("tokens", [16, 1024])
def test_the_gated_kernel_compiles_for_a_v5e_at_the_window_cells_widths(one_chip, tokens):
    """Three matrices an expert (16 held experts of 4096 x 4096 in bf16), a
    decode step's rows and a 1024-token chunk (the grouped call); the relu2
    call above lowers as it did (two weight operands)."""
    from nnstreamer_tpu.ops.expert_ffn import held_experts_ffn

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = arg((16, 4096, 4096))
    compiled = jax.jit(held_experts_ffn).lower(
        arg((tokens, 4096)), *_picks(one_chip, tokens, 8), w, w, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows,d,f,held,k", [
    (1024, 4096, 4096, 16, 8), (1024, 2048, 1792, 32, 4)],
    ids=["cmdaplus_ep8_docs_closed16", "lfm2moe_pp2_rag_closed32"])
def test_the_grouped_kernel_compiles_for_a_v5e_at_both_long_chunk_cells_shapes(
        one_chip, rows, d, f, held, k):
    """A 1024-row chunk at both cells' widths: Mosaic takes the call with
    the batch and its float32 result in VMEM beside the weight tiles (under
    its own limit), and nothing beside the call but the layout's tables: no
    expert weight copied or staged whole, no gathered copy of the batch."""
    from nnstreamer_tpu.ops import expert_ffn

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up, down = arg((held, d, f)), arg((held, f, d))
    compiled = expert_ffn.grouped_experts_ffn.lower(
        arg((rows, d)), *_picks(one_chip, rows, k), up, down, up).compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%nns_grouped_experts_ffn[.\d]* =", text))) == 1
    assert "nns_touched_experts_ffn" not in text and "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20
    assert expert_ffn._blocks(rows, d) == (1, rows)


# ---------------------------------------------------------------------------
# the dense cell's decode step through the chip's compiler (models/
# transformer.py, ops/decode_attention.py; here for the v5e fixture)
# ---------------------------------------------------------------------------
def _leaf_makers(text, leaf):
    """Instructions of a compiled program whose output is a cache leaf, by
    what they are: ``{"scatter": n}`` when only the row writes make one
    (parameters, tuple plumbing and the loop itself aside)."""
    made = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m or not m.group(1).startswith(leaf):
            continue
        op = m.group(2)
        if op in ("parameter", "get-tuple-element", "bitcast", "scatter"):
            continue  # a bare scatter is the body of a fusion counted below
        if op == "fusion" and re.search(r'op_name="[^"]*/scatter"', line):
            op = "scatter"
        made[op] = made.get(op, 0) + 1
    return made


@pytest.mark.parametrize("leaf,heads", [((16, 1024, 1280), 20), ((32, 4096, 256), 32),
                                        ((16, 4096, 1024), 128)],
                         ids=["gpt2_large", "nemotron3_nano", "command_a_plus_window"])
def test_the_decode_attention_kernel_compiles_for_a_v5e_at_both_cells_leaves(
        one_chip, leaf, heads):
    """Mosaic takes the kernel at the dense cell's leaves (20 heads of 64,
    multi-head), at the hybrid cell's (32 query heads on 2 KV heads of 128)
    and at a window layer's round leaf (128 query heads on 8 KV heads, the
    row to leave out as a second scalar operand), and no temporary the size
    of a leaf stands beside it."""
    from nnstreamer_tpu.ops.decode_attention import decode_attention

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, _, W = leaf
    head_dim = 64 if heads == 20 else 128
    skip = arg((B,), jnp.int32) if heads == 128 else None
    compiled = decode_attention.lower(
        arg(leaf), arg(leaf), arg((B, 1, heads * head_dim)), arg((B, 1, W)),
        arg((B, 1, W)), arg((B,), jnp.int32), n_heads=heads, skip=skip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _dense_decode_program(sharding_of, layers=2, slots=16, prefill=0, join=False,
                          **model_kw):
    """The dense family's ``k = 8`` decode scan at GPT-2-large's widths,
    cut to ``layers``, lowered on shapes placed by ``sharding_of``; or, with
    ``prefill`` rows, its prefill chunk; or its ``join``."""
    from nnstreamer_tpu.models.transformer import (
        SlotModel, TransformerConfig, TransformerLM)

    cfg = TransformerConfig(vocab=50257, d_model=1280, n_heads=20,
                            n_layers=layers, d_ff=5120, max_seq=1024)
    model = SlotModel(cfg, slots, **{"donate": True, **model_kw})

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding_of), tree)

    cache = placed(jax.eval_shape(lambda: model._model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32))["cache"]))
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding_of)
    if join:
        return model.reset_slot.lower(cache, slot)
    params = placed(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    if prefill:
        return model.prefill_fn(prefill).lower(
            params, cache, jax.ShapeDtypeStruct((1, prefill), jnp.int32, sharding=sharding_of),
            slot)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=sharding_of)
    return model.decode_fn(8).lower(params, cache, vec, vec, vec)


def test_the_dense_cells_decode_step_reads_its_leaves_through_the_kernel(one_chip):
    """``gpt2l_chat_closed16``'s decode program at its full depth: one
    ``nns_decode_attention`` call a layer inside the scan, and nothing but
    the two in-place row writes a layer has an output the size of a leaf.
    The leaf is the scan's donated carry, and a custom call's operand: XLA
    could copy it to write beside the read, and its memory-space assignment
    did stage 8 of the 72 whole through VMEM before the call until the call
    reserved the room (``decode_attention._vmem_limit``); which leaves it
    picks depends on the whole schedule, hence all 36 layers."""
    text = _dense_decode_program(
        one_chip, layers=36, device=one_chip._device).compile().as_text()
    assert len(set(re.findall(r"%nns_decode_attention[.\d]* =", text))) == 36
    assert _leaf_makers(text, "bf16[16,1024,1280]") == {"scatter": 72}
    assert _instructions(text) == 13361     # PR 32's program, operation for operation


def test_the_dense_cells_prefill_chunk_is_the_program_it_was(one_chip):
    """``gpt2l_chat_closed16``'s 128-token chunk at its full depth keeps the
    whole-leaf attention (10 MB of scores: far under the blocked form's
    threshold) and the instructions PR 32 left."""
    text = _dense_decode_program(
        one_chip, layers=36, prefill=128, device=one_chip._device).compile().as_text()
    assert _instructions(text) == 16359 and not re.findall(r"%while[.\d]* = ", text)


def test_the_dense_cells_join_zeroes_its_slot_in_place(one_chip):
    """``gpt2l_chat_closed16``'s join at its leaves (36 layers x K and V of
    ``(16,1024,1280)`` bf16 and the positions: 3.02 GB), donated as the
    decode and prefill programs take it: every leaf is aliased to its
    result, the only instructions that make a leaf are the row writes, each
    in place on its operand, and nothing the size of a leaf (42 MB) stands
    beside them.  Not donated, the same program copies every leaf: a
    second cache at each join."""
    leaf, leaf_bytes = "bf16[16,1024,1280]", 16 * 1024 * 1280 * 2

    def join(donate):
        compiled = _dense_decode_program(
            one_chip, layers=36, join=True, donate=donate,
            device=one_chip._device).compile()
        return compiled.as_text(), compiled.memory_analysis()

    text, mem = join(True)
    assert mem.alias_size_in_bytes >= 72 * leaf_bytes
    assert mem.temp_size_in_bytes < leaf_bytes
    assert _leaf_makers(text, leaf) == {"dynamic-update-slice": 72, "fusion": 72}
    writes = [line for line in text.splitlines()
              if re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = {re.escape(leaf)}\S* fusion\(", line)]
    assert len(writes) == 72 and all(
        'dynamic_update_slice"' in w and '"aliasing_operands"' in w for w in writes)
    text, mem = join(False)
    assert mem.alias_size_in_bytes == 0
    assert _leaf_makers(text, leaf).get("copy", 0) >= 36


def test_the_dense_decode_step_under_a_mesh_holds_no_custom_call(v5e):
    """``SlotModel(mesh=...)``: a Mosaic call cannot be partitioned, so the
    per-token read keeps its jnp form and the program compiles for four
    chips with no custom call in it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(v5e.devices).reshape(4), ("tp",))
    text = _dense_decode_program(
        NamedSharding(mesh, P()), slots=4, mesh=mesh).compile().as_text()
    assert "tpu_custom_call" not in text and "nns_decode_attention" not in text


# ---------------------------------------------------------------------------
# the stream cell's ViT through the chip's compiler (models/vit.py,
# ops/flash_attention.py; here because this file holds the v5e fixture)
# ---------------------------------------------------------------------------
def _vit_step(props, batch, sharding_of, **fn_kw):
    """The zoo ViT's jitted step lowered on shapes: (lowered, n layers)."""
    from nnstreamer_tpu.models import build

    fn, params, _, _ = build("vit", props)
    size = int(props["size"])
    p_sh, x_sh = sharding_of
    lowered = jax.jit(lambda p, x: fn(p, [x], **fn_kw)[0]).lower(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.dtype(props["dtype"]), sharding=p_sh), params),
        jax.ShapeDtypeStruct((batch, size, size, 3), jnp.uint8, sharding=x_sh))
    return lowered, int(props["layers"])


@pytest.mark.parametrize("batch", [128, 1])
def test_the_vit_cells_step_compiles_for_a_v5e_with_its_scores_in_vmem(one_chip, batch):
    """ViT-L/16-384 at the stream cell's widths, cut to two layers to keep
    the test short, at its largest and its smallest bucket: one
    ``nns_flash_attention`` call a layer, and no array anywhere in the
    program with two dimensions of 577 (or of the padded 640) — the score
    matrix and its softmax exist only inside the kernel."""
    props = {"size": "384", "patch": "16", "d_model": "1024", "heads": "16",
             "layers": "2", "d_ff": "4096", "classes": "1000", "dtype": "bfloat16"}
    lowered, layers = _vit_step(props, batch, (one_chip, one_chip))
    text = lowered.compile().as_text()
    assert len(set(re.findall(r"%nns_flash_attention[.\d]* =", text))) == layers
    squares = {shape for shape in re.findall(r"\[([\d,]+)\]", text)
               if sum(d in ("577", "640") for d in shape.split(",")) >= 2}
    assert not squares, squares


def test_a_small_vit_compiles_for_a_v5e_mesh(v5e):
    """``mesh=dp:4`` as backends/jax_xla.py compiles it (parameters
    replicated, the batch scattered on dp, ``single_device=False``): a
    Mosaic call cannot be partitioned, so the program must hold none."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(v5e.devices).reshape(4), ("dp",))
    props = {"size": "64", "patch": "16", "d_model": "128", "heads": "2",
             "layers": "2", "d_ff": "256", "classes": "10", "dtype": "bfloat16"}
    lowered, _ = _vit_step(
        props, 8, (NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))),
        single_device=False)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" not in text and "nns_flash_attention" not in text


# ---------------------------------------------------------------------------
# grouped-query attention through the one cache step
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("H_J", [(4, 2), (8, 1), (4, 4)])
def test_grouped_query_cache_step_matches_plain_attention(rng, T, H_J):
    nh, nj = H_J
    B, S, Dh = 3, 16, 8
    pos = np.array([0, 4, 9], np.int32)
    ck = rng.standard_normal((B, S, nj * Dh)).astype(np.float32)
    cv = rng.standard_normal((B, S, nj * Dh)).astype(np.float32)
    q = rng.standard_normal((B, T, nh * Dh)).astype(np.float32)
    k = rng.standard_normal((B, T, nj * Dh)).astype(np.float32)
    v = rng.standard_normal((B, T, nj * Dh)).astype(np.float32)
    nk, nv, attn = kv_attend_write(
        *map(jnp.asarray, (ck, cv, q, k, v, pos)), nh, n_kv_heads=nj)
    for b in range(B):
        n = pos[b]
        keys = np.concatenate([ck[b, :n], k[b]]).reshape(n + T, nj, Dh)
        vals = np.concatenate([cv[b, :n], v[b]]).reshape(n + T, nj, Dh)
        keys, vals = np.repeat(keys, nh // nj, 1), np.repeat(vals, nh // nj, 1)
        s = np.einsum("thd,shd->hts", q[b].reshape(T, nh, Dh), keys) / np.sqrt(Dh)
        s = np.where(np.arange(n + T)[None, None] <= n + np.arange(T)[None, :, None], s, -1e30)
        e = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hts,shd->thd", e / e.sum(-1, keepdims=True), vals)
        np.testing.assert_allclose(np.asarray(attn)[b], want.reshape(T, nh * Dh), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(nk)[b, n:n + T], k[b])
        np.testing.assert_array_equal(np.asarray(nv)[b, :n], cv[b, :n])


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
    for name in H.COUNTER_NAMES:   # always on: tracing is off here
        assert health[name] > 0 or name.startswith(("gen_moe_prefill", "gen_moe_grouped")), name


@pytest.mark.parametrize("line,why", [
    ("slots=2 prefix-cache=on", "recurrent state cannot be cut by position"),
    ("slots=2 mesh=tp:2", "mesh= is not served for arch:nemotron_h"),
    ("slots=0", "arch:nemotron_h needs slots >= 1"),
])
def test_what_the_family_does_not_serve_is_refused_by_name(line, why):
    pipe = parse_pipeline(
        f"appsrc name=src ! tensor_generator {line} custom={custom()} ! tensor_sink name=out")
    with pytest.raises(Exception, match=why):
        pipe.start()
    pipe.stop()


def test_a_bad_pattern_or_share_is_refused_by_name():
    with pytest.raises(ValueError, match="one of M, E"):
        H.cfg_from_props(props(layers="MXE"))
    with pytest.raises(ValueError, match="not a share"):
        H.cfg_from_props(props(experts_held=6, expert_offset=4))
    with pytest.raises(ValueError, match="cannot be cut by position"):
        model, params, max_seq = H.build_slot_stream(props(), 2)
        SlotEngine(model, params, max_seq=max_seq, prefill_chunk=8,
                   prefix_cache=PrefixCache(grain=8))
    with pytest.raises(ValueError, match="mesh="):
        H.build_slot_stream(props(), 2, mesh=object())


def test_the_resume_signature_covers_family_and_every_config_field():
    dense = {"vocab": "97", "d_model": "64", "heads": "4", "layers": "2", "seq": "96"}

    def sig(family, fields):
        return resume_signature(family, max_new=8, **fields)

    base = sig(H.FAMILY, H.resume_fields(props()))
    assert base == sig(H.FAMILY, H.resume_fields(props()))
    assert base != sig("zoo", dense_fields(dense))
    for key, value in (("expert_offset", 4), ("experts_held", 4), ("layers", "MEM*EMM"),
                       ("kv_heads", 1), ("ssm_state", 8), ("routed_scale", 1.0),
                       ("seed", 6), ("gen_seed", 1), ("temperature", 0.5)):
        over = {key: value, **({"experts_held": 4} if key == "expert_offset" else {})}
        assert sig(H.FAMILY, H.resume_fields(props(**over))) != base, key
    # the dense family's signature follows its config's fields too
    d0 = sig("zoo", dense_fields(dense))
    for key, value in (("d_ff", "128"), ("dtype", "float32"), ("attn", "flash"),
                       ("seed", "3"), ("top_k", "5")):
        assert sig("zoo", dense_fields({**dense, key: value})) != d0, key


def test_the_three_slot_models_satisfy_the_one_protocol(served):
    dense, _, _ = build_dense(
        {"vocab": "31", "d_model": "16", "heads": "2", "layers": "1", "seq": "16",
         "dtype": "float32"}, 2)
    for model in (served[0], dense, SimSlotModel(2)):
        assert isinstance(model, SlotModelProtocol), type(model)
    assert served[0].counter_names == H.COUNTER_NAMES and not served[0].supports_prefix
    assert dense.counter_names == ("gen_kv_rows_read", "gen_kv_rows_held")
    assert dense.supports_prefix
