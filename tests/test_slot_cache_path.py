"""The decode cache path (models/transformer.py ``kv_attend_write``): the
KV cache is a pair of lane-dense ``(slots, max_seq, d_model)`` leaves per
layer, a step reads each leaf once as it lies and writes its new rows with
one in-place scatter.

Oracles:

* a dense float32 ``jnp`` attention over the same K/V rows (the helper
  alone, bf16 and float32 leaves), and the cache-less model over the whole
  sequence (the slotted model, step by step);
* the jaxpr of one decode step and of one prefill chunk: what touches a
  whole leaf, and what loops.

Single-occupant slotted == unslotted bit-parity lives in
``test_continuous_batching.py::TestSlotModelParity``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models.transformer import (
    TransformerLM,
    _cfg_from_props,
    build_slot_stream,
    kv_attend_write,
)
from nnstreamer_tpu.ops import decode_attention

PROPS = {
    "dtype": "float32", "vocab": 61, "d_model": 32, "heads": 2,
    "layers": 2, "d_ff": 64, "seq": 64, "seed": 11,
}
SPROPS = {k: str(v) for k, v in PROPS.items()}
MAX_SEQ = PROPS["seq"]


def _dense_attention(ck, cv, q, k, v, pos, H):
    """Row by row, in float32: the rows a query may see, gathered densely."""
    B, T, D = q.shape
    Dh = D // H
    f = lambda a: np.asarray(a, np.float32)
    ck, cv, q, k, v = map(f, (ck, cv, q, k, v))
    out = np.zeros((B, T, D), np.float32)
    for b in range(B):
        n = min(int(pos[b]), ck.shape[1])
        for t in range(T):
            keys = np.concatenate([ck[b, :n], k[b, :t + 1]]).reshape(-1, H, Dh)
            vals = np.concatenate([cv[b, :n], v[b, :t + 1]]).reshape(-1, H, Dh)
            s = np.einsum("hd,shd->hs", q[b, t].reshape(H, Dh), keys)
            s = s / np.sqrt(Dh)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, t] = np.einsum("hs,shd->hd", p, vals).reshape(D)
    return out


class TestAttendWriteHelper:
    @pytest.mark.parametrize("T", [1, 5], ids=["step", "chunk"])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    def test_matches_dense_attention_and_writes_rows(self, rng, dtype, T):
        """Staggered depths, an empty row and a full one: the output is the
        dense float32 attention over exactly the rows each query may see,
        and the leaves change in the new rows alone."""
        B, S, H, D = 5, 24, 4, 32
        pos = np.asarray([0, 3, 11, S - T, S], np.int32)  # last: no room
        mk = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
        ck, cv = mk(B, S, D), mk(B, S, D)
        q, k, v = mk(B, T, D), mk(B, T, D), mk(B, T, D)
        nk, nv, attn = jax.jit(kv_attend_write, static_argnums=6)(
            ck, cv, q, k, v, jnp.asarray(pos), H)
        assert attn.dtype == dtype and attn.shape == (B, T, D)
        want = _dense_attention(ck, cv, q, k, v, pos, H)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5  # bf16: output rounding
        np.testing.assert_allclose(
            np.asarray(attn, np.float32), want, rtol=tol, atol=tol)
        for old, new, rows in ((ck, nk, k), (cv, nv, v)):
            want_leaf = np.array(old, np.float32)
            for b in range(B - 1):
                want_leaf[b, pos[b]:pos[b] + T] = np.asarray(
                    rows[b], np.float32)
            np.testing.assert_array_equal(
                np.asarray(new, np.float32), want_leaf)

    @pytest.mark.parametrize("form", ["xla", "kernel"])
    def test_probabilities_are_not_rounded(self, rng, form, monkeypatch):
        """bf16 leaves, float32 softmax: against the dense float32 oracle
        BEFORE the output is rounded the error is float32-sized, far under
        what rounding p to bf16 would leave (4e-3 of a value).  The jnp
        form, and the fill-bounded kernel (ops/decode_attention.py) forced
        and interpreted at a shape it takes: one bound for both."""
        B, S, H, D = (2, 40, 4, 32) if form == "xla" else (2, 256, 4, 128)
        monkeypatch.setattr(decode_attention, "INTERPRET", form == "kernel")
        mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)
        ck, cv, q, k, v = mk(B, S, D), mk(B, S, D), mk(B, 1, D), mk(B, 1, D), mk(B, 1, D)
        pos = np.asarray([S - 1, 17], np.int32)
        f32 = lambda a: a.astype(jnp.float32)
        # float32 q/k/v with bf16 leaves keep the output unrounded
        _, _, attn = kv_attend_write(
            ck, cv, f32(q), f32(k), f32(v), jnp.asarray(pos), H)
        want = _dense_attention(ck, cv, q, k, v, pos, H)
        np.testing.assert_allclose(np.asarray(attn), want, rtol=0, atol=5e-6)


def _prefill(model, params, cache, slot, prompt):
    cache = model.reset_slot(cache, np.int32(slot))
    cache, logits = model.prefill_fn(prompt.shape[1])(
        params, cache, prompt, np.int32(slot))
    return cache, int(np.asarray(model.pick_first(logits))[0])


def _leaves(cache):
    return [np.array(c) for c in jax.tree.leaves(cache)]


class TestSlottedScan:
    def test_staggered_slots_idle_and_full(self, rng):
        """4 slots at depths 5 / 20 / max_seq-1 and an IDLE one at 9, three
        decode steps: every live slot's logits equal the cache-less model
        over its whole sequence; the idle slot changes in its frozen row
        alone and never advances; a slot stepped at pos == max_seq changes
        no page; and the k = 3 scan picks the same tokens."""
        model, params, _ = build_slot_stream(SPROPS, 4)
        cfg = _cfg_from_props(SPROPS)
        full = TransformerLM(cfg)
        depths = [5, 20, MAX_SEQ - 1, 9]
        idle = 3
        seqs, cache = [], model.init_cache()
        for slot, n in enumerate(depths):
            prompt = rng.integers(0, 61, (1, n)).astype(np.int32)
            cache, first = _prefill(model, params, cache, slot, prompt)
            seqs.append(list(prompt[0]) + [first])
        tok = jnp.asarray([s[-1] for s in seqs], jnp.int32)
        gen = jnp.ones((4,), jnp.int32)
        active = jnp.ones((4,), jnp.int32).at[idle].set(0)
        scan_cache, _tok, _gen, scan_toks, _counts = model.decode_fn(3)(
            params, jax.tree.map(jnp.copy, cache), tok, gen, active)
        before = _leaves(cache)
        for step in range(3):
            logits, upd = model._model.apply(
                {"params": params["params"], "cache": cache},
                tok[:, None], mutable=["cache"], active=active)
            prev, cache = _leaves(cache), upd["cache"]
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            np.testing.assert_array_equal(
                nxt[:3], np.asarray(scan_toks)[:3, step])
            for slot in range(3):
                if len(seqs[slot]) > MAX_SEQ:
                    continue  # full: nothing left to compare against
                want = full.apply(
                    params, jnp.asarray([seqs[slot]], jnp.int32))[0, -1]
                np.testing.assert_allclose(
                    np.asarray(logits[slot, -1]), np.asarray(want),
                    rtol=2e-4, atol=2e-4)
                seqs[slot].append(int(nxt[slot]))
            if step == 1:
                # slot 2 was stepped at pos == max_seq: no page moved
                for a, b in zip(prev, _leaves(cache)):
                    if a.ndim == 3:
                        np.testing.assert_array_equal(a[2], b[2])
            tok = jnp.where(active > 0, jnp.asarray(nxt, jnp.int32), tok)
        for a, b, c in zip(before, _leaves(cache), _leaves(scan_cache)):
            # the scan IS these steps (another program: float32 ulps)
            np.testing.assert_allclose(b, c, rtol=1e-5, atol=1e-6)
            if a.ndim == 3:
                keep = np.arange(MAX_SEQ) != depths[idle]
                np.testing.assert_array_equal(a[idle, keep], b[idle, keep])
                assert np.abs(b[idle, depths[idle]]).sum() > 0
            else:
                assert b[idle] == a[idle] == depths[idle]
                assert list(b[:3]) == [8, 23, MAX_SEQ + 2]


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


class TestProgramStructure:
    @pytest.mark.parametrize("form", ["xla", "kernel"])
    @pytest.mark.parametrize("layers", [2, 3])
    def test_decode_step_touches_a_leaf_by_scatter_alone(
            self, layers, form, monkeypatch):
        """In one decode step nothing but the row scatter has an output
        the size of a cache leaf (no select over the cache, no float32 or
        transposed copy), and there are exactly two scatters a layer: with
        the jnp read, and with the fill-bounded kernel forced (leaves it
        takes: 128 lanes, 128 rows), whose one call a layer reads the
        leaves and returns a row a slot."""
        shape = {} if form == "xla" else {"d_model": "128", "seq": "128"}
        monkeypatch.setattr(decode_attention, "INTERPRET", form == "kernel")
        props = {**SPROPS, **shape, "layers": str(layers), "dtype": "bfloat16"}
        model, params, _ = build_slot_stream(props, 4)
        cache = model.init_cache()
        leaf = (4, int(props["seq"]), int(props["d_model"]))
        assert {c.shape for c in jax.tree.leaves(cache) if c.ndim > 1} == {leaf}

        def step(params, cache, tok, active):
            return model._model.apply(
                {"params": params["params"], "cache": cache},
                tok[:, None], mutable=["cache"], active=active)

        jaxpr = jax.make_jaxpr(step)(
            params, cache, jnp.zeros((4,), jnp.int32),
            jnp.ones((4,), jnp.int32))
        writers = [
            e.primitive.name for e in _eqns(jaxpr.jaxpr)
            if any(getattr(v.aval, "size", 0) >= np.prod(leaf)
                   for v in e.outvars)
            and e.primitive.name not in ("pjit", "closed_call", "core_call")
        ]
        assert writers == ["scatter"] * (2 * layers), writers
        kernels = [e for e in _eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert len(kernels) == (layers if form == "kernel" else 0)
        assert {e.params["name"] for e in kernels} <= {"nns_decode_attention"}

    @pytest.mark.parametrize("donate", [True, False, None])
    @pytest.mark.parametrize("family", ["dense", "nemotron_h"])
    def test_a_join_takes_the_cache_as_the_step_programs_do(
            self, family, donate):
        """``reset_slot`` follows the model's ``donate`` like the prefill
        and decode programs: donated, every cache leaf is a donor of the
        lowered join and aliases its own result; not donated (``None`` on
        a CPU), none is.  A lowering needs no device that honours it."""
        if family == "dense":
            model, _, _ = build_slot_stream(SPROPS, 4, donate=donate)
        else:
            from nnstreamer_tpu.models import hybrid_lm

            model = hybrid_lm.HybridSlotModel(hybrid_lm.cfg_from_props({
                "layers": "M*E", "vocab": "61", "d_model": "32",
                "ssm_heads": "2", "ssm_head_dim": "16", "ssm_groups": "1",
                "ssm_state": "8", "heads": "2", "kv_heads": "1",
                "head_dim": "16", "experts": "4", "experts_held": "4",
                "experts_per_tok": "2", "d_expert": "16", "d_shared": "16",
                "seq": "64", "dtype": "float32"}), 4, donate=donate)
        cache = model.init_cache()
        lowered = model.reset_slot.lower(cache, np.int32(1))
        (cache_info, slot_info), _ = lowered.args_info
        n = len(jax.tree.leaves(cache))
        assert [a.donated for a in jax.tree.leaves(cache_info)] == (
            [bool(donate)] * n)
        assert not slot_info.donated
        assert lowered.as_text().count("tf.aliasing_output") == (
            n if donate else 0)
        assert bool(model._donate) == bool(donate)  # one flag, three programs

    @pytest.mark.parametrize("n", [4, 32])
    def test_prefill_chunk_holds_no_loop(self, n):
        """The benchmark tells the decode program from the prefill programs
        by its while loop (both are ``jit_traced``): a prefill chunk must
        hold none, and the decode program must keep its scan."""
        model, params, _ = build_slot_stream(SPROPS, 2)
        cache = model.init_cache()
        jaxpr = jax.make_jaxpr(model._prefill_chunk)(
            params, cache, jnp.zeros((1, n), jnp.int32), np.int32(1))
        loops = {"while", "scan"}
        assert not loops & {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
        jaxpr = jax.make_jaxpr(
            lambda *a: model._decode_scan(2, *a))(
            params, cache, jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.int32), jnp.ones((2,), jnp.int32))
        assert "scan" in {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
        assert model.decode_fn(2).__wrapped__.__name__ == "traced"
        assert model.prefill_fn(n).__wrapped__.__name__ == "traced"
