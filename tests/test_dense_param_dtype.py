"""The dense slot model stores its parameters as it serves them
(``models/transformer.py::served_params``, called by
``SlotModel.place_params``): every leaf whose module converts it to the
model dtype before each use is stored in that dtype once, so no program
converts it again.

Oracles: the parameter tree as drawn (float32), driven through the same
programs.  The conversion the module made becomes the identity, so the
logits, the tokens and the caches are BIT-equal, not close.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models._init_util import host_init
from nnstreamer_tpu.models.transformer import (
    SlotModel,
    TransformerLM,
    _cfg_from_props,
    build_slot_stream,
    served_params,
)

PROPS = {"dtype": "bfloat16", "vocab": "61", "d_model": "32", "heads": "2",
         "layers": "2", "d_ff": "80", "seq": "48", "seed": "5"}
SLOTS = 3


def _drawn(props):
    """The tree ``build_slot_stream`` draws, before placement."""
    cfg = _cfg_from_props(props)
    return cfg, host_init(TransformerLM(cfg).init, int(props["seed"]),
                          np.zeros((1, 8), np.int32))


def _by_path(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _served_in_model_dtype(path, quant):
    """The rule, written out: both embedding tables always, a block's
    projection kernels unless quantized."""
    if path.endswith("/embedding"):
        return True
    return (not quant and path.startswith("params/block")
            and path.endswith("/kernel"))


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_placed_leaves_are_stored_in_the_dtype_their_module_serves(quant):
    """bf16: the four projection kernels of every block and both embedding
    tables are bf16, equal to the drawn leaf rounded; the LayerNorms and
    ``lm_head`` are the drawn float32 leaves.  Under int8 the kernels stay
    float32: ``QuantDense`` derives its scales from them."""
    props = {**PROPS, **({"quantize": "int8"} if quant else {})}
    _, drawn = _drawn(props)
    _, placed, _ = build_slot_stream(props, SLOTS)
    drawn, placed = _by_path(drawn), _by_path(placed)
    assert drawn.keys() == placed.keys()
    cast = sorted(p for p in drawn if _served_in_model_dtype(p, quant))
    assert len(cast) == (2 if quant else 4 * 2 + 2)
    assert "params/lm_head/kernel" not in cast
    for path, leaf in placed.items():
        want = np.asarray(drawn[path])
        if path in cast:
            assert leaf.dtype == jnp.bfloat16, path
            want = want.astype(jnp.bfloat16)
        else:
            assert leaf.dtype == jnp.float32, path
        np.testing.assert_array_equal(np.asarray(leaf), want)


def test_a_float32_model_keeps_the_tree_it_was_given():
    """``dtype:float32``: nothing is converted at use, so the tree and every
    leaf are the objects drawn, and placement moves nothing more."""
    cfg, drawn = _drawn({**PROPS, "dtype": "float32"})
    assert served_params(cfg, drawn) is drawn
    model = SlotModel(cfg, SLOTS)
    placed = model.place_params(drawn)
    for a, b in zip(jax.tree.leaves(drawn), jax.tree.leaves(placed)):
        assert b.dtype == a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _drive(model, params, prompts):
    """Every program a turn runs, from ``params``: each prompt prefilled in
    two chunks into its own slot, its first token picked, then two decode
    scans (k = 3, then 2) over all slots and one bare step.  Returns every
    logit, token and cache leaf made on the way, as host arrays."""
    out = []
    cache = model.init_cache()
    first = []
    for slot, prompt in enumerate(prompts):
        cache = model.reset_slot(cache, np.int32(slot))
        for piece in (prompt[:, :5], prompt[:, 5:]):
            cache, logits = model.prefill_fn(piece.shape[1])(
                params, cache, piece, np.int32(slot))
            out.append(logits)
        first.append(model.pick_first(logits)[0])
    idle = SLOTS - len(prompts)
    tok = jnp.asarray(first + [0] * idle, jnp.int32)
    gen = jnp.ones((SLOTS,), jnp.int32)
    active = jnp.asarray([1] * len(prompts) + [0] * idle, jnp.int32)
    for k in (3, 2):
        cache, tok, gen, toks, counts = model.decode_fn(k)(
            params, cache, tok, gen, active)
        out += [tok, gen, toks, counts]
    logits, upd = model._model.apply(
        {"params": params["params"], "cache": cache},
        tok[:, None], mutable=["cache"], active=active)
    out += [logits] + jax.tree.leaves(cache) + jax.tree.leaves(upd["cache"])
    return [np.asarray(a) for a in out]


@pytest.mark.parametrize("placement", ["device", "tp_mesh"])
def test_programs_from_the_placed_tree_are_bit_equal_to_the_drawn_tree(
        rng, placement):
    """Prefill chunks, their logits and first tokens, two decode scans and
    their caches, from the placed tree and from the drawn float32 tree
    placed as before (committed to the device, or tp-sharded without the
    cast): equal bit for bit."""
    from nnstreamer_tpu.parallel.mesh import make_mesh
    from nnstreamer_tpu.parallel.sharding import shard_params, transformer_rules

    cfg, drawn = _drawn(PROPS)
    mesh = (make_mesh({"tp": 2}, devices=jax.devices()[:2])
            if placement == "tp_mesh" else None)
    model = SlotModel(cfg, SLOTS, mesh=mesh)
    placed = model.place_params(drawn)
    raw = (jax.device_put(drawn, model.device) if mesh is None
           else shard_params(drawn, mesh, transformer_rules()))
    assert jax.tree.leaves(raw)[0].dtype == jnp.float32
    prompts = [rng.integers(0, 61, (1, n)).astype(np.int32) for n in (9, 13)]
    want, got = _drive(model, raw, prompts), _drive(model, placed, prompts)
    assert len(want) == len(got)
    for a, b in zip(want, got):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    if mesh is not None:
        qkv = placed["params"]["block0"]["attn_qkv"]["kernel"]
        assert qkv.dtype == jnp.bfloat16 and len(qkv.sharding.device_set) == 2
