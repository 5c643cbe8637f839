"""Plain reference of the ``cohere2_moe`` decoder (CohereLabs command-a-plus-
05-2026, https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/
config.json): float32, ``highest`` matmul precision, one sequence, no cache,
no ring, no slots, no chunking of the mathematics.  Every layer is one
PARALLEL block with one norm (``use_parallel_block``):

    h = LayerNorm(x)                  # mean-centred, weight only, no bias
    x <- x + Attn(h) + MoE(h)

* ``Attn``: ``q = h W_q`` (``num_attention_heads`` x ``head_dim``), ``k = h
  W_k``, ``v = h W_v`` (``num_key_value_heads`` x ``head_dim``; a group of
  queries shares a KV head), no bias, no QK norm, causal softmax at scale
  ``head_dim^-1/2``, ``W_o``.  A ``sliding_attention`` layer turns ``q`` and
  ``k`` by rotary positions first (``rope_gptj``: the interleaved pair
  ``(x[2i], x[2i+1])`` by ``p rope_theta^(-2i / head_dim)``, every
  dimension) and a query at position ``p`` sees the keys with ``0 <= p - p_k
  < sliding_window``; a ``full_attention`` layer applies NO positional
  encoding and sees every earlier position.
* ``MoE``: ``s = sigmoid(h W_r)`` in float32 over ``router_experts``; the
  chosen are the top-``num_experts_per_tok`` of ``s``; weights ``s_e / sum of
  the chosen s`` (``norm_topk_prob``); an expert is ``W_down(silu(h W_gate)
  * (h W_up))``; the ``num_shared_experts`` shared experts of the same form
  run for every token and their outputs are AVERAGED.  The routed part is a
  loop over the HELD expert ids with a mask.
* after the last layer: LayerNorm, logits ``= h E^T logit_scale`` with the
  embedding ``E`` (``tie_word_embeddings``).

Departures from the published model (``assumed`` in the configuration file):
weights are random from the seed, with the initialisers named below; ``seq``
is a serving cap far under the published 200,000 positions; text only (the
row's image tower has no configuration here); ``average`` is read as the
mean of the shared experts' outputs; the router has no correction bias and
no scaling factor (the config gives none); sampling is greedy; the experts
held are ``[expert_offset, expert_offset + num_experts)`` of the router's
``router_experts`` outputs and what absent experts would add is left out
(one chip's share of an expert-parallel deployment: model-configs guide
section 4); the vocabulary is the slice the configuration states.

Weights are made ONE BLOCK AT A TIME (a float32 block is 4.6 GB):
``make_params`` returns a handle, ``forward`` materialises each block, runs
it and lets it go.  Block ``i`` takes ``fold_in(PRNGKey(seed), i)``, the
embedding and the final norm ``n_layers``; inside a block flax folds the key
by the parameter's path (``benchmark/weights.py``); expert ``e``'s matrices
take ``fold_in(<the leaf's key>, e)`` with ``e`` the GLOBAL expert id, shared
expert ``j``'s ``fold_in(<the leaf's key>, j)`` (kept side by side in one
leaf, as the program serves them).  Attention is computed in blocks of
QUERIES (each block's softmax is over all keys at once), so that a
12,800-token sequence fits one chip beside a block's weights.  Imports
nothing of the program.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import weights
from ..weights import Leaf, dense, matmul
# what every block-at-a-time reference of this benchmark shares: the jitted
# birth of one part, the experts' initialiser by global id, a config's key
from .ref_nemotron_h import _born, _expert_stack, _hashable

_HI = jax.lax.Precision.HIGHEST
_lecun = nn.initializers.lecun_normal()
#: queries per block of the attention (a block's scores are heads x this x
#: length float32: 1.7 GB at 128 heads and 12,800 keys)
QUERY_BLOCK = 256


# ---------------------------------------------------------------------------
# initialisers this family adds, under new names (weights.py's own untouched)
# ---------------------------------------------------------------------------
def _side_by_side(n, axis):
    def init(key, shape, dtype=jnp.float32):
        one = tuple(s // n if a == axis else s for a, s in enumerate(shape))
        return jnp.concatenate(
            [_lecun(jax.random.fold_in(key, j), one, dtype) for j in range(n)], axis=axis)

    return init


def _register(cfg):
    """Names for ``weights.INITS`` that carry this configuration's numbers."""
    stack = f"expert_stack@{cfg['expert_offset']}"
    n = cfg["num_shared_experts"]
    weights.INITS.setdefault(stack, _expert_stack(cfg["expert_offset"]))
    for axis in (0, 1):
        weights.INITS.setdefault(f"side_by_side@{n}:{axis}", _side_by_side(n, axis))
    return stack, f"side_by_side@{n}:1", f"side_by_side@{n}:0"


def norm_spec(d):
    return (("scale", Leaf((d,), "ones")),)


def block_spec(cfg):
    """One layer's parameters in creation order: the norm, the attention
    (``mixer0``) and the experts (``mixer1``) of the parallel block."""
    stack, wide, tall = _register(cfg)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    held, n = cfg["num_experts"], cfg["num_shared_experts"]
    attn = (("q_proj", dense(d, q)), ("k_proj", dense(d, kv)),
            ("v_proj", dense(d, kv)), ("o_proj", dense(q, d)))
    moe = (
        ("router", (("kernel", Leaf((d, cfg["router_experts"]), "lecun_normal")),)),
        ("experts", (("gate", Leaf((held, d, f), stack)), ("up", Leaf((held, d, f), stack)),
                     ("down", Leaf((held, f, d), stack)))),
        ("shared_gate", (("kernel", Leaf((d, n * f), wide)),)),
        ("shared_up", (("kernel", Leaf((d, n * f), wide)),)),
        ("shared_down", (("kernel", Leaf((n * f, d), tall)),)),
    )
    return (("norm", norm_spec(d)), ("mixer0", attn), ("mixer1", moe))


def part(cfg, seed, name):
    """One part of the float32 tree: layer ``i`` (an int), ``"embed"`` or
    ``"norm_f"`` (the head is the embedding)."""
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    if name == "embed":
        spec, index = (("embedding", Leaf((cfg["vocab_size"], d), "embed")),), n
    elif name == "norm_f":
        spec, index = norm_spec(d), n
    else:
        spec, index = block_spec(cfg), name
    return _born(spec)(jnp.int32(seed), jnp.int32(index))


def make_params(cfg, seed):
    """A handle: the weights are made layer by layer inside ``forward``."""
    return {"seed": int(seed)}


# ---------------------------------------------------------------------------
# the layer, one sequence x (T, d)
# ---------------------------------------------------------------------------
def layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """``x`` (T, heads, head_dim) at positions 0 .. T-1: the interleaved
    pairs turned by ``p theta^(-2i / head_dim)``."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv  # (T, 1, dh/2)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def attention(x, p, cfg, sliding, precision):
    t = x.shape[0]
    h, j, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = matmul(x, p["q_proj"]["kernel"], precision).reshape(t, h, dh)
    k = matmul(x, p["k_proj"]["kernel"], precision).reshape(t, j, dh)
    v = matmul(x, p["v_proj"]["kernel"], precision).reshape(t, j, dh)
    if sliding:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    nq = min(QUERY_BLOCK, t)
    pad = (-t) % nq
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, nq, j, h // j, dh)
    p_k = jnp.arange(t)

    def block(xs):  # all keys at once for nq queries
        q, first = xs
        p_q = first + jnp.arange(nq)
        seen = p_q[:, None] >= p_k[None, :]
        if sliding:
            seen &= p_q[:, None] - p_k[None, :] < cfg["sliding_window"]
        s = jnp.einsum("qjgd,kjd->jgqk", q, k, precision=_HI) / dh ** 0.5
        s = jnp.where(seen[None, None], s, -1e30)
        return jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(s, -1), v, precision=_HI)

    a = jax.lax.map(block, (q, jnp.arange(q.shape[0]) * nq))
    return matmul(a.reshape(-1, h * dh)[:t], p["o_proj"]["kernel"], precision)


def route(x, p, cfg):
    """(chosen ids (T, k), weights (T, k), scores (T, E)): float32 whatever
    the precision of the products."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"], precision=_HI))
    _, ids = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / w.sum(-1, keepdims=True), s


def _expert(x, gate, up, down, precision):
    hid = jax.nn.silu(matmul(x, gate, precision)) * matmul(x, up, precision)
    return matmul(hid, down, precision)


def routed(x, p, cfg, precision="f32"):
    """The held experts' part: a loop over the held ids, each expert run over
    every token and masked by its weight (0 where it was not chosen)."""
    ids, w, _ = route(x, p, cfg)

    def one(acc, xs):
        e, gate, up, down = xs
        weight = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)  # (T,)
        return acc + weight[:, None] * _expert(x, gate, up, down, precision), None

    held = cfg["expert_offset"] + jnp.arange(cfg["num_experts"])
    ex = p["experts"]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (held, ex["gate"], ex["up"], ex["down"]))
    return acc


def shared(x, p, cfg, precision="f32"):
    """The mean of the shared experts, each the routed experts' form."""
    n, f = cfg["num_shared_experts"], cfg["intermediate_size"]
    gate, up, down = (p[k]["kernel"] for k in ("shared_gate", "shared_up", "shared_down"))
    outs = [_expert(x, gate[:, j * f:(j + 1) * f], up[:, j * f:(j + 1) * f],
                    down[j * f:(j + 1) * f], precision) for j in range(n)]
    return sum(outs) / n


def _layer(x, p, sliding, cfg, precision):
    h = layer_norm(x, p["norm"]["scale"], cfg["layer_norm_eps"])
    return (x + attention(h, p["mixer0"], cfg, sliding, precision)
            + routed(h, p["mixer1"], cfg, precision) + shared(h, p["mixer1"], cfg, precision))


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, precision):
    cfg = dict(cfg_items)
    layers = {sliding: jax.jit(functools.partial(
        _layer, sliding=sliding, cfg=cfg, precision=precision)) for sliding in (True, False)}
    head = jax.jit(lambda x, norm, embed: cfg["logit_scale"] * matmul(
        layer_norm(x, norm["scale"], cfg["layer_norm_eps"]), embed["embedding"].T, precision))
    return layers, head


def forward(params, tokens, cfg, precision="f32"):
    """Logits (T, vocab_size) float32 for one sequence of token ids (T,).
    ``precision="fp8"`` (the control) rounds both operands of every
    projection, expert and head product to fp8 as ``weights.matmul`` does;
    the router, the rotation and the softmax stay float32.  The caller pads
    sequences to one length: the layer is causal, so the padded tail has no
    influence on the positions before it."""
    seed = params["seed"]
    layers, head = _programs(_hashable(cfg), precision or "f32")
    with jax.default_matmul_precision("highest"):
        x = part(cfg, seed, "embed")["embedding"][jnp.asarray(tokens, jnp.int32)]
        for i, kind in enumerate(cfg["layer_types"]):
            x = layers[kind == "sliding_attention"](x, part(cfg, seed, i))
        return head(x, part(cfg, seed, "norm_f"), part(cfg, seed, "embed"))
