"""Plain reference of the ``lfm2_moe`` decoder (LiquidAI LFM2-8B-A1B,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json; the
equations of transformers' ``modeling_lfm2_moe.py``): float32, ``highest``
matmul precision, one sequence, no cache, no slots, no chunking of the
mathematics.  Every layer is TWO sequential residual steps:

    x <- x + Op(RMSNorm_operator(x))
    x <- x + FF(RMSNorm_ffn(x))

* ``Op`` of a ``conv`` layer, the gated short convolution: ``[B | C | u] = h
  W_in`` (``hidden -> 3 hidden``, no bias); ``g = B * u``; ``c_t = sum_k
  w[:, k] * g_{t - (L-1) + k}`` over the ``L = conv_L_cache`` taps (depthwise,
  causal, no bias, no activation), written here as a plain sum over ``L``
  shifted copies of ``g``; ``(C * c) W_out``.
* ``Op`` of a ``full_attention`` layer: ``q = h W_q`` (``num_attention_heads``
  x ``head_dim``), ``k = h W_k``, ``v = h W_v`` (``num_key_value_heads`` x
  ``head_dim``; a group of queries shares a KV head), no bias; ``q`` and
  ``k`` RMS-normed over ``head_dim`` with a learned weight (``q_layernorm``,
  ``k_layernorm``), then turned by rotary positions in the HALF-SPLIT pair
  layout (``x[i]`` with ``x[i + head_dim/2]`` by ``p rope_theta^(-2i /
  head_dim)``, every dimension: ``rotate_half``); causal softmax at
  ``head_dim^-1/2`` over every earlier position; ``W_o``.
* ``FF`` of layer ``l < num_dense_layers``: ``W_2(silu(h W_1) * (h W_3))`` of
  width ``intermediate_size``.
* ``FF`` of the other layers: ``s = sigmoid(h W_r)`` in float32 over
  ``num_experts``; the chosen are the top-``num_experts_per_tok`` of ``s +
  expert_bias``; weights ``s_e / (sum of the chosen s + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; an expert is
  ``W_2e(silu(h W_1e) * (h W_3e))`` of width ``moe_intermediate_size``; no
  shared expert.  The routed part is a loop over the expert ids with a mask.
* after the last layer: RMSNorm (``embedding_norm``), logits ``= h E^T``
  with the embedding ``E``, read unscaled at the input.

Departures from the published model (``assumed`` in the configuration file):
weights are random from the seed, with the initialisers named below
(``expert_bias`` normal(0.02) rather than zeros, so that the bias moves the
choice; the conv taps ``lecun_normal`` over their 3 inputs); the head is the
embedding (``tie_word_embeddings``: the catalog row does not carry the key;
tied, the parameters count the published 8.3 B, untied 8.47 B); ``seq`` is a
serving cap far under the published 128,000 positions; sampling is greedy.

Weights are made ONE BLOCK AT A TIME (an expert layer is 1.4 GB in float32):
``make_params`` returns a handle, ``forward`` materialises each block, runs
it and lets it go.  A layer is two blocks of the program's pattern (its
operator, then its feed-forward): block ``i`` takes ``fold_in(PRNGKey(seed),
i)``, the embedding and the final norm ``2 x num_hidden_layers``; inside a
block flax folds the key by the parameter's path (``benchmark/weights.py``);
expert ``e``'s matrices take ``fold_in(<the leaf's key>, e)``.  Attention is
computed in blocks of QUERIES (each block's softmax is over all keys at
once).  Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import weights
from ..weights import Leaf, dense, matmul
# what every block-at-a-time reference of this benchmark shares: the jitted
# birth of one part, the experts' initialiser by global id, a config's key
from .ref_nemotron_h import _born, _expert_stack, _hashable

_HI = jax.lax.Precision.HIGHEST
#: queries per block of the attention (a block's scores are heads x this x
#: length float32: 302 MB at 32 heads and 4608 keys)
QUERY_BLOCK = 512
#: the published ``+ 1e-6`` in the normalising sum of the chosen scores
ROUTE_EPS = 1e-6


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def pattern(cfg):
    """The program's pattern string of this configuration, derived from
    ``layer_types`` and ``num_dense_layers``: a layer is its operator's
    letter (``C`` conv, ``*`` attention) and its feed-forward's (``D``
    dense, ``E`` experts)."""
    return "".join(
        ("*" if kind == "full_attention" else "C")
        + ("D" if l < cfg["num_dense_layers"] else "E")
        for l, kind in enumerate(cfg["layer_types"]))


def norm_spec(d):
    return (("scale", Leaf((d,), "ones")),)


def block_spec(cfg, letter):
    """One block's parameters in creation order: its norm and its mixer."""
    weights.INITS.setdefault("expert_stack@0", _expert_stack(0))
    d, dh = cfg["hidden_size"], head_dim(cfg)
    if letter == "C":
        mixer = (("in_proj", dense(d, 3 * d)),
                 ("conv", (("kernel", Leaf((cfg["conv_L_cache"], 1, d), "lecun_normal")),)),
                 ("out_proj", dense(d, d)))
    elif letter == "*":
        q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
        mixer = (("q_proj", dense(d, q)), ("k_proj", dense(d, kv)),
                 ("v_proj", dense(d, kv)), ("o_proj", dense(q, d)),
                 ("q_norm", norm_spec(dh)), ("k_norm", norm_spec(dh)))
    elif letter == "D":
        f = cfg["intermediate_size"]
        mixer = (("gate_proj", dense(d, f)), ("up_proj", dense(d, f)),
                 ("down_proj", dense(f, d)))
    else:
        n, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        mixer = (
            ("router", (("kernel", Leaf((d, n), "lecun_normal")),
                        ("bias", Leaf((n,), "normal0.02")))),
            ("experts", (("gate", Leaf((n, d, f), "expert_stack@0")),
                         ("up", Leaf((n, d, f), "expert_stack@0")),
                         ("down", Leaf((n, f, d), "expert_stack@0")))),
        )
    return (("norm", norm_spec(d)), ("mixer", mixer))


def part(cfg, seed, name):
    """One part of the float32 tree: block ``i`` of the pattern (an int: layer
    ``i // 2``'s operator where ``i`` is even, its feed-forward where odd),
    ``"embed"`` or ``"norm_f"`` (the head is the embedding)."""
    letters, d = pattern(cfg), cfg["hidden_size"]
    if name == "embed":
        spec, index = (("embedding", Leaf((cfg["vocab_size"], d), "embed")),), len(letters)
    elif name == "norm_f":
        spec, index = norm_spec(d), len(letters)
    else:
        spec, index = block_spec(cfg, letters[name]), name
    return _born(spec)(jnp.int32(seed), jnp.int32(index))


def make_params(cfg, seed):
    """A handle: the weights are made block by block inside ``forward``."""
    return {"seed": int(seed)}


# ---------------------------------------------------------------------------
# the layer, one sequence x (T, d)
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """``x`` (T, heads, head_dim) at positions 0 .. T-1: the half-split pairs
    ``(x[i], x[i + head_dim/2])`` turned by ``p theta^(-2i / head_dim)``."""
    t, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv  # (T, 1, dh/2)
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def short_conv(x, p, cfg, precision="f32"):
    """The gated short convolution: a plain sum over ``L`` shifted copies."""
    t, taps = x.shape[0], cfg["conv_L_cache"]
    b, c, u = jnp.split(matmul(x, p["in_proj"]["kernel"], precision), 3, axis=-1)
    g = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))  # nothing before position 0
    w = p["conv"]["kernel"][:, 0]  # (L, d)
    conv = sum(g[k:k + t] * w[k] for k in range(taps))
    return matmul(c * conv, p["out_proj"]["kernel"], precision)


def attention(x, p, cfg, precision="f32"):
    t = x.shape[0]
    h, j, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = matmul(x, p["q_proj"]["kernel"], precision).reshape(t, h, dh)
    k = matmul(x, p["k_proj"]["kernel"], precision).reshape(t, j, dh)
    v = matmul(x, p["v_proj"]["kernel"], precision).reshape(t, j, dh)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], cfg["norm_eps"]), cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], cfg["norm_eps"]), cfg["rope_theta"])
    nq = min(QUERY_BLOCK, t)
    pad = (-t) % nq
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, nq, j, h // j, dh)
    p_k = jnp.arange(t)

    def block(xs):  # all keys at once for nq queries
        q, first = xs
        seen = (first + jnp.arange(nq))[:, None] >= p_k[None, :]
        s = jnp.einsum("qjgd,kjd->jgqk", q, k, precision=_HI) / dh ** 0.5
        s = jnp.where(seen[None, None], s, -1e30)
        return jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(s, -1), v, precision=_HI)

    a = jax.lax.map(block, (q, jnp.arange(q.shape[0]) * nq))
    return matmul(a.reshape(-1, h * dh)[:t], p["o_proj"]["kernel"], precision)


def _gated(x, gate, up, down, precision):
    hid = jax.nn.silu(matmul(x, gate, precision)) * matmul(x, up, precision)
    return matmul(hid, down, precision)


def dense_mlp(x, p, cfg, precision="f32"):
    del cfg
    return _gated(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                  p["down_proj"]["kernel"], precision)


def route(x, p, cfg):
    """(chosen ids (T, k), weights (T, k)): float32 whatever the precision
    of the products."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"], precision=_HI))
    _, ids = jax.lax.top_k(s + p["router"]["bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (w.sum(-1, keepdims=True) + ROUTE_EPS) * cfg["routed_scaling_factor"]


def routed(x, p, cfg, precision="f32"):
    """A loop over the expert ids, each expert run over every token and
    masked by its weight (0 where it was not chosen)."""
    ids, w = route(x, p, cfg)

    def one(acc, xs):
        e, gate, up, down = xs
        weight = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)  # (T,)
        return acc + weight[:, None] * _gated(x, gate, up, down, precision), None

    ex = p["experts"]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(cfg["num_experts"]), ex["gate"], ex["up"], ex["down"]))
    return acc


MIXERS = {"C": short_conv, "*": attention, "D": dense_mlp, "E": routed}


def _block(x, p, letter, cfg, precision):
    h = rms_norm(x, p["norm"]["scale"], cfg["norm_eps"])
    return x + MIXERS[letter](h, p["mixer"], cfg, precision)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, precision):
    cfg = dict(cfg_items)
    blocks = {letter: jax.jit(functools.partial(
        _block, letter=letter, cfg=cfg, precision=precision)) for letter in MIXERS}
    head = jax.jit(lambda x, norm, embed: matmul(
        rms_norm(x, norm["scale"], cfg["norm_eps"]), embed["embedding"].T, precision))
    return blocks, head


def forward(params, tokens, cfg, precision="f32"):
    """Logits (T, vocab_size) float32 for one sequence of token ids (T,).
    ``precision="fp8"`` (the control) rounds both operands of every
    projection, MLP, expert and head product to fp8 as ``weights.matmul``
    does; the router, the convolution's sum, the norms, the rotation and the
    softmax stay float32.  The caller pads sequences to one length: every
    mixer is causal, so the padded tail has no influence on the positions
    before it."""
    seed = params["seed"]
    # layer_types is a list: the pattern string stands for it in the key
    blocks, head = _programs(_hashable({**cfg, "_pattern": pattern(cfg)}), precision or "f32")
    with jax.default_matmul_precision("highest"):
        x = part(cfg, seed, "embed")["embedding"][jnp.asarray(tokens, jnp.int32)]
        for i, letter in enumerate(pattern(cfg)):
            x = blocks[letter](x, part(cfg, seed, i))
        return head(x, part(cfg, seed, "norm_f"), part(cfg, seed, "embed"))
