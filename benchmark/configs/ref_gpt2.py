"""Plain reference of the GPT-2 decoder (Radford et al. 2019): float32,
``highest`` matmul precision, full causal attention over the whole sequence,
no cache, no slots, one layer at a time.

Departures from the published model, all the zoo's (``assumed`` in the
configuration file): the output head is untied from the embedding and has no
bias, the projections carry no bias, LayerNorm's epsilon is flax's 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..weights import Leaf, dense, init_params, layer_norm, matmul


def param_spec(cfg):
    d, f = cfg["d_model"], cfg["d_ff"]
    block = (("ln1", layer_norm(d)), ("attn_qkv", dense(d, 3 * d)),
             ("attn_out", dense(d, d)), ("ln2", layer_norm(d)),
             ("mlp_up", dense(d, f)), ("mlp_down", dense(f, d)))
    return (
        ("embed", (("embedding", Leaf((cfg["vocab"], d), "embed")),)),
        ("pos_embed", (("embedding", Leaf((cfg["seq"], d), "embed")),)),
    ) + tuple((f"block{i}", block) for i in range(cfg["layers"])) + (
        ("ln_f", layer_norm(d)), ("lm_head", dense(d, cfg["vocab"])))


def make_params(cfg, seed):
    return init_params(param_spec(cfg), seed)["params"]


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def _block(x, p, heads, precision):
    t, d = x.shape
    qkv = matmul(_ln(x, p["ln1"]), p["attn_qkv"]["kernel"], precision)
    q, k, v = (a.reshape(t, heads, d // heads) for a in jnp.split(qkv, 3, -1))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / (d // heads) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision="highest")
    x = x + matmul(a.reshape(t, d), p["attn_out"]["kernel"], precision)
    h = jax.nn.gelu(matmul(_ln(x, p["ln2"]), p["mlp_up"]["kernel"], precision))
    return x + matmul(h, p["mlp_down"]["kernel"], precision)


def forward(params, tokens, cfg, precision="f32"):
    """Logits (T, vocab) float32 for one sequence of token ids (T,).  The
    caller pads sequences to one length: causal attention leaves the padded
    tail without influence on the positions before it."""
    embed = jax.jit(lambda p, t: p["embed"]["embedding"][t]
                    + p["pos_embed"]["embedding"][jnp.arange(t.shape[0])])
    block = jax.jit(lambda x, p: _block(x, p, cfg["heads"], precision))
    head = jax.jit(lambda x, p: matmul(_ln(x, p["ln_f"]), p["lm_head"]["kernel"], "f32"))
    x = embed(params, jnp.asarray(tokens, jnp.int32))
    for layer in range(cfg["layers"]):
        x = block(x, params[f"block{layer}"])
    return head(x, params)
