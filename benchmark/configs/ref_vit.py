"""Plain reference of the ViT classifier (arXiv:2010.11929): float32,
``highest`` matmul precision, no batching tricks, one encoder layer at a time.

Departures from the paper, all the zoo's (``assumed`` in the configuration
file): pixels are scaled to [-1, 1] inside the model, GELU is the tanh
approximation, LayerNorm's epsilon is flax's 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..weights import Leaf, dense, init_params, layer_norm, matmul


def param_spec(cfg):
    d, f, p = cfg["d_model"], cfg["d_ff"], cfg["patch"]
    tokens = (cfg["size"] // p) ** 2 + 1
    block = (("ln1", layer_norm(d)), ("attn_qkv", dense(d, 3 * d)),
             ("attn_out", dense(d, d)), ("ln2", layer_norm(d)),
             ("mlp_up", dense(d, f)), ("mlp_down", dense(f, d)))
    return (
        ("patch_embed", (("kernel", Leaf((p, p, 3, d), "lecun_normal")),
                         ("bias", Leaf((d,), "zeros")))),
        ("cls", Leaf((1, 1, d), "zeros")),
        ("pos_embed", Leaf((1, tokens, d), "normal0.02")),
    ) + tuple((f"block{i}", block) for i in range(cfg["layers"])) + (
        ("ln_f", layer_norm(d)), ("head", dense(d, cfg["classes"], bias=True)))


def make_params(cfg, seed):
    return init_params(param_spec(cfg), seed)["params"]


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def _embed(p, images, patch, precision):
    x = images.astype(jnp.float32) * (2.0 / 255.0) - 1.0
    b, s, _, c = x.shape
    g = s // patch
    x = x.reshape(b, g, patch, g, patch, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, g * g, patch * patch * c)
    w = p["patch_embed"]["kernel"].reshape(patch * patch * c, -1)
    x = matmul(x, w, precision) + p["patch_embed"]["bias"]
    cls = jnp.broadcast_to(p["cls"], (b, 1, x.shape[-1]))
    return jnp.concatenate([cls, x], axis=1) + p["pos_embed"]


def _block(x, p, heads, precision):
    b, t, d = x.shape
    qkv = matmul(_ln(x, p["ln1"]), p["attn_qkv"]["kernel"], precision)
    q, k, v = (a.reshape(b, t, heads, d // heads) for a in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / (d // heads) ** 0.5
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision="highest")
    x = x + matmul(a.reshape(b, t, d), p["attn_out"]["kernel"], precision)
    h = jax.nn.gelu(matmul(_ln(x, p["ln2"]), p["mlp_up"]["kernel"], precision))
    return x + matmul(h, p["mlp_down"]["kernel"], precision)


def _head(x, p):
    x = _ln(x, p["ln_f"])[:, 0]
    return matmul(x, p["head"]["kernel"], "f32") + p["head"]["bias"]


def forward(params, images_u8, cfg, precision="f32", rows=16):
    """Logits (N, classes) float32 for uint8 images (N, S, S, 3), ``rows``
    images at a time and layer by layer, so that it fits beside nothing."""
    embed = jax.jit(lambda p, x: _embed(p, x, cfg["patch"], precision))
    block = jax.jit(lambda x, p: _block(x, p, cfg["heads"], precision))
    head = jax.jit(_head)
    n = images_u8.shape[0]
    pad = -n % rows  # whole blocks only: one shape, one compiled program
    if pad:
        images_u8 = np.concatenate([images_u8, np.repeat(images_u8[-1:], pad, 0)])
    out = []
    for i in range(0, n + pad, rows):
        x = embed(params, jnp.asarray(images_u8[i:i + rows]))
        for layer in range(cfg["layers"]):
            x = block(x, params[f"block{layer}"])
        out.append(head(x, params))
    return jnp.concatenate(out, axis=0)[:n]
