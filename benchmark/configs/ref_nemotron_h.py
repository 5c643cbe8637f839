"""Plain reference of the ``nemotron_h`` decoder (NVIDIA-Nemotron-3-Nano-30B-
A3B-BF16, https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
config.json): float32, ``highest`` matmul precision, one sequence, no cache,
no slots, no chunking.  Every block is ``x <- x + Mixer(RMSNorm(x))`` with
the mixer named by the block's letter in ``hybrid_override_pattern``:

* ``M`` Mamba-2 as a plain ``lax.scan`` over time steps: ``[z | xBC | dt] =
  x W_in``; ``xBC <- silu(causal_depthwise_conv(xBC) + b)``; ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h_t = exp(dt_t A)
  h_{t-1} + dt_t u_t B_t^T``, ``y_t = h_t C_t + D u_t`` (head ``h`` uses the
  B and C of group ``h // (heads / groups)``); ``y <- GroupRMSNorm(y
  silu(z)) w``; ``y W_out``.
* ``*`` full causal softmax attention with grouped-query heads at scale
  ``head_dim^-1/2``.
* ``E`` routed experts as a loop over the HELD expert ids with a mask: ``s =
  sigmoid(x W_r)``; the chosen are the top-k of ``s + b``; weights ``s_e /
  sum of the chosen s`` times ``routed_scaling_factor``; an expert is
  ``relu(x W_up)^2 W_down``; one shared expert is added for every token.

Departures from the published model (``assumed`` in the configuration file):
weights are random from the seed; the attention layers apply no positional
encoding (``nemotron_h`` applies none; ``rope_theta`` is unused); the experts
held are ``[expert_offset, expert_offset + n_routed_experts)`` of the router's
``router_experts`` outputs and what absent experts would add is left out
(one chip's share of an expert-parallel deployment: model-configs guide
section 4); the vocabulary is the slice the configuration states; the scan
state is float32; ``e_score_correction_bias`` is normal(0.02), not zeros.

Weights are made ONE BLOCK AT A TIME (the cut's float32 share is 21 GB):
``make_params`` returns a handle, ``forward`` materialises each block, runs
it and lets it go.  Block ``i`` takes ``fold_in(PRNGKey(seed), i)``, the
embedding ``n_layers`` and the head ``n_layers + 1``; inside a block flax
folds the key by the parameter's path (``benchmark/weights.py``); expert
``e``'s matrices take ``fold_in(<the leaf's key>, e)`` with ``e`` the global
expert id.  Imports nothing of the program.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import weights
from ..weights import Leaf, dense, matmul

_HI = jax.lax.Precision.HIGHEST
_lecun = nn.initializers.lecun_normal()


# ---------------------------------------------------------------------------
# initialisers this family adds, under new names (weights.py's own untouched)
# ---------------------------------------------------------------------------
def _a_log(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias(lo, hi, floor):
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (np.log(hi) - np.log(lo))
                     + np.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus

    return init


def _expert_stack(offset):
    def init(key, shape, dtype=jnp.float32):
        ids = offset + jnp.arange(shape[0])
        return jax.vmap(lambda e: _lecun(jax.random.fold_in(key, e), shape[1:], dtype))(ids)

    return init


def _register(cfg):
    """Names for ``weights.INITS`` that carry this configuration's numbers."""
    dt = f"mamba_dt_bias@{cfg['time_step_min']}:{cfg['time_step_max']}:{cfg['time_step_floor']}"
    stack = f"expert_stack@{cfg['expert_offset']}"
    weights.INITS.setdefault("mamba_a_log", _a_log)
    weights.INITS.setdefault(dt, _dt_bias(
        cfg["time_step_min"], cfg["time_step_max"], cfg["time_step_floor"]))
    weights.INITS.setdefault(stack, _expert_stack(cfg["expert_offset"]))
    return dt, stack


def _sizes(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "H": h, "P": p, "G": g, "N": n, "di": h * p,
            "C": h * p + 2 * g * n, "K": cfg["conv_kernel"]}


def rms_norm(d):
    return (("scale", Leaf((d,), "ones")),)


def block_spec(cfg, kind):
    """One block's parameters in creation order."""
    dt_init, stack = _register(cfg)
    z = _sizes(cfg)
    d = z["d"]
    if kind == "M":
        mixer = (
            ("in_proj", dense(d, 2 * z["di"] + 2 * z["G"] * z["N"] + z["H"])),
            ("conv", (("kernel", Leaf((z["K"], 1, z["C"]), "lecun_normal")),
                      ("bias", Leaf((z["C"],), "zeros")))),
            ("dt_bias", Leaf((z["H"],), dt_init)),
            ("A_log", Leaf((z["H"],), "mamba_a_log")),
            ("D", Leaf((z["H"],), "ones")),
            ("norm", rms_norm(z["di"])),
            ("out_proj", dense(z["di"], d)),
        )
    elif kind == "*":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        mixer = (("q_proj", dense(d, q)), ("k_proj", dense(d, kv)),
                 ("v_proj", dense(d, kv)), ("o_proj", dense(q, d)))
    elif kind == "E":
        held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = cfg["moe_shared_expert_intermediate_size"]
        mixer = (
            ("router", (("kernel", Leaf((d, cfg["router_experts"]), "lecun_normal")),
                        ("bias", Leaf((cfg["router_experts"],), "normal0.02")))),
            ("experts", (("up", Leaf((held, d, f), stack)),
                         ("down", Leaf((held, f, d), stack)))),
            ("shared_up", dense(d, fs)),
            ("shared_down", dense(fs, d)),
        )
    else:
        raise ValueError(f"hybrid_override_pattern: unknown block kind {kind!r}")
    return (("norm", rms_norm(d)), ("mixer", mixer))


@functools.lru_cache(maxsize=None)
def _born(spec):
    twin = weights._Twin(spec)
    return jax.jit(lambda s, i: twin.init(
        jax.random.fold_in(jax.random.PRNGKey(s), i))["params"])


def part(cfg, seed, name):
    """One part of the float32 tree: block ``i`` (an int), ``"embed"``,
    ``"norm_f"`` or ``"lm_head"``."""
    n, d = len(cfg["hybrid_override_pattern"]), cfg["hidden_size"]
    if name == "embed":
        spec, index = (("embedding", Leaf((cfg["vocab_size"], d), "embed")),), n
    elif name == "norm_f":
        spec, index = rms_norm(d), n
    elif name == "lm_head":
        spec, index = dense(d, cfg["vocab_size"]), n + 1
    else:
        spec, index = block_spec(cfg, cfg["hybrid_override_pattern"][name]), name
    return _born(spec)(jnp.int32(seed), jnp.int32(index))


def make_params(cfg, seed):
    """A handle: the weights are made block by block inside ``forward``."""
    return {"seed": int(seed)}


# ---------------------------------------------------------------------------
# the mixers, one sequence x (T, d)
# ---------------------------------------------------------------------------
def _rms(x, scale, eps, groups=1):
    g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def _mamba(x, p, cfg, precision):
    z_ = _sizes(cfg)
    H, P, G, N, di, K = (z_[k] for k in ("H", "P", "G", "N", "di", "K"))
    t = x.shape[0]
    zxd = matmul(x, p["in_proj"]["kernel"], precision)
    z, xbc, dt = jnp.split(zxd, [di, di + z_["C"]], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], axis=0)
    taps = p["conv"]["kernel"][:, 0]  # (K, C)
    xbc = jax.nn.silu(sum(padded[k:k + t] * taps[k] for k in range(K)) + p["conv"]["bias"])
    u, bm, cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    u = u.reshape(t, H, P)
    # head h reads the B and C of group h // (H / G)
    bm = jnp.repeat(bm.reshape(t, G, N), H // G, axis=1)
    cm = jnp.repeat(cm.reshape(t, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (t, H)
    a = -jnp.exp(p["A_log"])

    def step(h, xs):  # h (H, P, N)
        u_t, b_t, c_t, dt_t = xs
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * u_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t, precision=_HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (u, bm, cm, dt))
    y = (y + p["D"][:, None] * u).reshape(t, di) * jax.nn.silu(z)
    y = _rms(y, p["norm"]["scale"], cfg["norm_eps"], groups=G)
    return matmul(y, p["out_proj"]["kernel"], precision)


def _attention(x, p, cfg, precision):
    t = x.shape[0]
    h, j, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = matmul(x, p["q_proj"]["kernel"], precision).reshape(t, h, dh)
    k = matmul(x, p["k_proj"]["kernel"], precision).reshape(t, j, dh)
    v = matmul(x, p["v_proj"]["kernel"], precision).reshape(t, j, dh)
    k, v = jnp.repeat(k, h // j, axis=1), jnp.repeat(v, h // j, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / dh ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=_HI)
    return matmul(a.reshape(t, h * dh), p["o_proj"]["kernel"], precision)


def route(x, p, cfg):
    """(chosen ids (T, k), weights (T, k), scores (T, E)): float32 whatever
    the precision of the products."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"], precision=_HI))
    _, ids = jax.lax.top_k(s + p["router"]["bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"], s


def _expert(x, up, down, precision):
    return matmul(jnp.square(jax.nn.relu(matmul(x, up, precision))), down, precision)


def routed(x, p, cfg, precision="f32"):
    """The held experts' part: a loop over the held ids, each expert run over
    every token and masked by its weight (0 where it was not chosen)."""
    ids, w, _ = route(x, p, cfg)

    def one(acc, xs):
        e, up, down = xs
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)  # (T,)
        return acc + gate[:, None] * _expert(x, up, down, precision), None

    held = cfg["expert_offset"] + jnp.arange(cfg["n_routed_experts"])
    acc, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (held, p["experts"]["up"], p["experts"]["down"]))
    return acc


def shared(x, p, precision="f32"):
    return _expert(x, p["shared_up"]["kernel"], p["shared_down"]["kernel"], precision)


def _block(x, p, kind, cfg, precision):
    h = _rms(x, p["norm"]["scale"], cfg["norm_eps"])
    m = p["mixer"]
    if kind == "M":
        return x + _mamba(h, m, cfg, precision)
    if kind == "*":
        return x + _attention(h, m, cfg, precision)
    return x + routed(h, m, cfg, precision) + shared(h, m, precision)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, precision):
    cfg = dict(cfg_items)
    blocks = {kind: jax.jit(functools.partial(
        _block, kind=kind, cfg=cfg, precision=precision)) for kind in "ME*"}
    head = jax.jit(lambda x, norm, w: matmul(
        _rms(x, norm["scale"], cfg["norm_eps"]), w["kernel"], precision))
    return blocks, head


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def forward(params, tokens, cfg, precision="f32"):
    """Logits (T, vocab_size) float32 for one sequence of token ids (T,).
    ``precision="fp8"`` (the control) rounds both operands of every
    projection, expert and head product to fp8 as ``weights.matmul`` does;
    the router, the convolution, the scan and the softmax stay float32.  The
    caller pads sequences to one length: every mixer is causal, so the padded
    tail has no influence on the positions before it."""
    seed = params["seed"]
    blocks, head = _programs(_hashable(cfg), precision)
    with jax.default_matmul_precision("highest"):
        x = part(cfg, seed, "embed")["embedding"][jnp.asarray(tokens, jnp.int32)]
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            x = blocks[kind](x, part(cfg, seed, i))
        return head(x, part(cfg, seed, "norm_f"), part(cfg, seed, "lm_head"))
