"""Hybrid decoder LM for the slotted generation path: one mixer per block,
chosen by a pattern string (the ``nemotron_h`` family).

Every block is ``x <- x + Mixer(RMSNorm(x))``; the pattern names the mixer
of each block by one letter:

* ``M`` — Mamba-2: ``[z | xBC | dt] = x W_in``; a causal depthwise
  convolution and ``silu`` over ``xBC``; ``h_t = exp(dt_t A) h_{t-1} + dt_t
  u_t B_t^T`` per head in float32, ``y_t = h_t C_t + D u_t``; a grouped
  RMSNorm gated by ``silu(z)``; ``W_out``.  Two forms that agree: the
  chunked scan of the Mamba-2 paper for a prefill chunk (from the state the
  slot's previous chunk left) and the one-step recurrence in the decode
  scan.
* ``*`` — grouped-query attention without positional encoding, through
  :func:`~nnstreamer_tpu.models.transformer.kv_attend_write` (the one cache
  step every generation path shares).
* ``E`` — routed experts: sigmoid router in float32 over ALL ``experts``,
  top-``top_k`` of score + bias, weights normalised over the chosen and
  scaled; an expert is ``relu(x W_up)^2 W_down``; one shared expert runs for
  every token.  The layer is TOLD which experts it holds
  (``[expert_offset, expert_offset + experts_held)``): it routes over all of
  them and computes its own experts' part — tokens sorted by expert, one
  grouped product over the held experts, no capacity limit, no token a held
  expert was chosen for ever dropped.  What absent experts would add is left
  out (on one chip the layer runs without its exchange).

A slot owns state of two kinds: K/V rows by position per attention layer,
and per Mamba-2 layer a conv window and a scan state with NO position axis.
:class:`HybridSlotModel` implements what ``core/slots.py`` calls on a slot
model (``core.slots.SlotModelProtocol``).

Parameters are born one block at a time in float32 and cast to the model
dtype on the device; the seed is an ARGUMENT of the init programs (one
compile-cache entry per layer kind).  Block ``i`` takes
``fold_in(PRNGKey(seed), i)``, the embedding ``n_layers`` and the head
``n_layers + 1``; inside a block flax folds the key by the parameter's path.
Expert ``e``'s matrices take ``fold_in(<the leaf's key>, e)`` with ``e`` the
GLOBAL expert id, so two shares of one layer hold slices of the same experts.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.expert_ffn import touched_experts_ffn
from .transformer import (
    _make_pick, config_resume_fields, kv_attend_write, pick_slots,
)

FAMILY = "nemotron_h"
#: always-on counters the decode scan and the prefill chunks sum over their
#: steps and ``E`` layers (the engine adds them to ``snapshot()``): choices
#: that fell on a held expert, distinct held experts with a token, tokens on
#: the busiest held expert, (layer, step) pairs counted; and the prefill
#: chunks' part of the first two
COUNTER_NAMES = ("gen_moe_local", "gen_moe_expert_reads", "gen_moe_max_load",
                 "gen_moe_layer_steps", "gen_moe_prefill_local",
                 "gen_moe_prefill_reads")
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    pattern: str = "MEM*EME"
    vocab: int = 256
    d_model: int = 64
    # Mamba-2
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    scan_chunk: int = 128
    # grouped-query attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    # routed experts: the router's width, the share held here, experts per token
    experts: int = 8
    experts_held: int = 8
    expert_offset: int = 0
    top_k: int = 2
    d_expert: int = 32
    d_shared: int = 64
    routed_scale: float = 2.5
    norm_eps: float = 1e-5
    max_seq: int = 256
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.pattern) - set("ME*")
        if bad or not self.pattern:
            raise ValueError(
                f"layers pattern {self.pattern!r}: one of M, E, * per block")
        if self.ssm_heads % self.ssm_groups or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide by their groups")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held <= self.experts
                and 1 <= self.top_k <= self.experts):
            raise ValueError(
                f"experts held [{self.expert_offset}, "
                f"{self.expert_offset + self.experts_held}) of {self.experts}, "
                f"top {self.top_k}: not a share of the router's width")

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_conv(self) -> int:  # the xBC channels the convolution runs over
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def cfg_from_props(props: Dict[str, str]) -> HybridConfig:
    """The ``custom=`` dialect of this family (Documentation/examples.md):
    ``layers`` is the pattern string; every other key is a number."""
    d = HybridConfig()

    def num(key, default, cast=int):
        return cast(props.get(key, default))

    return HybridConfig(
        pattern=props.get("layers", d.pattern),
        vocab=num("vocab", d.vocab),
        d_model=num("d_model", d.d_model),
        ssm_heads=num("ssm_heads", d.ssm_heads),
        ssm_head_dim=num("ssm_head_dim", d.ssm_head_dim),
        ssm_groups=num("ssm_groups", d.ssm_groups),
        ssm_state=num("ssm_state", d.ssm_state),
        conv_kernel=num("conv", d.conv_kernel),
        scan_chunk=num("scan_chunk", d.scan_chunk),
        n_heads=num("heads", d.n_heads),
        n_kv_heads=num("kv_heads", d.n_kv_heads),
        head_dim=num("head_dim", d.head_dim),
        experts=num("experts", d.experts),
        experts_held=num("experts_held", props.get("experts", d.experts_held)),
        expert_offset=num("expert_offset", d.expert_offset),
        top_k=num("experts_per_tok", d.top_k),
        d_expert=num("d_expert", d.d_expert),
        d_shared=num("d_shared", d.d_shared),
        routed_scale=num("routed_scale", d.routed_scale, float),
        norm_eps=num("eps", d.norm_eps, float),
        max_seq=num("seq", d.max_seq),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            props.get("dtype", "bfloat16")],
    )


def resume_fields(props: Dict[str, str]) -> Dict[str, Any]:
    """EVERY field of the config (the expert share among them), the seeds
    and the sampling rule."""
    return config_resume_fields(cfg_from_props(props), props)


# ---------------------------------------------------------------------------
# parameters: a names-only flax tree per block, born float32, cast on device
# ---------------------------------------------------------------------------
_lecun = nn.initializers.lecun_normal()


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, _F32, 1.0, 16.0))


def _dt_bias(key, shape, lo=1e-3, hi=0.1, floor=1e-4):
    dt = jnp.exp(jax.random.uniform(key, shape, _F32) * (np.log(hi) - np.log(lo))
                 + np.log(lo))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1


def _expert_stack(offset, true_shape):
    """(held, ...): expert ``offset + i`` is the ``lecun_normal`` matrix of
    ``true_shape`` from ``fold_in(key, offset + i)``, so a share holds a slice
    of the whole layer's experts; zeros pad it to the stored shape."""

    def init(key, shape):
        ids = offset + jnp.arange(shape[0])
        full = jax.vmap(
            lambda e: _lecun(jax.random.fold_in(key, e), true_shape))(ids)
        return jnp.pad(full, [(0, 0)] + [
            (0, n - t) for n, t in zip(shape[1:], true_shape)])

    return init


class _Tree(nn.Module):
    """Holds nothing but names: ``spec`` is a tuple of ``(name, (shape, init)
    | nested spec)`` in creation order; flax folds each key by its path."""
    spec: Any

    @nn.compact
    def __call__(self):
        for name, sub in self.spec:
            if len(sub) == 2 and callable(sub[1]):
                self.param(name, sub[1], sub[0])
            else:
                _Tree(sub, name=name)()


#: leaves that stay float32 whatever the model dtype
_KEEP_F32 = ("scale", "A_log", "D", "dt_bias", "router")


def _norm(d):
    return (("scale", ((d,), nn.initializers.ones)),)


def _dense(d_in, d_out):
    return (("kernel", ((d_in, d_out), _lecun)),)


def block_spec(cfg: HybridConfig, kind: str):
    d = cfg.d_model
    if kind == "M":
        h, c = cfg.ssm_heads, cfg.d_conv
        mixer = (
            ("in_proj", _dense(d, 2 * cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state + h)),
            ("conv", (("kernel", ((cfg.conv_kernel, 1, c), _lecun)),
                      ("bias", ((c,), nn.initializers.zeros)))),
            ("dt_bias", ((h,), _dt_bias)),
            ("A_log", ((h,), _a_log)),
            ("D", ((h,), nn.initializers.ones)),
            ("norm", _norm(cfg.d_inner)),
            ("out_proj", _dense(cfg.d_inner, d)),
        )
    elif kind == "*":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        mixer = (("q_proj", _dense(d, q)), ("k_proj", _dense(d, kv)),
                 ("v_proj", _dense(d, kv)), ("o_proj", _dense(q, d)))
    else:
        held, f = cfg.experts_held, cfg.d_expert
        # an expert's width is stored padded with zeros to whole 128-lane
        # tiles (1856 -> 1920): a stack whose minor dim is not whole tiles
        # is kept transposed on a TPU, and the grouped product then copies
        # all of it at every dispatch
        fp = -(-f // 128) * 128
        up = _expert_stack(cfg.expert_offset, (d, f))
        down = _expert_stack(cfg.expert_offset, (f, d))
        mixer = (
            ("router", (("kernel", ((d, cfg.experts), _lecun)),
                        ("bias", ((cfg.experts,), nn.initializers.normal(0.02))))),
            ("experts", (("up", ((held, d, fp), up)), ("down", ((held, fp, d), down)))),
            ("shared_up", _dense(d, cfg.d_shared)),
            ("shared_down", _dense(cfg.d_shared, d)),
        )
    return (("norm", _norm(d)), ("mixer", mixer))


def _cast(tree, dtype):
    def one(path, leaf):
        names = {getattr(p, "key", None) for p in path}
        return leaf if names & set(_KEEP_F32) else leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def init_params(cfg: HybridConfig, seed: int, device=None):
    """The parameter tree, one block at a time: each float32 block is cast to
    ``cfg.dtype`` inside its init program and freed before the next."""
    n = len(cfg.pattern)
    programs = {}
    place = None if device is None else jax.sharding.SingleDeviceSharding(device)

    def born(spec_key, spec, index):
        if spec_key not in programs:
            tree = _Tree(spec)
            programs[spec_key] = jax.jit(
                lambda s, i: _cast(
                    tree.init(jax.random.fold_in(jax.random.PRNGKey(s), i))["params"],
                    cfg.dtype),
                out_shardings=place)
        return programs[spec_key](np.int32(seed), np.int32(index))

    blocks = [born(kind, block_spec(cfg, kind), i) for i, kind in enumerate(cfg.pattern)]
    embed = (("embedding", ((cfg.vocab, cfg.d_model), nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0))),)
    return {
        "embed": born("embed", embed, n),
        "blocks": blocks,
        "norm_f": born("norm_f", _norm(cfg.d_model), n),
        "lm_head": born("lm_head", _dense(cfg.d_model, cfg.vocab), n + 1),
    }


# ---------------------------------------------------------------------------
# the mixers (x is (B, T, D) in cfg.dtype; state rows belong to the B rows)
# ---------------------------------------------------------------------------
def _mm(x, w, dtype):
    return jnp.matmul(x, w, preferred_element_type=_F32).astype(dtype)


def _rms(x, scale, eps, groups=1):
    x = x.astype(_F32)
    g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def ssm_step(h, u, dt, a, bm, cm):
    """One step of the recurrence.  ``h`` (B, G, Hg, P, N) float32, ``u`` (B,
    G, Hg, P), ``dt`` (B, G, Hg), ``a`` (G, Hg), ``bm``/``cm`` (B, G, N)."""
    decay = jnp.exp(dt * a)[..., None, None]
    h = h * decay + (dt[..., None] * u)[..., None] * bm[:, :, None, None, :]
    return h, jnp.sum(h * cm[:, :, None, None, :], axis=-1)


def ssm_chunked(h, u, dt, a, bm, cm, chunk):
    """The same recurrence over T steps as the chunked scan of the Mamba-2
    paper: inside a chunk a masked (Q, Q) product, between chunks the state.
    ``u`` (B, T, G, Hg, P), ``dt`` (B, T, G, Hg), ``bm``/``cm`` (B, T, G, N);
    returns ``(h, y (B, T, G, Hg, P))``.  T is padded to whole chunks with
    ``dt = 0``: such a step decays nothing and adds nothing."""
    B, T = u.shape[:2]
    q = min(chunk, T)
    pad = (-T) % q
    if pad:
        u, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (u, dt, bm, cm))
    nc = (T + pad) // q
    # (nc, B, q, ...)
    u, dt, bm, cm = (jnp.moveaxis(t.reshape((B, nc, q) + t.shape[2:]), 1, 0)
                     for t in (u, dt, bm, cm))
    ein = functools.partial(jnp.einsum, precision=_HI)
    tri = jnp.tri(q, dtype=bool)

    def one(h, xs):
        u, dt, bm, cm = xs
        cs = jnp.cumsum(dt * a, axis=1)  # (B, q, G, Hg), <= 0 and falling
        # decay from step s to step t >= s; masked BEFORE the exponential
        seg = cs[:, :, None] - cs[:, None]  # (B, t, s, G, Hg)
        seg = jnp.exp(jnp.where(tri[None, :, :, None, None], seg, -jnp.inf))
        cb = ein("btgn,bsgn->btsg", cm, bm)
        w = seg * cb[..., None] * dt[:, None]  # (B, t, s, G, Hg)
        y = ein("btsgh,bsghp->btghp", w, u)
        y = y + jnp.exp(cs)[..., None] * ein("btgn,bghpn->btghp", cm, h)
        last = cs[:, -1]  # (B, G, Hg)
        carry = jnp.exp(last[:, None] - cs) * dt  # (B, s, G, Hg)
        h = (jnp.exp(last)[..., None, None] * h
             + ein("bsgh,bsghp,bsgn->bghpn", carry, u, bm))
        return h, y

    h, y = jax.lax.scan(one, h, (u, dt, bm, cm))
    y = jnp.moveaxis(y, 0, 1).reshape((B, nc * q) + y.shape[3:])
    return h, y[:, :T]


def mamba_mix(p, x, conv, ssm, cfg: HybridConfig, keep=None):
    """Returns ``(out, conv, ssm)``.  ``conv`` (B, K-1, C) is the window of
    the last ``K-1`` inputs of the convolution, ``ssm`` (B, H, P, N) float32.
    ``keep`` (B,) bool: rows whose state must come out bit-equal."""
    B, T, _ = x.shape
    G, H, P, N = cfg.ssm_groups, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    Hg, di = H // G, cfg.d_inner
    with jax.named_scope("nns.ssm"):
        zxd = _mm(x, p["in_proj"]["kernel"], cfg.dtype)
        z, xbc, dt = jnp.split(zxd, [di, di + cfg.d_conv], axis=-1)
        seq = jnp.concatenate([conv, xbc], axis=1)  # (B, K-1+T, C)
        taps = p["conv"]["kernel"][:, 0].astype(_F32)  # (K, C)
        out = sum(seq[:, k:k + T].astype(_F32) * taps[k]
                  for k in range(cfg.conv_kernel))
        xbc = jax.nn.silu(out + p["conv"]["bias"].astype(_F32)).astype(cfg.dtype)
        new_conv = seq[:, T:]
        u, bm, cm = jnp.split(xbc.astype(_F32), [di, di + G * N], axis=-1)
        u = u.reshape(B, T, G, Hg, P)
        bm, cm = bm.reshape(B, T, G, N), cm.reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(_F32) + p["dt_bias"]).reshape(B, T, G, Hg)
        a = -jnp.exp(p["A_log"]).reshape(G, Hg)
        h = ssm.reshape(B, G, Hg, P, N)
        if T == 1:
            h, y = ssm_step(h, u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
            y = y[:, None]
        else:
            h, y = ssm_chunked(h, u, dt, a, bm, cm, cfg.scan_chunk)
        y = y + p["D"].reshape(G, Hg)[..., None] * u
        y = y.reshape(B, T, di) * jax.nn.silu(z.astype(_F32))
        y = _rms(y, p["norm"]["scale"], cfg.norm_eps, groups=G).astype(cfg.dtype)
        new_ssm = h.reshape(ssm.shape)
        if keep is not None:
            new_conv = jnp.where(keep[:, None, None], conv, new_conv)
            new_ssm = jnp.where(keep[:, None, None, None], ssm, new_ssm)
        return _mm(y, p["out_proj"]["kernel"], cfg.dtype), new_conv, new_ssm


def attn_mix(p, x, ck, cv, pos, cfg: HybridConfig, active=None):
    """Returns ``(out, ck, cv)``: grouped-query attention over the slot's
    K/V rows plus the new rows, no positional encoding.  ``active`` (B,):
    a row with 0 reads none of its K/V rows in the per-token step."""
    with jax.named_scope("nns.attn"):
        q, k, v = (_mm(x, p[n]["kernel"], ck.dtype)
                   for n in ("q_proj", "k_proj", "v_proj"))
        ck, cv, attn = kv_attend_write(
            ck, cv, q, k, v, pos, cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            active=active)
        return _mm(attn.astype(cfg.dtype), p["o_proj"]["kernel"], cfg.dtype), ck, cv


def route(p, xt, cfg: HybridConfig):
    """Router over ALL experts, float32: ``(ids (M, k), weights (M, k))``."""
    s = jax.nn.sigmoid(jnp.matmul(
        xt.astype(_F32), p["router"]["kernel"], precision=_HI))
    _, ids = jax.lax.top_k(s + p["router"]["bias"], cfg.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale


def moe_mix(p, x, cfg: HybridConfig, live=None):
    """Returns ``(out, counts (4,) int32)``: the held experts' part for the
    tokens routed to them plus the shared expert.  ``live`` (B,) bool: rows
    that carry a token (an idle slot's row routes nowhere and counts
    nothing).  ``counts``: the first four of :data:`COUNTER_NAMES`."""
    B, T, D = x.shape
    M, k, held = B * T, cfg.top_k, cfg.experts_held
    with jax.named_scope("nns.moe"):
        xt = x.reshape(M, D)
        ids, w = route(p, xt, cfg)
        local = (ids >= cfg.expert_offset) & (ids < cfg.expert_offset + held)
        if live is not None:
            local = local & jnp.repeat(live, T)[:, None]
        lid = jnp.where(local, ids - cfg.expert_offset, held)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[lid.reshape(-1)].add(1)[:held]
        gate = jnp.where(local, w, 0.0)
        up, down = p["experts"]["up"], p["experts"]["down"]

        def grouped(xt, lid, gate):
            """Choices sorted by held expert (those of absent experts go
            last), one grouped product over the held experts."""
            order = jnp.argsort(lid.reshape(-1), stable=True)
            tok = order // k
            hid = jax.lax.ragged_dot(jnp.take(xt, tok, axis=0), up, sizes,
                                     preferred_element_type=_F32)
            hid = jnp.square(jax.nn.relu(hid)).astype(cfg.dtype)
            part = jax.lax.ragged_dot(hid, down, sizes, preferred_element_type=_F32)
            g = gate.reshape(-1)[order]
            # rows past the held experts' groups belong to no group
            part = jnp.where((g > 0)[:, None], part, 0.0) * g[:, None]
            return jnp.zeros((M, D), _F32).at[tok].add(part)

        def touched(xt, lid, gate):
            """On a TPU: the touched experts' weights streamed once, every
            token through each (ops/expert_ffn.py)."""
            one_hot = lid[:, :, None] == jnp.arange(held)[None, None, :]
            gates = jnp.sum(jnp.where(one_hot, gate[:, :, None], 0.0), axis=1)
            return touched_experts_ffn(xt, gates, up, down)

        routed = jax.lax.platform_dependent(
            xt, lid, gate, tpu=touched, default=grouped)
        sh = _mm(xt, p["shared_up"]["kernel"], _F32)
        sh = jnp.square(jax.nn.relu(sh)).astype(cfg.dtype)
        sh = jnp.matmul(sh, p["shared_down"]["kernel"], preferred_element_type=_F32)
        counts = jnp.stack([jnp.sum(local), jnp.sum(sizes > 0), jnp.max(sizes),
                            jnp.int32(1)]).astype(jnp.int32)
        return (routed + sh).astype(cfg.dtype).reshape(B, T, D), counts


def forward_rows(params, rows, tokens, cfg: HybridConfig, active=None):
    """Run ``tokens`` (B, T) through every block against the state ``rows``
    (the cache's leaves for these B rows).  ``active`` (B,) int: rows with 0
    keep their recurrent state and their position.  Returns ``(hidden (B, T,
    D), rows, counts)``."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    keep = None if active is None else active == 0
    live = None if active is None else active > 0
    counts = jnp.zeros((4,), jnp.int32)
    layers = {}
    for i, kind in enumerate(cfg.pattern):
        blk = params["blocks"][i]
        h = _rms(x, blk["norm"]["scale"], cfg.norm_eps).astype(cfg.dtype)
        st = rows["layers"].get(str(i))
        if kind == "M":
            y, conv, ssm = mamba_mix(blk["mixer"], h, st["conv"], st["ssm"], cfg, keep)
            layers[str(i)] = {"conv": conv, "ssm": ssm}
        elif kind == "*":
            y, ck, cv = attn_mix(
                blk["mixer"], h, st["k"], st["v"], rows["pos"], cfg, active)
            layers[str(i)] = {"k": ck, "v": cv}
        else:
            y, c = moe_mix(blk["mixer"], h, cfg, live)
            counts = counts + c
        x = x + y
    T = tokens.shape[1]
    adv = T if active is None else T * active.astype(jnp.int32)
    return x, {"pos": rows["pos"] + adv, "layers": layers}, counts


def head(params, x, cfg: HybridConfig):
    """float32 logits of hidden rows ``x`` (..., D)."""
    h = _rms(x, params["norm_f"]["scale"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.matmul(h, params["lm_head"]["kernel"], preferred_element_type=_F32)


class HybridSlotModel:
    """The jittable halves of the slotted path for this family: the same
    contract as :class:`~nnstreamer_tpu.models.transformer.SlotModel`
    (``core.slots.SlotModelProtocol``), the same pick and seed semantics.

    The cache: ``pos`` (slots,), per attention layer ``k``/``v`` (slots,
    max_seq, n_kv_heads x head_dim) in the model dtype (lane-dense), per
    Mamba-2 layer ``conv`` (slots, K-1, C) and ``ssm`` (slots, H, P, N)
    float32, and ``counts``: the expert counters the prefill chunks have
    summed since the last decode dispatch took them."""

    counter_names = COUNTER_NAMES
    #: a recurrent state cannot be cut by position
    supports_prefix = False

    def __init__(self, cfg: HybridConfig, slots: int, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, donate: Optional[bool] = None,
                 device=None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        from ..core.hw import default_device

        self.cfg = cfg
        self.slots = int(slots)
        self.device = device if device is not None else default_device()
        self._pick = _make_pick(temperature, top_k)
        self._temperature = temperature
        self._key0 = jax.random.PRNGKey(seed)
        if donate is None:
            donate = self.device.platform != "cpu"
        self._donate = (1,) if donate else ()
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self.reset_slot = jax.jit(
            self._reset_slot, donate_argnums=(0,) if donate else ())
        self.pick_first = jax.jit(lambda lg: self._pick(lg, self._key0))

    def place_params(self, params):
        params = jax.device_put(params, self.device)
        jax.block_until_ready(params)
        return params

    # -- cache lifecycle ----------------------------------------------------
    def _layer_shapes(self):
        c, s = self.cfg, self.slots
        kv = (s, c.max_seq, c.n_kv_heads * c.head_dim)
        out = {}
        for i, kind in enumerate(c.pattern):
            if kind == "M":
                out[str(i)] = {
                    "conv": ((s, c.conv_kernel - 1, c.d_conv), c.dtype),
                    "ssm": ((s, c.ssm_heads, c.ssm_head_dim, c.ssm_state), _F32)}
            elif kind == "*":
                out[str(i)] = {"k": (kv, c.dtype), "v": (kv, c.dtype)}
        return out

    def init_cache(self):
        def zeros(shape, dtype):
            return jnp.zeros(shape, dtype, device=self.device)

        return {
            "pos": zeros((self.slots,), jnp.int32),
            "counts": zeros((len(COUNTER_NAMES),), jnp.int32),
            "layers": {i: {n: zeros(*sd) for n, sd in leaves.items()}
                       for i, leaves in self._layer_shapes().items()},
        }

    @staticmethod
    def _row_start(c, slot):
        return (slot,) + (0,) * (c.ndim - 1)

    @staticmethod
    def _slotted(cache):
        """The leaves with a slot axis (``counts`` is the cache's own)."""
        return {"pos": cache["pos"], "layers": cache["layers"]}

    @classmethod
    def _rows(cls, cache, slot):
        """One slot's state as B = 1 rows."""
        return jax.tree.map(
            lambda c: jax.lax.dynamic_slice(
                c, cls._row_start(c, slot), (1,) + c.shape[1:]),
            cls._slotted(cache))

    @classmethod
    def _put_rows(cls, cache, rows, slot, counts):
        out = jax.tree.map(
            lambda c, r: jax.lax.dynamic_update_slice(c, r, cls._row_start(c, slot)),
            cls._slotted(cache), rows)
        out["counts"] = counts
        return out

    def _reset_slot(self, cache, slot):
        zero = jax.tree.map(jnp.zeros_like, self._rows(cache, slot))
        return self._put_rows(cache, zero, slot, cache["counts"])

    def export_prefix(self, cache, slot, start, stop):
        raise NotImplementedError(
            f"{FAMILY}: a recurrent state cannot be cut by position")

    attach_prefix = export_prefix

    # -- prefill (chunked, one slot at a time) ------------------------------
    def _prefill_chunk(self, params, cache, toks, slot):
        x, rows, counts = forward_rows(
            params, self._rows(cache, slot), toks, self.cfg)
        cache = self._put_rows(
            cache, rows, slot,
            cache["counts"] + jnp.concatenate([counts, counts[:2]]))
        return cache, head(params, x[:, -1], self.cfg)

    def prefill_fn(self, n: int):
        def nns_hybrid_prefill(params, cache, toks, slot):
            self.prefill_compiles += 1  # trace-time only
            return self._prefill_chunk(params, cache, toks, slot)

        del n  # bucketing key only; the shape specializes the jit
        return jax.jit(nns_hybrid_prefill, donate_argnums=self._donate)

    # -- decode (whole slot batch, k tokens per dispatch) -------------------
    def step_logits(self, params, cache, tok, active):
        """One token step of every slot: ``(cache, logits (S, V))``; the
        step's expert counters are added to the cache's."""
        x, rows, counts = forward_rows(
            params, self._slotted(cache), tok[:, None], self.cfg, active)
        rows["counts"] = cache["counts"] + jnp.pad(counts, (0, 2))
        return rows, head(params, x[:, 0], self.cfg)

    def _decode_scan(self, k, params, cache, tok, gen, active):
        def step(carry, _i):
            cache, tok, gen = carry
            cache, logits = self.step_logits(params, cache, tok, active)
            nxt = pick_slots(self._pick, self._key0, self._temperature, logits, gen)
            tok = jnp.where(active > 0, nxt, tok)
            return (cache, tok, gen + active), nxt

        (cache, tok, gen), toks = jax.lax.scan(
            step, (cache, tok, gen), jnp.arange(k))
        # hand the counters over and start them again at zero
        counts = cache["counts"]
        cache = {**cache, "counts": jnp.zeros_like(counts)}
        return cache, tok, gen, jnp.moveaxis(toks, 0, 1), counts

    def decode_fn(self, k: int):
        """``(params, cache, tok, gen, active) -> (cache, tok, gen, toks (S,
        k), counts)``; ``counts`` follows :attr:`counter_names`."""

        def nns_hybrid_decode(params, cache, tok, gen, active):
            self.decode_compiles += 1  # trace-time only
            return self._decode_scan(k, params, cache, tok, gen, active)

        return jax.jit(nns_hybrid_decode, donate_argnums=self._donate)


def build_slot_stream(props: Dict[str, str], slots: int,
                      donate: Optional[bool] = None, mesh=None, device=None):
    """Factory of the continuous-batching path for this family: the twin of
    ``models.transformer.build_slot_stream`` (``seed`` = parameters,
    ``gen_seed`` = sampling).  Returns ``(model, params, max_seq)``."""
    if mesh is not None:
        raise ValueError(
            f"arch:{FAMILY} does not shard over mesh=: the experts' ep axis "
            "and its exchange do not exist yet (one chip holds one share)")
    cfg = cfg_from_props(props)
    model = HybridSlotModel(
        cfg, slots,
        temperature=float(props.get("temperature", "0")),
        top_k=int(props.get("top_k", "0")),
        seed=int(props.get("gen_seed", "0")),
        donate=donate, device=device)
    params = init_params(cfg, int(props.get("seed", "0")), device=model.device)
    return model, model.place_params(params), cfg.max_seq
