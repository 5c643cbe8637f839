"""Hybrid decoder LM for the slotted generation path: the mixers of every
block chosen by a pattern string (the ``nemotron_h``, ``cohere2_moe`` and
``lfm2_moe`` families; :data:`FAMILIES` holds what differs between them as
config fields).

A block is ``x <- x + Mixer(Norm(x))``, or, for letters in parentheses, a
PARALLEL block ``h = Norm(x); x <- x + sum of Mixer_j(h)``: one norm, every
mixer of the group reads the same ``h``, one residual add (``(WE)(WE)(WE)(*E)``
is one period of ``cohere2_moe``).  ``Norm`` is RMSNorm or the mean-centred,
weight-only LayerNorm (``norm``).  The pattern names each mixer by one letter:

* ``M`` — Mamba-2: ``[z | xBC | dt] = x W_in``; a causal depthwise
  convolution and ``silu`` over ``xBC``; ``h_t = exp(dt_t A) h_{t-1} + dt_t
  u_t B_t^T`` per head in float32, ``y_t = h_t C_t + D u_t``; a grouped
  RMSNorm gated by ``silu(z)``; ``W_out``.  Two forms that agree: the
  chunked scan of the Mamba-2 paper for a prefill chunk (from the state the
  slot's previous chunk left) and the one-step recurrence in the decode
  scan.
* ``*`` — grouped-query attention through
  :func:`~nnstreamer_tpu.models.transformer.kv_attend_write` (the one cache
  step every generation path shares): the GLOBAL layer, its K/V leaves hold
  ``max_seq`` rows by position.  Without positional encoding, or, where the
  family says so (``rope_global``), ``q`` and ``k`` turned by rotary
  positions before the cache write; ``qk_norm``: an RMSNorm over
  ``head_dim`` with a learned weight on every query and key head first.
* ``W`` — the same attention as a WINDOW layer: rotary positions on ``q`` and
  ``k`` before the cache write (angles in float32 from the absolute
  position, computed in the program; the pairs are the family's
  ``rope_pairs``: ``interleaved`` ``(x[2i], x[2i+1])`` or ``half`` ``(x[i],
  x[i + head_dim/2])``), a query at position ``p`` sees ``p - window + 1 ..
  p``, and its leaves hold ``window`` rows written round (position ``p`` at
  row ``p mod window``).
* ``C`` — the gated short convolution: ``[B | C | u] = x W_in``; ``g = B * u``;
  a causal depthwise convolution of ``conv`` taps over ``g`` (no bias, no
  activation); ``(C * conv) W_out``.  Its slot state is the window of the
  last ``conv - 1`` rows of ``g`` and nothing else: a prefill chunk starts
  from the window the slot's previous chunk left, and the decode scan's one
  step is the same sum over ``conv`` rows.
* ``D`` — a dense gated MLP as a block of its own: ``(silu(x W_gate) * (x
  W_up)) W_down`` of width ``d_ff``; no state.
* ``E`` — routed experts: sigmoid router in float32 over ALL ``experts``,
  top-``top_k`` of score (+ bias where the family has one), weights
  normalised over the chosen and scaled; an expert is ``relu(x W_up)^2
  W_down`` or, ``expert_act = silu_gated``, ``(silu(x W_gate) * (x W_up))
  W_down``; ``shared_experts`` shared experts of width ``d_shared`` run for
  every token, summed or averaged (kept side by side as ONE FFN; with
  ``shared_experts: 0`` the layer has no shared leaves and computes none).
  The layer is TOLD which experts it holds
  (``[expert_offset, expert_offset + experts_held)``): it routes over all of
  them and computes its own experts' part — tokens sorted by expert, one
  grouped product over the held experts, no capacity limit, no token a held
  expert was chosen for ever dropped.  What absent experts would add is left
  out (on one chip the layer runs without its exchange).

A slot owns state of four kinds: K/V rows by position per global layer, a
window of K/V rows written round per window layer, per Mamba-2 layer a conv
window and a scan state with NO position axis, and per short-convolution
layer a conv window alone.
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
import types
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import decode_attention
from ..ops import expert_ffn
from .transformer import (
    _make_pick, config_resume_fields, kv_attend_write, pick_slots,
)

FAMILY = "nemotron_h"
#: the ``arch:`` names this module serves: the stem of each one's program
#: names (``jit_nns_<stem>_decode``) and the config fields in which it
#: differs from :class:`HybridConfig`'s defaults.  ``norm``, ``tied_head``,
#: ``expert_act``, ``router_bias``, ``shared_combine``, ``rope_global``,
#: ``rope_pairs``, ``qk_norm``, ``route_eps`` and ``kv_counters`` are the
#: family's alone; a ``custom=`` key may set the others.
FAMILIES = {
    FAMILY: dict(stem="hybrid", fields={}),
    "cohere2_moe": dict(stem="cohere2_moe", fields=dict(
        pattern="(WE)(WE)(WE)(*E)", norm="layer", expert_act="silu_gated",
        shared_combine="average", router_bias=False, routed_scale=1.0,
        tied_head=True)),
    "lfm2_moe": dict(stem="lfm2_moe", fields=dict(
        pattern="CDCD*ECECECE*ECECECE*ECE", conv_kernel=3, expert_act="silu_gated",
        routed_scale=1.0, route_eps=1e-6, shared_experts=0, tied_head=True,
        rope_theta=1e6, rope_global=True, rope_pairs="half", qk_norm=True,
        kv_counters=True)),
}
#: always-on counters the decode scan and the prefill chunks sum over their
#: steps and ``E`` layers (the engine adds them to ``snapshot()``): choices
#: that fell on a held expert, distinct held experts with a token, tokens on
#: the busiest held expert, (layer, step) pairs counted; the prefill
#: chunks' part of the first two; and, of the chunks long enough to go through
#: the grouped expert kernel alone, the rows that carry a pick and the rows
#: its tiles ran, padding in
COUNTER_NAMES = ("gen_moe_local", "gen_moe_expert_reads", "gen_moe_max_load",
                 "gen_moe_layer_steps", "gen_moe_prefill_local",
                 "gen_moe_prefill_reads", "gen_moe_grouped_rows",
                 "gen_moe_grouped_rows_run")
#: and, for a pattern with a window layer or a family with ``kv_counters``,
#: summed the same way over attention
#: layers and live slots: cache rows a decode step NEEDS by position and
#: window, rows its reads covered, rows the leaves hold; and the keys a
#: prefill chunk's queries see (their own row counted), which follow from the
#: chunk's position alone and grow with its square: the engine is told them
#: in Python integers as each chunk is dispatched
#: (:meth:`HybridSlotModel.prefill_counts`), no program sums them
KV_COUNTER_NAMES = ("gen_kv_rows_need", "gen_kv_rows_read", "gen_kv_rows_held",
                    "gen_kv_prefill_rows_need")
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    pattern: str = "MEM*EME"
    vocab: int = 256
    d_model: int = 64
    norm: str = "rms"            # rms | layer (mean-centred, weight only)
    tied_head: bool = False      # the embedding read as the head
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
    # window layers: rows a query sees (its own counted), rotary base
    window: int = 0
    rope_theta: float = 10000.0
    # rotary on the global layers too; the pairs turned: interleaved | half
    rope_global: bool = False
    rope_pairs: str = "interleaved"
    qk_norm: bool = False        # RMSNorm over head_dim on q and k heads
    kv_counters: bool = False    # KV_COUNTER_NAMES without a window layer
    # the dense gated MLP (D): its width
    d_ff: int = 0
    # routed experts: the router's width, the share held here, experts per token
    experts: int = 8
    experts_held: int = 8
    expert_offset: int = 0
    top_k: int = 2
    d_expert: int = 32
    expert_act: str = "relu2"    # relu2 | silu_gated
    router_bias: bool = True
    routed_scale: float = 2.5
    route_eps: float = 0.0       # added to the chosen scores' normalising sum
    # shared experts: how many, the width of one, sum | average
    shared_experts: int = 1
    d_shared: int = 64
    shared_combine: str = "sum"
    norm_eps: float = 1e-5
    max_seq: int = 256
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        parse_pattern(self.pattern)  # raises by name
        for field, known in (("norm", ("rms", "layer")),
                             ("expert_act", ("relu2", "silu_gated")),
                             ("shared_combine", ("sum", "average")),
                             ("rope_pairs", ("interleaved", "half"))):
            if getattr(self, field) not in known:
                raise ValueError(f"{field}:{getattr(self, field)}: one of {known}")
        if "W" in self.pattern and (self.window < 1 or self.head_dim % 2):
            raise ValueError(
                f"a window layer needs window >= 1 (got {self.window}) and an "
                "even head_dim (rotary pairs)")
        if self.rope_global and self.head_dim % 2:
            raise ValueError("rotary positions need an even head_dim")
        if "D" in self.pattern and self.d_ff < 1:
            raise ValueError(f"a dense MLP block (D) needs d_ff >= 1, got {self.d_ff}")
        if "C" in self.pattern and self.conv_kernel < 2:
            raise ValueError(
                f"a short convolution (C) needs conv >= 2 taps, got {self.conv_kernel}")
        if self.shared_experts < 0:
            raise ValueError(f"shared_experts:{self.shared_experts}: not a count")
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
    def groups(self):
        """The pattern by block: ``("WE", "WE", "WE", "*E")``."""
        return parse_pattern(self.pattern)

    @property
    def blocks(self):
        """Per block its mixers as ``(name in the block, kind, state key)``;
        the state key numbers the mixers through the whole pattern."""
        keys = iter(range(len(self.pattern)))
        return tuple(tuple((name, kind, str(next(keys)))
                           for name, kind in zip(mixer_names(group), group))
                     for group in self.groups)

    @property
    def kv_counted(self) -> bool:
        """The programs sum :data:`KV_COUNTER_NAMES`."""
        return self.kv_counters or "W" in self.pattern

    def kv_rows(self, kind: str) -> int:
        """Rows of one slot's K and V leaves in a layer of ``kind``."""
        return min(self.window, self.max_seq) if kind == "W" else self.max_seq

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_conv(self) -> int:  # the xBC channels the convolution runs over
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


_LETTERS = "ME*WCD"


@functools.lru_cache(maxsize=None)
def parse_pattern(pattern: str):
    """``"(WE)M*"`` -> ``("WE", "M", "*")``: a letter is a block of one
    mixer, letters in parentheses one parallel block."""
    out, inside = [], None
    for ch in pattern:
        if ch == "(" and inside is None:
            inside = ""
        elif ch == ")" and inside:
            out.append(inside)
            inside = None
        elif ch in _LETTERS and inside is None:
            out.append(ch)
        elif ch in _LETTERS:
            inside += ch
        else:
            out = None
            break
    if not out or inside is not None:
        raise ValueError(
            f"layers pattern {pattern!r}: one of M, E, *, W, C, D per mixer, the "
            "mixers of a parallel block in one pair of parentheses")
    return tuple(out)


def mixer_names(group: str):
    """The parameter names of a block's mixers: ``mixer``, or ``mixer0``,
    ``mixer1``, ... in a parallel block."""
    return ("mixer",) if len(group) == 1 else tuple(
        f"mixer{j}" for j in range(len(group)))


def cfg_from_props(props: Dict[str, str]) -> HybridConfig:
    """The ``custom=`` dialect of these families (Documentation/examples.md):
    ``arch`` gives the family's defaults and what only the family decides,
    ``layers`` is the pattern string, every other key is a number."""
    d = types.SimpleNamespace(**{
        **{f.name: f.default for f in dataclasses.fields(HybridConfig)},
        **FAMILIES[props.get("arch", FAMILY)]["fields"]})

    def num(key, default, cast=int):
        return cast(props.get(key, default))

    return HybridConfig(
        pattern=props.get("layers", d.pattern),
        vocab=num("vocab", d.vocab),
        d_model=num("d_model", d.d_model),
        norm=d.norm,
        tied_head=d.tied_head,
        ssm_heads=num("ssm_heads", d.ssm_heads),
        ssm_head_dim=num("ssm_head_dim", d.ssm_head_dim),
        ssm_groups=num("ssm_groups", d.ssm_groups),
        ssm_state=num("ssm_state", d.ssm_state),
        conv_kernel=num("conv", d.conv_kernel),
        scan_chunk=num("scan_chunk", d.scan_chunk),
        n_heads=num("heads", d.n_heads),
        n_kv_heads=num("kv_heads", d.n_kv_heads),
        head_dim=num("head_dim", d.head_dim),
        window=num("window", d.window),
        rope_theta=num("rope_theta", d.rope_theta, float),
        rope_global=d.rope_global,
        rope_pairs=d.rope_pairs,
        qk_norm=d.qk_norm,
        kv_counters=d.kv_counters,
        d_ff=num("d_ff", d.d_ff),
        experts=num("experts", d.experts),
        experts_held=num("experts_held", props.get("experts", d.experts_held)),
        expert_offset=num("expert_offset", d.expert_offset),
        top_k=num("experts_per_tok", d.top_k),
        d_expert=num("d_expert", d.d_expert),
        expert_act=d.expert_act,
        router_bias=d.router_bias,
        routed_scale=num("routed_scale", d.routed_scale, float),
        route_eps=d.route_eps,
        shared_experts=num("shared_experts", d.shared_experts),
        d_shared=num("d_shared", d.d_shared),
        shared_combine=d.shared_combine,
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


def _side_by_side(n, true_shape, axis):
    """``n`` matrices of ``true_shape`` laid side by side along ``axis``:
    matrix ``j`` is ``lecun_normal`` from ``fold_in(key, j)`` (the shared
    experts kept as one FFN)."""

    def init(key, shape):
        del shape
        each = [_lecun(jax.random.fold_in(key, j), true_shape) for j in range(n)]
        return jnp.concatenate(each, axis=axis)

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


def mixer_spec(cfg: HybridConfig, kind: str):
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
    elif kind in "*W":
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        mixer = (("q_proj", _dense(d, q)), ("k_proj", _dense(d, kv)),
                 ("v_proj", _dense(d, kv)), ("o_proj", _dense(q, d)))
        if cfg.qk_norm:
            mixer += (("q_norm", _norm(cfg.head_dim)), ("k_norm", _norm(cfg.head_dim)))
    elif kind == "C":
        mixer = (("in_proj", _dense(d, 3 * d)),
                 ("conv", (("kernel", ((cfg.conv_kernel, 1, d), _lecun)),)),
                 ("out_proj", _dense(d, d)))
    elif kind == "D":
        mixer = (("gate_proj", _dense(d, cfg.d_ff)), ("up_proj", _dense(d, cfg.d_ff)),
                 ("down_proj", _dense(cfg.d_ff, d)))
    else:
        held, f = cfg.experts_held, cfg.d_expert
        # an expert's width is stored padded with zeros to whole 128-lane
        # tiles (1856 -> 1920): a stack whose minor dim is not whole tiles
        # is kept transposed on a TPU, and the grouped product then copies
        # all of it at every dispatch
        fp = -(-f // 128) * 128
        up = _expert_stack(cfg.expert_offset, (d, f))
        down = _expert_stack(cfg.expert_offset, (f, d))
        gated = cfg.expert_act == "silu_gated"
        n, fs = cfg.shared_experts, cfg.d_shared

        def shared(d_in, d_out, axis):
            if n == 1:
                return _dense(d_in, d_out)
            wide = (d_in, n * d_out) if axis == 1 else (n * d_in, d_out)
            return (("kernel", (wide, _side_by_side(n, (d_in, d_out), axis))),)

        bias = (("bias", ((cfg.experts,), nn.initializers.normal(0.02))),)
        mixer = (
            ("router", (("kernel", ((d, cfg.experts), _lecun)),)
             + (bias if cfg.router_bias else ())),
            ("experts", ((("gate", ((held, d, fp), up)),) if gated else ())
             + (("up", ((held, d, fp), up)), ("down", ((held, fp, d), down)))),
        )
        if n:  # no shared expert: no shared leaves
            mixer += ((("shared_gate", shared(d, fs, 1)),) if gated else ()) + (
                ("shared_up", shared(d, fs, 1)),
                ("shared_down", shared(fs, d, 0)),
            )
    return mixer


def block_spec(cfg: HybridConfig, group: str):
    """One block's parameters: its norm and its mixers."""
    return (("norm", _norm(cfg.d_model)),) + tuple(
        (name, mixer_spec(cfg, kind)) for name, kind in zip(mixer_names(group), group))


def _cast(tree, dtype):
    def one(path, leaf):
        names = {getattr(p, "key", None) for p in path}
        return leaf if names & set(_KEEP_F32) else leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def init_params(cfg: HybridConfig, seed: int, device=None):
    """The parameter tree, one block at a time: each float32 block is cast to
    ``cfg.dtype`` inside its init program and freed before the next."""
    n = len(cfg.groups)
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

    blocks = [born(group, block_spec(cfg, group), i) for i, group in enumerate(cfg.groups)]
    embed = (("embedding", ((cfg.vocab, cfg.d_model), nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", out_axis=0))),)
    params = {
        "embed": born("embed", embed, n),
        "blocks": blocks,
        "norm_f": born("norm_f", _norm(cfg.d_model), n),
    }
    if not cfg.tied_head:
        params["lm_head"] = born("lm_head", _dense(cfg.d_model, cfg.vocab), n + 1)
    return params


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


def _layer_norm(x, scale, eps):
    """Mean-centred, weight only, no bias, float32."""
    x = x.astype(_F32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _head_rms(x, scale, n_heads: int, eps):
    """RMSNorm over ``head_dim`` of every head of ``x`` (B, T, heads x
    head_dim) with ONE learned weight (head_dim,) for all heads (QK norm)."""
    return _rms(x, jnp.tile(scale, n_heads), eps, groups=n_heads).astype(x.dtype)


def _normed(x, scale, cfg):
    norm = _rms if cfg.norm == "rms" else _layer_norm
    return norm(x, scale, cfg.norm_eps).astype(cfg.dtype)


def rotary(x, pos, n_heads: int, theta: float, pairs: str = "interleaved"):
    """Rotary positions on ``x`` (B, T, heads x head_dim): pair ``i`` of every
    head turned by ``p x theta^(-2i / head_dim)``, ``p = pos[b] + t`` the
    row's absolute position.  ``pairs``: ``interleaved``, the pair ``(x[2i],
    x[2i+1])``, or ``half``, the pair ``(x[i], x[i + head_dim/2])``
    (transformers' ``rotate_half``).  Angles, sines and the rotation are
    float32, made here from ``pos`` (no table is baked into the program)."""
    B, T, D = x.shape
    dh = D // n_heads
    lane = jnp.arange(dh)
    half = pairs == "half"
    pair = lane % (dh // 2) if half else lane // 2
    inv = jnp.asarray(theta, _F32) ** (-(pair * 2).astype(_F32) / dh)
    p = (pos[:, None] + jnp.arange(T)[None, :]).astype(_F32)
    ang = p[:, :, None, None] * inv  # (B, T, 1, dh)
    xf = x.astype(_F32).reshape(B, T, n_heads, dh)
    if half:
        # out[i] = x[i] cos - x[i + dh/2] sin, out[i + dh/2] = x[i + dh/2] cos + x[i] sin
        turned = jnp.roll(xf, dh // 2, axis=-1)
        other = jnp.where(lane < dh // 2, -turned, turned)
    else:
        # the pair's other element, signed: out[2i] = x[2i] cos - x[2i+1] sin,
        # out[2i+1] = x[2i+1] cos + x[2i] sin
        other = jnp.where(lane % 2 == 0, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return (xf * jnp.cos(ang) + other * jnp.sin(ang)).reshape(B, T, D).astype(x.dtype)


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


def _window_conv(conv, new, kernel, taps: int):
    """The causal depthwise convolution of ``new`` (B, T, C) continuing the
    window ``conv`` (B, taps-1, C) of the rows before it: ``(out (B, T, C)
    float32, the window the rows leave)``.  ``T == 1`` is the one-step form."""
    T = new.shape[1]
    seq = jnp.concatenate([conv, new], axis=1)  # (B, taps-1+T, C)
    w = kernel[:, 0].astype(_F32)  # (taps, C)
    out = sum(seq[:, k:k + T].astype(_F32) * w[k] for k in range(taps))
    return out, seq[:, T:]


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
        out, new_conv = _window_conv(conv, xbc, p["conv"]["kernel"], cfg.conv_kernel)
        xbc = jax.nn.silu(out + p["conv"]["bias"].astype(_F32)).astype(cfg.dtype)
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


def conv_mix(p, x, conv, cfg: HybridConfig, keep=None):
    """The gated short convolution.  Returns ``(out, conv)``: ``conv`` (B,
    K-1, D) is the window of the last ``K-1`` rows of the gated input ``B *
    u``, the layer's whole slot state.  A chunk (``T > 1``) and the one-step
    form (``T == 1``) are the same sum over ``K`` shifted rows of ``[window |
    new rows]``, in float32 (:func:`_window_conv`, Mamba-2's too).  ``keep``
    (B,) bool: rows whose window must come out bit-equal."""
    with jax.named_scope("nns.conv"):
        b, c, u = jnp.split(_mm(x, p["in_proj"]["kernel"], cfg.dtype), 3, axis=-1)
        g = (b.astype(_F32) * u.astype(_F32)).astype(cfg.dtype)
        out, new_conv = _window_conv(conv, g, p["conv"]["kernel"], cfg.conv_kernel)
        if keep is not None:
            new_conv = jnp.where(keep[:, None, None], conv, new_conv)
        y = (c.astype(_F32) * out).astype(cfg.dtype)
        return _mm(y, p["out_proj"]["kernel"], cfg.dtype), new_conv


def mlp_mix(p, x, cfg: HybridConfig):
    """The dense gated MLP: ``(silu(x W_gate) * (x W_up)) W_down``."""
    with jax.named_scope("nns.mlp"):
        hid = _expert_act(cfg, _mm(x, p["up_proj"]["kernel"], _F32),
                          _mm(x, p["gate_proj"]["kernel"], _F32)).astype(cfg.dtype)
        return _mm(hid, p["down_proj"]["kernel"], cfg.dtype)


def attn_mix(p, x, ck, cv, pos, cfg: HybridConfig, active=None, window=False):
    """Returns ``(out, ck, cv)``: grouped-query attention over the slot's
    K/V rows plus the new rows.  A global layer applies no positional
    encoding unless the family gives it one (``rope_global``) and its leaves
    hold every position; a ``window`` layer turns ``q`` and ``k`` by their
    positions before the cache write and its leaves are written round.
    ``qk_norm``: every query and key head is RMS-normed over ``head_dim``
    with a learned weight before the rotation.  ``active`` (B,): a row with
    0 reads none of its K/V rows in the per-token step."""
    with jax.named_scope("nns.attn.window" if window else "nns.attn.global"):
        q, k, v = (_mm(x, p[n]["kernel"], ck.dtype)
                   for n in ("q_proj", "k_proj", "v_proj"))
        if cfg.qk_norm:
            q = _head_rms(q, p["q_norm"]["scale"], cfg.n_heads, cfg.norm_eps)
            k = _head_rms(k, p["k_norm"]["scale"], cfg.n_kv_heads, cfg.norm_eps)
        if window or cfg.rope_global:
            q = rotary(q, pos, cfg.n_heads, cfg.rope_theta, cfg.rope_pairs)
            k = rotary(k, pos, cfg.n_kv_heads, cfg.rope_theta, cfg.rope_pairs)
        ck, cv, attn = kv_attend_write(
            ck, cv, q, k, v, pos, cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            active=active, ring=window)
        return _mm(attn.astype(cfg.dtype), p["o_proj"]["kernel"], cfg.dtype), ck, cv


def kv_counts(leaf, pos, active, window: bool):
    """One attention layer's part of a decode step in the first three of
    :data:`KV_COUNTER_NAMES`, (3,) int32: the older rows each live slot needs
    by position and window (a window counts the query's own position, so a
    full one needs ``rows - 1``), the rows its read covers (whole blocks up
    to the fill where the kernel is taken, every row where not), the rows
    held."""
    B, S, _ = leaf.shape
    n = decode_attention.live_rows(pos, active, S)
    need = jnp.minimum(n, S - 1) if window else n
    return jnp.stack([jnp.sum(need), decode_attention.rows_read(n, leaf),
                      B * S]).astype(jnp.int32)


def keys_seen(pos: int, n: int, rows: int) -> int:
    """The keys the ``n`` queries of a chunk at position ``pos`` see, their
    own counted, where one query sees at most ``rows``: the sum of ``min(p +
    1, rows)`` over ``p = pos .. pos + n - 1``, in Python integers."""
    m = max(0, min(pos + n, rows) - pos)  # queries that see fewer than ``rows``
    return m * pos + m * (m + 1) // 2 + (n - m) * rows


def route(p, xt, cfg: HybridConfig):
    """Router over ALL experts, float32: ``(ids (M, k), weights (M, k))``."""
    s = jax.nn.sigmoid(jnp.matmul(
        xt.astype(_F32), p["router"]["kernel"], precision=_HI))
    _, ids = jax.lax.top_k(
        s + p["router"]["bias"] if cfg.router_bias else s, cfg.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    total = jnp.sum(w, axis=-1, keepdims=True)
    if cfg.route_eps:
        total = total + cfg.route_eps
    return ids, w / total * cfg.routed_scale


def _expert_act(cfg: HybridConfig, up, gate=None):
    """The experts' activation on float32 products: ``relu(up)^2``, or
    ``silu(gate) * up`` where the experts are gated."""
    if cfg.expert_act == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up


def moe_mix(p, x, cfg: HybridConfig, live=None):
    """Returns ``(out, counts (4,) int32)``: the held experts' part for the
    tokens routed to them plus the shared experts, where it has any.  ``live`` (B,) bool: rows
    that carry a token (an idle slot's row routes nowhere and counts
    nothing).  ``counts``: the first four of :data:`COUNTER_NAMES`, and for a
    batch that goes through the grouped kernel the last two after them (6,)."""
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
        wg = p["experts"].get("gate")  # None: the experts are not gated

        def grouped(xt, lid, gate):
            """Choices sorted by held expert (those of absent experts go
            last), one grouped product over the held experts."""
            order = jnp.argsort(lid.reshape(-1), stable=True)
            tok = order // k
            rows = jnp.take(xt, tok, axis=0)
            hid = jax.lax.ragged_dot(rows, up, sizes, preferred_element_type=_F32)
            gate_h = None if wg is None else jax.lax.ragged_dot(
                rows, wg, sizes, preferred_element_type=_F32)
            hid = _expert_act(cfg, hid, gate_h).astype(cfg.dtype)
            part = jax.lax.ragged_dot(hid, down, sizes, preferred_element_type=_F32)
            g = gate.reshape(-1)[order]
            # rows past the held experts' groups belong to no group
            part = jnp.where((g > 0)[:, None], part, 0.0) * g[:, None]
            return jnp.zeros((M, D), _F32).at[tok].add(part)

        def kernel(xt, lid, gate):
            """On a TPU (ops/expert_ffn.py): a small batch streams the
            touched experts' weights once, every token through each; a
            large one goes expert by expert through the expert's own rows."""
            return expert_ffn.held_experts_ffn(xt, lid, gate, up, down, wg)

        routed = jax.lax.platform_dependent(
            xt, lid, gate, tpu=kernel, default=grouped)
        out = routed
        if cfg.shared_experts:
            sh = _mm(xt, p["shared_up"]["kernel"], _F32)
            sh = _expert_act(cfg, sh, None if wg is None else _mm(
                xt, p["shared_gate"]["kernel"], _F32)).astype(cfg.dtype)
            sh = jnp.matmul(sh, p["shared_down"]["kernel"], preferred_element_type=_F32)
            if cfg.shared_combine == "average" and cfg.shared_experts > 1:
                sh = sh * (1.0 / cfg.shared_experts)
            out = routed + sh
        counts = [jnp.sum(local), jnp.sum(sizes > 0), jnp.max(sizes), jnp.int32(1)]
        run = expert_ffn.rows_run(lid, held, D)
        if run is not None:  # the batch goes by expert: how full its tiles are
            counts += [counts[0], run]
        return out.astype(cfg.dtype).reshape(B, T, D), jnp.stack(counts).astype(jnp.int32)


def forward_rows(params, rows, tokens, cfg: HybridConfig, active=None):
    """Run ``tokens`` (B, T) through every block against the state ``rows``
    (the cache's leaves for these B rows).  ``active`` (B,) int: rows with 0
    keep their recurrent state and their position.  Returns ``(hidden (B, T,
    D), rows, counts, kv)``: ``counts`` the expert layers' sums (:func:`moe_mix`), ``kv``
    (3,) the attention layers' in a decode step (:func:`kv_counts`; None for
    a prefill chunk, ``active`` None, and for a config that does not count
    them, ``kv_counted``)."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    T = tokens.shape[1]
    keep = None if active is None else active == 0
    live = None if active is None else active > 0
    counts = None
    counted = active is not None and cfg.kv_counted
    kv = jnp.zeros((3,), jnp.int32) if counted else None
    layers = {}
    for blk, mixers in zip(params["blocks"], cfg.blocks):
        # one norm, every mixer of the block reads the same h, one residual add
        h = _normed(x, blk["norm"]["scale"], cfg)
        outs = []
        for name, kind, key in mixers:
            st = rows["layers"].get(key)
            if kind == "M":
                out, conv, ssm = mamba_mix(blk[name], h, st["conv"], st["ssm"], cfg, keep)
                layers[key] = {"conv": conv, "ssm": ssm}
            elif kind == "E":
                out, c = moe_mix(blk[name], h, cfg, live)
                counts = c if counts is None else counts + c
            elif kind == "C":
                out, conv = conv_mix(blk[name], h, st["conv"], cfg, keep)
                layers[key] = {"conv": conv}
            elif kind == "D":
                out = mlp_mix(blk[name], h, cfg)
            else:
                if kv is not None:
                    kv = kv + kv_counts(st["k"], rows["pos"], active, kind == "W")
                out, ck, cv = attn_mix(
                    blk[name], h, st["k"], st["v"], rows["pos"], cfg, active, kind == "W")
                layers[key] = {"k": ck, "v": cv}
            outs.append(out)
        x = x + functools.reduce(jnp.add, outs)
    adv = T if active is None else T * active.astype(jnp.int32)
    if counts is None:  # a pattern without an expert layer
        counts = jnp.zeros((4,), jnp.int32)
    return x, {"pos": rows["pos"] + adv, "layers": layers}, counts, kv


def head(params, x, cfg: HybridConfig):
    """float32 logits of hidden rows ``x`` (..., D)."""
    h = _normed(x, params["norm_f"]["scale"], cfg)
    if not cfg.tied_head:
        return jnp.matmul(h, params["lm_head"]["kernel"], preferred_element_type=_F32)
    return jnp.einsum("...d,vd->...v", h, params["embed"]["embedding"],
                      preferred_element_type=_F32)


class HybridSlotModel:
    """The jittable halves of the slotted path for this family: the same
    contract as :class:`~nnstreamer_tpu.models.transformer.SlotModel`
    (``core.slots.SlotModelProtocol``), the same pick and seed semantics.

    The cache: ``pos`` (slots,), per attention layer ``k``/``v`` (slots,
    rows, n_kv_heads x head_dim) in the model dtype (lane-dense), ``rows``
    being ``max_seq`` for a global layer and ``window`` for a window layer
    (written round), per Mamba-2 layer ``conv`` (slots, K-1, C) and ``ssm``
    (slots, H, P, N) float32, per short-convolution layer ``conv`` (slots,
    K-1, d_model), and ``counts``: the counters the prefill
    chunks have summed since the last decode dispatch took them."""

    #: a config that counts them (``kv_counted``) adds :data:`KV_COUNTER_NAMES`
    counter_names = COUNTER_NAMES
    #: neither a recurrent state nor a leaf written round can be cut by position
    supports_prefix = False

    def __init__(self, cfg: HybridConfig, slots: int, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, donate: Optional[bool] = None,
                 device=None, family: str = FAMILY):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        from ..core.hw import default_device

        self.cfg = cfg
        self.family = family  # for program names and messages alone
        self.slots = int(slots)
        if cfg.kv_counted:
            self.counter_names = COUNTER_NAMES + KV_COUNTER_NAMES
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
        out = {}
        for _, kind, key in (m for mixers in c.blocks for m in mixers):
            if kind == "M":
                out[key] = {
                    "conv": ((s, c.conv_kernel - 1, c.d_conv), c.dtype),
                    "ssm": ((s, c.ssm_heads, c.ssm_head_dim, c.ssm_state), _F32)}
            elif kind == "C":
                out[key] = {"conv": ((s, c.conv_kernel - 1, c.d_model), c.dtype)}
            elif kind in "*W":
                kv = (s, c.kv_rows(kind), c.n_kv_heads * c.head_dim)
                out[key] = {"k": (kv, c.dtype), "v": (kv, c.dtype)}
        return out

    def init_cache(self):
        def zeros(shape, dtype):
            return jnp.zeros(shape, dtype, device=self.device)

        return {
            "pos": zeros((self.slots,), jnp.int32),
            "counts": zeros((len(self.counter_names),), jnp.int32),
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
            f"{self.family}: a recurrent state cannot be cut by position, "
            "nor a leaf written round")

    attach_prefix = export_prefix

    # -- prefill (chunked, one slot at a time) ------------------------------
    def _tally(self, moe, kv=None):
        """One program's sums in the order of :attr:`counter_names`; what
        the program does not count stays 0."""
        out = moe if kv is None else jnp.concatenate([moe, kv])
        rest = len(self.counter_names) - out.shape[0]
        return jnp.pad(out, (0, rest)) if rest else out

    def _prefill_chunk(self, params, cache, toks, slot):
        x, rows, counts, _ = forward_rows(
            params, self._rows(cache, slot), toks, self.cfg)
        cache = self._put_rows(
            cache, rows, slot,
            cache["counts"] + self._tally(
                jnp.concatenate([counts[:4], counts[:2], counts[4:]])))
        return cache, head(params, x[:, -1], self.cfg)

    def prefill_counts(self, pos: int, n: int) -> Dict[str, int]:
        """What a chunk of ``n`` tokens at position ``pos`` adds to the
        counters that follow from position alone."""
        if not self.cfg.kv_counted:
            return {}
        return {KV_COUNTER_NAMES[3]: sum(
            keys_seen(pos, n, self.cfg.kv_rows(kind))
            for mixers in self.cfg.blocks for _, kind, _ in mixers if kind in "*W")}

    def _named(self, fn, phase: str):
        """``fn`` under the program name of this family and ``phase``
        (``jit_nns_hybrid_decode``, ``jit_nns_cohere2_moe_prefill``, ...):
        what a trace's module line shows."""
        stem = FAMILIES[self.family]["stem"]
        fn.__name__ = fn.__qualname__ = f"nns_{stem}_{phase}"
        return jax.jit(fn, donate_argnums=self._donate)

    def prefill_fn(self, n: int):
        def program(params, cache, toks, slot):
            self.prefill_compiles += 1  # trace-time only
            return self._prefill_chunk(params, cache, toks, slot)

        del n  # bucketing key only; the shape specializes the jit
        return self._named(program, "prefill")

    # -- decode (whole slot batch, k tokens per dispatch) -------------------
    def step_logits(self, params, cache, tok, active):
        """One token step of every slot: ``(cache, logits (S, V))``; the
        step's expert counters are added to the cache's."""
        x, rows, counts, kv = forward_rows(
            params, self._slotted(cache), tok[:, None], self.cfg, active)
        rows["counts"] = cache["counts"] + self._tally(
            jnp.pad(counts, (0, len(COUNTER_NAMES) - counts.shape[0])), kv)
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

        def program(params, cache, tok, gen, active):
            self.decode_compiles += 1  # trace-time only
            return self._decode_scan(k, params, cache, tok, gen, active)

        return self._named(program, "decode")


def build_slot_stream(props: Dict[str, str], slots: int,
                      donate: Optional[bool] = None, mesh=None, device=None):
    """Factory of the continuous-batching path for these families: the twin
    of ``models.transformer.build_slot_stream`` (``seed`` = parameters,
    ``gen_seed`` = sampling).  Returns ``(model, params, max_seq)``."""
    cfg, family = cfg_from_props(props), props.get("arch", FAMILY)
    if mesh is not None:
        raise ValueError(
            f"arch:{family} does not shard over mesh=: the experts' ep axis "
            "and its exchange do not exist yet (one chip holds one share)")
    model = HybridSlotModel(
        cfg, slots,
        temperature=float(props.get("temperature", "0")),
        top_k=int(props.get("top_k", "0")),
        seed=int(props.get("gen_seed", "0")),
        donate=donate, device=device, family=family)
    params = init_params(cfg, int(props.get("seed", "0")), device=model.device)
    return model, model.place_params(params), cfg.max_seq
