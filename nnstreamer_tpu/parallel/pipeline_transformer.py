"""Manual-SPMD transformer training step over a 5-axis mesh (dp/tp/sp/pp/ep).

The reference has *no* intra-model sharding of any kind (SURVEY §2.3 "NOT
present": no TP/SP/EP/CP, no collectives); scale-out there is among-device
fan-out over nnstreamer-edge.  This module is the TPU build's net-new
answer: one training step written per-shard under ``shard_map`` so every
parallelism dimension is explicit and rides ICI collectives:

  * ``dp`` — batch sharded; gradient ``psum`` (inserted by autodiff of the
    loss ``psum``).
  * ``tp`` — Megatron-style: qkv/up kernels column-sharded, out/down
    kernels row-sharded, one ``psum`` after each row-sharded matmul.
  * ``sp`` — sequence sharded; exact attention via the ring-attention body
    (``ring_attention._ring_attn_local``): K/V blocks ``ppermute`` around
    the ring.
  * ``pp`` — layer stack split into ``pp`` stages (stage-stacked param
    leading axis sharded on pp); GPipe microbatch schedule: activations
    hop stage→stage via ``ppermute`` each tick, M+S-1 ticks total.
  * ``ep`` — Switch-style top-1 MoE FFN: tokens dispatched to experts with
    ``all_to_all`` over ep, expert matmuls (tp-sharded), combined back.

Everything is a single jitted program; XLA overlaps the ppermute/all_to_all
DMAs with the MXU matmuls.  Pattern references: GPipe (arXiv 1811.06965),
Megatron-LM (1909.08053), Switch Transformer (2101.03961), Ring Attention
(2310.01889) — all public; see PAPERS.md.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import _ring_attn_local


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2        # must divide by mesh pp
    d_ff: int = 128
    n_experts: int = 4       # 0 => dense FFN; must divide by mesh ep
    max_seq: int = 64
    n_microbatches: int = 2  # GPipe schedule depth (must divide local batch)
    capacity_factor: float = 2.0
    dtype: Any = jnp.float32


# ---------------------------------------------------------------------------
# Params: plain pytree, stage-stacked on the leading axis.
# ---------------------------------------------------------------------------
def init_params(cfg: PipelineConfig, seed: int = 0) -> Dict[str, Any]:
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    L, D, F, V, E = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_experts
    dt = cfg.dtype
    s = lambda *sh: 1.0 / np.sqrt(sh[-2] if len(sh) >= 2 else sh[-1])
    p = {
        "embed": jax.random.normal(ks[0], (V, D), dt) * 0.02,
        "pos": jax.random.normal(ks[1], (cfg.max_seq, D), dt) * 0.02,
        "ln1": jnp.ones((L, D), dt),
        # (L, D, 3, D) so each of q/k/v column-shards independently on tp
        "qkv": jax.random.normal(ks[2], (L, D, 3, D), dt) * s(D, D),
        "out": jax.random.normal(ks[3], (L, D, D), dt) * s(D, D),
        "ln2": jnp.ones((L, D), dt),
        "ln_f": jnp.ones((D,), dt),
        "lm_head": jax.random.normal(ks[4], (D, V), dt) * s(D, V),
    }
    if E > 0:
        p["router"] = jax.random.normal(ks[5], (L, D, E), dt) * s(D, E)
        p["moe_up"] = jax.random.normal(ks[6], (L, E, D, F), dt) * s(D, F)
        p["moe_down"] = jax.random.normal(ks[7], (L, E, F, D), dt) * s(F, D)
    else:
        p["mlp_up"] = jax.random.normal(ks[6], (L, D, F), dt) * s(D, F)
        p["mlp_down"] = jax.random.normal(ks[7], (L, F, D), dt) * s(F, D)
    return p


def param_specs(cfg: PipelineConfig) -> Dict[str, P]:
    """PartitionSpec per leaf: stage axis on pp, Megatron dims on tp,
    experts on ep."""
    sp = {
        "embed": P(),
        "pos": P(),
        "ln1": P("pp", None),
        "qkv": P("pp", None, None, "tp"),
        "out": P("pp", "tp", None),
        "ln2": P("pp", None),
        "ln_f": P(),
        "lm_head": P("tp", None),
    }
    if cfg.n_experts > 0:
        sp["router"] = P("pp", None, None)
        sp["moe_up"] = P("pp", "ep", None, "tp")
        sp["moe_down"] = P("pp", "ep", "tp", None)
    else:
        sp["mlp_up"] = P("pp", None, "tp")
        sp["mlp_down"] = P("pp", "tp", None)
    return sp


AXES = ("dp", "pp", "sp", "tp", "ep")


def _ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + 1e-6) * scale


def _moe_ffn(h, router, w_up, w_down, cfg: PipelineConfig, mesh: Mesh):
    """Per-shard Switch top-1 MoE.  h: (N, D) local tokens; experts sharded
    over ep (w_up: (E_loc, D, F_loc)); dispatch/combine via all_to_all."""
    ep = mesh.shape["ep"]
    N, D = h.shape
    E = cfg.n_experts
    C = max(1, int(cfg.capacity_factor * N / E))  # per-source-shard capacity

    glogits = h @ router                       # (N, E)
    gprobs = jax.nn.softmax(glogits.astype(jnp.float32), -1)
    eidx = jnp.argmax(gprobs, -1)              # (N,)
    gate = jnp.max(gprobs, -1)                 # (N,)
    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.float32)          # (N, E)
    pos = jnp.cumsum(onehot, 0) * onehot                          # 1-based
    keep = (pos > 0) & (pos <= C)
    disp = onehot[..., None] * jax.nn.one_hot(
        (pos - 1).astype(jnp.int32), C, dtype=jnp.float32
    )                                                             # (N, E, C)
    disp = disp * keep.astype(jnp.float32)[..., None]
    xin = jnp.einsum("nec,nd->ecd", disp, h.astype(jnp.float32)).astype(h.dtype)

    if ep > 1:
        # (E, C, D) -> each ep rank keeps its E/ep experts, gains the
        # other ranks' capacity slots: (E/ep, ep*C, D)
        xin = lax.all_to_all(xin, "ep", split_axis=0, concat_axis=1, tiled=True)
    act = jnp.einsum("ecd,edf->ecf", xin, w_up,
                     preferred_element_type=jnp.float32)
    act = jax.nn.gelu(act).astype(h.dtype)
    yout = jnp.einsum("ecf,efd->ecd", act, w_down,
                      preferred_element_type=jnp.float32)
    yout = lax.psum(yout, "tp")  # F is tp-sharded: partial sums
    if ep > 1:
        yout = lax.all_to_all(yout, "ep", split_axis=1, concat_axis=0, tiled=True)
    out = jnp.einsum("nec,ecd->nd", disp * gate[:, None, None].astype(jnp.float32),
                     yout)
    return out.astype(h.dtype)


def _make_stage_fn(cfg: PipelineConfig, mesh: Mesh):
    """Per-shard body for ONE transformer layer (tp/sp/ep-parallel)."""
    tp = mesh.shape["tp"]
    H_loc = cfg.n_heads // tp
    hd = cfg.d_model // cfg.n_heads
    D = cfg.d_model

    def layer(x, lp):
        # x: (mb, T_loc, D) full residual stream on every tp rank
        B, T, _ = x.shape
        h = _ln(x, lp["ln1"])
        # kernel (D, 3, D/tp): q/k/v each col-sharded on tp (head-aligned)
        qkv = jnp.einsum("btd,dke->btke", h, lp["qkv"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B, T, D/tp)
        q = q.reshape(B, T, H_loc, hd)
        k = k.reshape(B, T, H_loc, hd)
        v = v.reshape(B, T, H_loc, hd)
        attn = _ring_attn_local(
            q, k, v, axis_name="sp", all_axes=AXES, causal=True
        ).reshape(B, T, D // tp)
        proj = attn @ lp["out"]                # row-sharded: partial sums
        x = x + lax.psum(proj, "tp")
        h = _ln(x, lp["ln2"])
        if cfg.n_experts > 0:
            y = _moe_ffn(h.reshape(B * T, D), lp["router"], lp["moe_up"],
                         lp["moe_down"], cfg, mesh).reshape(B, T, D)
        else:
            a = jax.nn.gelu(h @ lp["mlp_up"])  # col-sharded
            y = lax.psum(a @ lp["mlp_down"], "tp")
        return x + y

    def stage(stage_params, x):
        # stage_params leaves have leading axis L_loc (this stage's layers)
        L_loc = stage_params["ln1"].shape[0]
        for i in range(L_loc):
            x = layer(x, jax.tree.map(lambda a: a[i], stage_params))
        return x

    return stage


def make_pipeline_train_step(
    mesh: Mesh,
    cfg: Optional[PipelineConfig] = None,
    learning_rate: float = 1e-3,
    seed: int = 0,
):
    """Build the 5-axis-parallel LM training step.

    Returns ``(train_step, params, opt_state, data_sharding)``;
    ``train_step(params, opt_state, tokens) -> (params, opt_state, loss)``.
    ``tokens``: (B, T) int32, B % (dp * n_microbatches) == 0, T % sp == 0.
    """
    import optax

    cfg = cfg or PipelineConfig()
    for ax in AXES:
        if ax not in mesh.shape:
            raise ValueError(f"mesh must have axis {ax!r} (size 1 is fine)")
    pp, sp_n, tp, ep = (mesh.shape[a] for a in ("pp", "sp", "tp", "ep"))
    if cfg.n_layers % pp:
        raise ValueError("n_layers must divide by pp")
    if cfg.n_heads % tp or cfg.d_ff % tp or cfg.d_model % tp:
        raise ValueError("heads/d_ff/d_model must divide by tp")
    if cfg.n_experts and cfg.n_experts % ep:
        raise ValueError("n_experts must divide by ep")

    params = init_params(cfg, seed)
    specs = param_specs(cfg)
    params = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }
    tx = optax.adamw(learning_rate)
    # optimizer moments propagate the param shardings; leaves with NO
    # param dependence (adam's step count) come out single-device, so pin
    # every non-mesh leaf replicated over the mesh — a mixed placement
    # breaks later jitted steps and checkpoint-restore templates
    opt_state = jax.jit(tx.init)(params)
    _rep = jax.sharding.NamedSharding(mesh, P())
    opt_state = jax.tree.map(
        lambda a: a if isinstance(
            getattr(a, "sharding", None), jax.sharding.NamedSharding
        ) else jax.device_put(a, _rep),
        opt_state,
    )
    data_sh = NamedSharding(mesh, P("dp", "sp"))
    stage_fn = _make_stage_fn(cfg, mesh)
    M = cfg.n_microbatches
    S = pp

    def _fwd_loss(p, tokens):
        """Per-shard: tokens (B_loc, T_loc) int32."""
        B_loc, T_loc = tokens.shape
        mb = B_loc // M
        D, V = cfg.d_model, cfg.vocab
        pp_idx = lax.axis_index("pp")
        sp_idx = lax.axis_index("sp")
        tp_idx = lax.axis_index("tp")

        # ---- embed (stage-0 work, computed by all pp ranks; masked later)
        posids = sp_idx * T_loc + jnp.arange(T_loc)
        x0 = p["embed"][tokens] + p["pos"][posids][None]       # (B_loc,T_loc,D)
        x0 = x0.reshape(M, mb, T_loc, D)

        # ---- next-token targets: shift across the sp ring
        first = lax.ppermute(
            tokens[:, :1], "sp", [(j, (j - 1) % sp_n) for j in range(sp_n)]
        )
        targets = jnp.concatenate([tokens[:, 1:], first], axis=1)
        tmask = jnp.ones((B_loc, T_loc), jnp.float32)
        if sp_n > 1:
            tmask = jnp.where(sp_idx == sp_n - 1,
                              tmask.at[:, -1].set(0.0), tmask)
        else:
            tmask = tmask.at[:, -1].set(0.0)
        targets = targets.reshape(M, mb, T_loc)
        tmask = tmask.reshape(M, mb, T_loc)

        fwd_perm = [(j, (j + 1) % S) for j in range(S)]

        def tick(carry, t):
            state, loss_sum, cnt = carry
            # activations hop one stage forward; stage 0 ingests microbatch t
            shifted = lax.ppermute(state, "pp", fwd_perm) if S > 1 else state
            inj = lax.dynamic_index_in_dim(
                x0, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            cur = jnp.where(pp_idx == 0, inj, shifted) if S > 1 else inj
            new = stage_fn(p_stage, cur)
            # last stage, ticks S-1..M+S-2 hold microbatch t-(S-1)'s output
            midx = jnp.clip(t - (S - 1), 0, M - 1)
            hvalid = (t >= S - 1) & (pp_idx == S - 1)
            h = _ln(new, p["ln_f"])
            h_loc = lax.dynamic_slice_in_dim(h, tp_idx * (D // tp), D // tp, 2)
            logits = lax.psum(
                jnp.einsum("btd,dv->btv", h_loc,
                           p["lm_head"].astype(jnp.float32)), "tp")
            tgt = lax.dynamic_index_in_dim(targets, midx, 0, keepdims=False)
            msk = lax.dynamic_index_in_dim(tmask, midx, 0, keepdims=False)
            logp = jax.nn.log_softmax(logits, -1)
            ll = jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
            valid = hvalid.astype(jnp.float32)
            loss_sum = loss_sum + valid * (-(ll * msk).sum())
            cnt = cnt + valid * msk.sum()
            return (new, loss_sum, cnt), None

        p_stage = {
            k: v for k, v in p.items()
            if k not in ("embed", "pos", "ln_f", "lm_head")
        }
        state0 = lax.pcast(
            jnp.zeros((mb, T_loc, D), cfg.dtype), AXES, to="varying")
        l0 = lax.pcast(jnp.zeros((), jnp.float32), AXES, to="varying")
        (_, loss_sum, cnt), _ = lax.scan(
            tick, (state0, l0, l0), jnp.arange(M + S - 1)
        )
        # loss lives on the last pp stage only; tokens are sharded dp×sp.
        # psum over tp too (numerator/denominator both scale by tp — exact).
        total = lax.psum(loss_sum, ("pp", "dp", "sp", "tp", "ep"))
        n = lax.psum(cnt, ("pp", "dp", "sp", "tp", "ep"))
        return total / n

    # ---- per-shard loss AND grad in ONE shard-mapped body ----------------
    # value_and_grad lives INSIDE the body (per-shard grads, psum'd over
    # each param's replication axes) instead of wrapping the shard_map:
    # per-shard AD through the body's collectives is the classic
    # pmap-era recipe, and it keeps the replicated-out_specs transpose
    # out of the picture.  The math is identical: the final psum's
    # transpose seeds cotangent 1 on every device, so local partials
    # summed over a param's replication axes ARE the global grad.
    mesh_axes = tuple(mesh.axis_names)

    def _repl_axes(spec: P):
        named = set()
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                named.update(entry)
            else:
                named.add(entry)
        return tuple(a for a in mesh_axes if a not in named)

    grad_psum_axes = {k: _repl_axes(specs[k]) for k in params}

    def _fwd_loss_and_grad(p, tokens):
        loss, grads = jax.value_and_grad(_fwd_loss)(p, tokens)
        grads = {
            k: (lax.psum(g, grad_psum_axes[k]) if grad_psum_axes[k] else g)
            for k, g in grads.items()
        }
        return loss, grads

    in_specs = ({k: specs[k] for k in params}, P("dp", "sp"))
    sharded_loss_and_grad = jax.shard_map(
        _fwd_loss_and_grad, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), {k: specs[k] for k in params}),
        check_vma=False,
    )

    def _step(p, opt, tokens):
        loss, grads = sharded_loss_and_grad(p, tokens)
        updates, opt = tx.update(grads, opt, p)
        p = optax.apply_updates(p, updates)
        return p, opt, loss

    train_step = jax.jit(_step, donate_argnums=(0, 1))
    return train_step, params, opt_state, data_sh


# ---------------------------------------------------------------------------
# Single-device oracle (same params, dense math) for tests.
# ---------------------------------------------------------------------------
def reference_loss(params, tokens, cfg: PipelineConfig) -> jnp.ndarray:
    """Unsharded forward+loss over the same param pytree (test oracle;
    exact match requires capacity_factor high enough that no token drops)."""
    B, T = tokens.shape
    D, H, V = cfg.d_model, cfg.n_heads, cfg.vocab
    x = params["embed"][tokens] + params["pos"][jnp.arange(T)][None]
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], {
            k: v for k, v in params.items()
            if k not in ("embed", "pos", "ln_f", "lm_head")
        })
        h = _ln(x, lp["ln1"])
        qkv = jnp.einsum("btd,dke->btke", h, lp["qkv"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = q.reshape(B, T, H, D // H)
        k = k.reshape(B, T, H, D // H)
        v = v.reshape(B, T, H, D // H)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(D // H)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, -1).astype(v.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, D)
        x = x + attn @ lp["out"]
        h = _ln(x, lp["ln2"])
        if cfg.n_experts > 0:
            N = B * T
            hf = h.reshape(N, D)
            gp = jax.nn.softmax((hf @ lp["router"]).astype(jnp.float32), -1)
            eidx = jnp.argmax(gp, -1)
            gate = jnp.max(gp, -1)
            xin = jnp.einsum("ne,nd->ned", jax.nn.one_hot(eidx, cfg.n_experts),
                             hf.astype(jnp.float32)).astype(h.dtype)
            act = jax.nn.gelu(jnp.einsum("ned,edf->nef", xin, lp["moe_up"],
                                         preferred_element_type=jnp.float32)
                              ).astype(h.dtype)
            yo = jnp.einsum("nef,efd->ned", act, lp["moe_down"],
                            preferred_element_type=jnp.float32)
            y = jnp.einsum("ned,ne->nd", yo,
                           jax.nn.one_hot(eidx, cfg.n_experts) *
                           gate[:, None]).reshape(B, T, D).astype(h.dtype)
        else:
            y = jax.nn.gelu(h @ lp["mlp_up"]) @ lp["mlp_down"]
        x = x + y
    hf = _ln(x, params["ln_f"])
    logits = jnp.einsum("btd,dv->btv", hf, params["lm_head"].astype(jnp.float32))
    targets = jnp.roll(tokens, -1, 1)
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    msk = jnp.ones_like(ll).at[:, -1].set(0.0)
    return -(ll * msk).sum() / msk.sum()


# ---------------------------------------------------------------------------
# Elastic checkpointing: preemptible-TPU recovery for the 5-axis train step
# (SURVEY §5.3/§5.4 — the reference checkpoints only the trainer element;
# sharded multi-chip training state is net-new).  Orbax persists each
# jax.Array with its sharding; restoring against a sharded template puts
# every shard back on its mesh position, so a resumed run is bit-identical
# to an uninterrupted one (tests/test_pipeline_parallel.py asserts this).
# ---------------------------------------------------------------------------
def save_train_state(path: str, step: int, params, opt_state) -> str:
    """Persist (params, opt_state) as checkpoint `step` under `path`."""
    from ..core.checkpoint import save_state

    return save_state(path, step, {"params": params, "opt_state": opt_state})


def restore_train_state(path: str, step: int, params_template, opt_template):
    """-> (params, opt_state) restored onto the templates' shardings."""
    from ..core.checkpoint import restore_state

    state = restore_state(
        path, step, {"params": params_template, "opt_state": opt_template}
    )

    def _resharded(tmpl_tree, got_tree):
        # orbax can restore scalar/replicated leaves onto a single device;
        # re-commit every leaf to its template's mesh sharding so the next
        # jitted step sees a consistent placement
        def one(got, tmpl):
            if hasattr(tmpl, "sharding") and hasattr(got, "shape"):
                return jax.device_put(got, tmpl.sharding)
            return got

        return jax.tree.map(one, got_tree, tmpl_tree)

    return (
        _resharded(params_template, state["params"]),
        _resharded(opt_template, state["opt_state"]),
    )
