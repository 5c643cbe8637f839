"""Operations and bytes the ALGORITHM needs for the ``lfm2_moe`` family, from
a configuration's shapes and the program's always-on counters.

The same rules as ``work.py``, ``work_nemotron_h.py`` and
``work_cohere2_moe.py``: a multiply-add is 2 operations, a weight is read once
per step (or prefill chunk) in the served type (bf16), a touched expert's
THREE matrices once (22 020 096 B at the published widths), logits only where
a token is picked.  K/V rows are counted by POSITION, never by what a kernel
copied: a decode step needs the ``pos`` older rows of each attention layer,
which the program sums as ``gen_kv_rows_need``; a prefill chunk's queries see
``gen_kv_prefill_rows_need`` keys, their own counted.  A short-convolution
layer's token needs its ``conv_L_cache`` taps' products, its two gates, and
the ``conv_L_cache - 1`` state rows it reads.  Every expert of a layer is
held here, so every choice is local.
"""

from __future__ import annotations

BF16 = 2

#: the program counters a reader hands over beside the driver's units
COUNTERS = ("gen_moe_local", "gen_moe_expert_reads", "gen_moe_prefill_local",
            "gen_moe_prefill_reads", "gen_kv_rows_need", "gen_kv_prefill_rows_need",
            "gen_prefill_chunks", "gen_prefill_tokens", "gen_first_tokens")


def _z(cfg):
    kinds = cfg["layer_types"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    dense = min(cfg["num_dense_layers"], len(kinds))
    return {
        "d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
        "f": cfg["moe_intermediate_size"], "dh": dh,
        "q": cfg["num_attention_heads"] * dh, "kv": cfg["num_key_value_heads"] * dh,
        "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
        "K": cfg["conv_L_cache"], "L": len(kinds),
        "nC": kinds.count("conv"), "nA": kinds.count("full_attention"),
        "nD": dense, "nE": len(kinds) - dense,
    }


def conv_params(cfg):
    """One short-convolution operator: in, out and the taps."""
    z = _z(cfg)
    return z["d"] * 3 * z["d"] + z["d"] * z["d"] + z["K"] * z["d"]


def attn_params(cfg):
    """One attention operator: q, k, v, o and the two QK norms."""
    z = _z(cfg)
    return z["d"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["d"] + 2 * z["dh"]


def dense_params(cfg):
    z = _z(cfg)
    return 3 * z["d"] * z["ff"]


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    z = _z(cfg)
    return 3 * z["d"] * z["f"]


def other_params(cfg):
    """Everything of the layers but the routed experts: the operators, the
    two norms a layer, the dense MLPs, the routers with their bias."""
    z = _z(cfg)
    return (z["nC"] * conv_params(cfg) + z["nA"] * attn_params(cfg) + z["L"] * 2 * z["d"]
            + z["nD"] * dense_params(cfg) + z["nE"] * (z["d"] * z["E"] + z["E"]))


def params(cfg):
    """Parameters held HERE: the layers with every expert, the embedding
    (= the head) and the final norm."""
    z = _z(cfg)
    return (other_params(cfg) + z["nE"] * z["E"] * expert_params(cfg)
            + z["V"] * z["d"] + z["d"])


def active_params(cfg):
    """Parameters one token runs through: ``num_experts_per_tok`` routed
    experts an expert layer."""
    z = _z(cfg)
    return (other_params(cfg) + z["nE"] * z["k"] * expert_params(cfg)
            + z["V"] * z["d"] + z["d"])


def token_flops(cfg):
    """Operations ONE token needs outside the routed experts, attention's
    scores and the head: the projections, the dense MLPs, the routers, and a
    conv layer's taps (a multiply-add each) and two gates (a multiply each)."""
    z = _z(cfg)
    conv = 2 * 4 * z["d"] * z["d"] + 2 * z["K"] * z["d"] + 2 * z["d"]
    attn = 2 * (z["d"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["d"])
    return (z["nC"] * conv + z["nA"] * attn + z["nD"] * 2 * dense_params(cfg)
            + z["nE"] * 2 * z["d"] * z["E"])


def prompt_keys(cfg, n):
    """Keys the ``n`` queries of one prompt see over all attention layers,
    their own counted: ``i + 1`` at position ``i``."""
    return _z(cfg)["nA"] * n * (n + 1) // 2


def _flops(cfg, tokens, local, keys, picks):
    z = _z(cfg)
    return (tokens * token_flops(cfg) + local * 2 * expert_params(cfg)
            + 4 * z["q"] * keys + picks * 2 * z["d"] * z["V"])


def window_flops(cfg, units):
    """Operations behind what reached the users in a span of the run: every
    prompt token and every decode token through the layers, a choice's expert
    from the program's counter, attention over the keys position allows (a
    prompt's from its length, the decode steps' from the program's count of
    the rows they needed, plus their own), the head once per pick."""
    z = _z(cfg)
    decode = units["decode_tokens"]
    keys = (sum(prompt_keys(cfg, n) for n in units["prompts"])
            + units["gen_kv_rows_need"] + decode * z["nA"])
    return _flops(cfg, sum(units["prompts"]) + decode, units["gen_moe_local"],
                  keys, len(units["prompts"]) + decode)


def step_weight_bytes(cfg):
    """What one decode step, or one prefill chunk, must read whatever the
    batch: everything but the routed experts, the embedding once as the
    head, in bf16."""
    z = _z(cfg)
    return BF16 * (other_params(cfg) + z["d"] * z["V"] + z["d"])


def kv_row_bytes(cfg):
    """One position's K and V in one attention layer, bf16."""
    return 2 * _z(cfg)["kv"] * BF16


def conv_state_bytes(cfg):
    """The state rows one token's step reads in one conv layer, bf16."""
    z = _z(cfg)
    return (z["K"] - 1) * z["d"] * BF16


def kernel_work(kind, cfg, units):
    """(operations, bytes) the runs of one program family or kernel needed.

    ``lfm2_moe_decode`` / ``lfm2_moe_prefill``: the decode scans and the
    prefill chunks (``jit_nns_lfm2_moe_decode`` / ``_prefill``).  A chunk's
    bytes leave out the cache rows it reads (each needed row once: under 1 %
    of the experts it streams), so its share can only read low.
    ``touched_experts_ffn``: every call of the small-batch expert kernel in
    its gated form: a choice's three products, each touched expert's three
    matrices read once.  ``decode_attention``: the per-token reads of the
    scans: scores and mixes over the rows position allows plus the new row,
    those rows' K and V read once.  ``prefill_attention``: the chunks'
    attention: the keys their queries see; K and V of the rows are left out
    as above.  ``short_conv``: the decode steps' convolutions: the taps and
    gates, the state rows read once."""
    z = _z(cfg)
    expert_bytes = expert_params(cfg) * BF16
    decode = units["decode_tokens"]
    if kind == "touched_experts_ffn":
        return (units["gen_moe_local"] * 2 * expert_params(cfg),
                units["gen_moe_expert_reads"] * expert_bytes)
    if kind == "decode_attention":
        keys = units["gen_kv_rows_need"] + decode * z["nA"]
        return 4 * z["q"] * keys, units["gen_kv_rows_need"] * kv_row_bytes(cfg)
    if kind == "prefill_attention":
        return 4 * z["q"] * units["gen_kv_prefill_rows_need"], 0
    if kind == "short_conv":
        return (decode * z["nC"] * (2 * z["K"] * z["d"] + 2 * z["d"]),
                decode * z["nC"] * conv_state_bytes(cfg))
    if kind == "lfm2_moe_prefill":
        flops = _flops(cfg, units["gen_prefill_tokens"], units["gen_moe_prefill_local"],
                       units["gen_kv_prefill_rows_need"], units["gen_first_tokens"])
        nbytes = (units["gen_prefill_chunks"] * (step_weight_bytes(cfg)
                                                 + z["nC"] * conv_state_bytes(cfg))
                  + units["gen_moe_prefill_reads"] * expert_bytes
                  + units["gen_prefill_tokens"] * z["nA"] * kv_row_bytes(cfg))
        return flops, nbytes
    if kind != "lfm2_moe_decode":
        raise ValueError(f"no kernel work function {kind!r}")
    local = units["gen_moe_local"] - units["gen_moe_prefill_local"]
    reads = units["gen_moe_expert_reads"] - units["gen_moe_prefill_reads"]
    keys = units["gen_kv_rows_need"] + decode * z["nA"]
    flops = _flops(cfg, decode, local, keys, decode)
    nbytes = (units["steps"] * step_weight_bytes(cfg) + reads * expert_bytes
              + keys * kv_row_bytes(cfg) + decode * z["nC"] * conv_state_bytes(cfg))
    return flops, nbytes
