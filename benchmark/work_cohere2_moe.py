"""Operations and bytes the ALGORITHM needs for the ``cohere2_moe`` family,
from a configuration's shapes and the program's always-on counters.

The same rules as ``work.py`` and ``work_nemotron_h.py``: a multiply-add is 2
operations, a weight is read once per step (or prefill chunk) in the served
type (bf16), a touched expert's THREE matrices once, logits only where a token
is picked.  K/V rows are counted by POSITION AND WINDOW, never by what a
kernel copied: a decode step needs the older rows ``min(pos, window - 1)`` of
a window layer (the window counts the query's own position) and ``pos`` of a
global layer, which the program sums as ``gen_kv_rows_need``; a prefill
chunk's queries see ``gen_kv_prefill_rows_need`` keys, their own counted.
What absent experts would add is no work of this chip and is not counted.
"""

from __future__ import annotations

BF16 = 2

#: the program counters a reader hands over beside the driver's units
COUNTERS = ("gen_moe_local", "gen_moe_expert_reads", "gen_moe_prefill_local",
            "gen_moe_prefill_reads", "gen_kv_rows_need", "gen_kv_prefill_rows_need",
            "gen_prefill_chunks", "gen_prefill_tokens", "gen_first_tokens")


def _z(cfg):
    kinds = cfg["layer_types"]
    return {
        "d": cfg["hidden_size"], "f": cfg["intermediate_size"],
        "q": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
        "E": cfg["router_experts"], "held": cfg["num_experts"],
        "shared": cfg["num_shared_experts"], "V": cfg["vocab_size"],
        "W": cfg["sliding_window"], "L": len(kinds),
        "nW": kinds.count("sliding_attention"), "nG": kinds.count("full_attention"),
    }


def attn_params(cfg):
    z = _z(cfg)
    return z["d"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["d"]


def expert_params(cfg):
    """One expert, routed or shared: gate, up and down."""
    z = _z(cfg)
    return 3 * z["d"] * z["f"]


def layer_other_params(cfg):
    """A layer without its routed experts: attention, the shared experts,
    the router and the norm."""
    z = _z(cfg)
    return (attn_params(cfg) + z["shared"] * expert_params(cfg)
            + z["d"] * z["E"] + z["d"])


def params(cfg):
    """Parameters held HERE: the layers with ``num_experts`` experts each,
    the embedding (= the head) over ``vocab_size`` ids and the final norm."""
    z = _z(cfg)
    return (z["L"] * (layer_other_params(cfg) + z["held"] * expert_params(cfg))
            + z["V"] * z["d"] + z["d"])


def active_params(cfg):
    """Parameters one token runs through: ``num_experts_per_tok`` routed
    experts a layer."""
    z = _z(cfg)
    return (z["L"] * (layer_other_params(cfg)
                      + cfg["num_experts_per_tok"] * expert_params(cfg))
            + z["V"] * z["d"] + z["d"])


def token_flops(cfg):
    """Operations ONE token needs outside the routed experts, attention's
    scores and the head: the projections, the shared experts, the router."""
    z = _z(cfg)
    return z["L"] * 2 * (attn_params(cfg) + z["shared"] * expert_params(cfg)
                         + z["d"] * z["E"])


def prompt_keys(cfg, n):
    """Keys the ``n`` queries of one prompt see over all layers, their own
    counted: ``i + 1`` at position ``i`` of a global layer, at most the
    window in a window layer."""
    z = _z(cfg)
    w = min(n, z["W"])
    windowed = w * (w + 1) // 2 + (n - w) * z["W"]
    return z["nG"] * n * (n + 1) // 2 + z["nW"] * windowed


def _flops(cfg, tokens, local, keys, picks):
    z = _z(cfg)
    return (tokens * token_flops(cfg) + local * 2 * expert_params(cfg)
            + 4 * z["q"] * keys + picks * 2 * z["d"] * z["V"])


def window_flops(cfg, units):
    """Operations behind what reached the users in a span of the run: every
    prompt token and every decode token through the layers, a LOCAL choice's
    expert from the program's counter, attention over the keys position and
    window allow (a prompt's from its length, the decode steps' from the
    program's count of the rows they needed, plus their own), the head once
    per pick."""
    z = _z(cfg)
    decode = units["decode_tokens"]
    keys = (sum(prompt_keys(cfg, n) for n in units["prompts"])
            + units["gen_kv_rows_need"] + decode * z["L"])
    return _flops(cfg, sum(units["prompts"]) + decode, units["gen_moe_local"],
                  keys, len(units["prompts"]) + decode)


def step_weight_bytes(cfg):
    """What one decode step, or one prefill chunk, must read whatever the
    batch: everything but the routed experts, the embedding once as the
    head, in bf16."""
    z = _z(cfg)
    return BF16 * (z["L"] * layer_other_params(cfg) + z["d"] * z["V"] + z["d"])


def kv_row_bytes(cfg):
    """One position's K and V in one layer, bf16."""
    return 2 * _z(cfg)["kv"] * BF16


def kernel_work(kind, cfg, units):
    """(operations, bytes) the runs of one program family or kernel needed.

    ``cohere2_moe_decode`` / ``cohere2_moe_prefill``: the decode scans and
    the prefill chunks (``jit_nns_cohere2_moe_decode`` / ``_prefill``).  A
    chunk's bytes leave out the cache rows it reads (each needed row once:
    under 1 % of its weights), so its share can only read low.
    ``touched_experts_ffn``: every call of the small-batch expert kernel in
    its gated form: a local choice's three products, each touched expert's
    three matrices read once.  ``decode_attention``: the per-token reads of
    the scans: scores and mixes over the rows position and window allow
    plus the new row, those rows' K and V read once.  ``prefill_attention``:
    the chunks' attention: the keys their queries see; K and V of the rows
    are left out as above."""
    z = _z(cfg)
    expert_bytes = expert_params(cfg) * BF16
    if kind == "touched_experts_ffn":
        return (units["gen_moe_local"] * 2 * expert_params(cfg),
                units["gen_moe_expert_reads"] * expert_bytes)
    if kind == "decode_attention":
        keys = units["gen_kv_rows_need"] + units["decode_tokens"] * z["L"]
        return 4 * z["q"] * keys, units["gen_kv_rows_need"] * kv_row_bytes(cfg)
    if kind == "prefill_attention":
        return 4 * z["q"] * units["gen_kv_prefill_rows_need"], 0
    if kind == "cohere2_moe_prefill":
        flops = _flops(cfg, units["gen_prefill_tokens"], units["gen_moe_prefill_local"],
                       units["gen_kv_prefill_rows_need"], units["gen_first_tokens"])
        nbytes = (units["gen_prefill_chunks"] * step_weight_bytes(cfg)
                  + units["gen_moe_prefill_reads"] * expert_bytes
                  + units["gen_prefill_tokens"] * z["L"] * kv_row_bytes(cfg))
        return flops, nbytes
    if kind != "cohere2_moe_decode":
        raise ValueError(f"no kernel work function {kind!r}")
    local = units["gen_moe_local"] - units["gen_moe_prefill_local"]
    reads = units["gen_moe_expert_reads"] - units["gen_moe_prefill_reads"]
    decode = units["decode_tokens"]
    flops = _flops(cfg, decode, local, units["gen_kv_rows_need"] + decode * z["L"], decode)
    nbytes = (units["steps"] * step_weight_bytes(cfg) + reads * expert_bytes
              + (units["gen_kv_rows_need"] + decode * z["L"]) * kv_row_bytes(cfg))
    return flops, nbytes
