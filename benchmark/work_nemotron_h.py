"""Operations and bytes the ALGORITHM needs for the ``nemotron_h`` family,
from a configuration's shapes and the program's routing counters.

The same rules as ``work.py``: a multiply-add is 2 operations, a weight is
read once per step in the served type (bf16), K and V of the FILLED positions
only, logits only where a token is picked.  Two things no shape gives are
taken from the program's always-on counters: how many token-expert choices
fell on a HELD expert (``gen_moe_local``) and how many distinct held experts
a step had to read (``gen_moe_expert_reads``).  The counts do not depend on
how the program routes (sorted, dense or a kernel), so a faster expert layer
cannot make them stale; what absent experts would add is no work of this
chip and is not counted.
"""

from __future__ import annotations

BF16, F32 = 2, 4

#: the program counters a reader hands over beside the driver's units
COUNTERS = ("gen_moe_local", "gen_moe_expert_reads", "gen_moe_prefill_local",
            "gen_moe_prefill_reads")


def _z(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    pattern = cfg["hybrid_override_pattern"]
    return {
        "d": cfg["hidden_size"], "H": h, "P": p, "G": g, "N": n, "di": h * p,
        "C": h * p + 2 * g * n, "K": cfg["conv_kernel"],
        "q": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
        "f": cfg["moe_intermediate_size"], "fs": cfg["moe_shared_expert_intermediate_size"],
        "E": cfg["router_experts"], "held": cfg["n_routed_experts"],
        "V": cfg["vocab_size"],
        "nM": pattern.count("M"), "nE": pattern.count("E"), "nA": pattern.count("*"),
    }


def mamba_matmul_params(cfg):
    z = _z(cfg)
    return z["d"] * (2 * z["di"] + 2 * z["G"] * z["N"] + z["H"]) + z["di"] * z["d"]


def mamba_params(cfg):
    z = _z(cfg)  # + conv kernel and bias, dt_bias, A_log, D, the gated norm, the block's norm
    return (mamba_matmul_params(cfg) + z["K"] * z["C"] + z["C"] + 3 * z["H"]
            + z["di"] + z["d"])


def attn_matmul_params(cfg):
    z = _z(cfg)
    return z["d"] * (z["q"] + 2 * z["kv"]) + z["q"] * z["d"]


def expert_params(cfg):
    """One routed expert: up and down."""
    z = _z(cfg)
    return 2 * z["d"] * z["f"]


def moe_other_params(cfg):
    """An expert layer without its routed experts: shared expert, router
    kernel and bias, the block's norm."""
    z = _z(cfg)
    return 2 * z["d"] * z["fs"] + z["d"] * z["E"] + z["E"] + z["d"]


def params(cfg):
    """Parameters held HERE: the pattern's blocks with ``n_routed_experts``
    experts each, the embedding and the head over ``vocab_size`` ids."""
    z = _z(cfg)
    return (z["nM"] * mamba_params(cfg)
            + z["nA"] * (attn_matmul_params(cfg) + z["d"])
            + z["nE"] * (z["held"] * expert_params(cfg) + moe_other_params(cfg))
            + 2 * z["V"] * z["d"] + z["d"])


def token_flops(cfg):
    """Operations ONE token needs outside the routed experts, attention's
    scores and the head: the projections, the shared expert, the router, the
    convolution, and the scan's state update (decay, outer product, add: 3 a
    state element) and read (2)."""
    z = _z(cfg)
    scan = 5 * z["H"] * z["P"] * z["N"] + 2 * z["K"] * z["C"]
    return (z["nM"] * (2 * mamba_matmul_params(cfg) + scan)
            + z["nA"] * 2 * attn_matmul_params(cfg)
            + z["nE"] * 2 * (2 * z["d"] * z["fs"] + z["d"] * z["E"]))


def _flops(cfg, tokens, local, filled, picks):
    z = _z(cfg)
    return (tokens * token_flops(cfg) + local * 2 * expert_params(cfg)
            + z["nA"] * 4 * z["q"] * filled + picks * 2 * z["d"] * z["V"])


def window_flops(cfg, units):
    """Operations behind what reached the users in a span of the run: every
    prompt token and every decode token through the blocks, a LOCAL choice's
    expert from the program's counter, attention over the filled positions,
    the head once per pick."""
    prompt_tokens = sum(units["prompts"])
    filled = units["filled"] + sum(n * (n + 1) // 2 for n in units["prompts"])
    return _flops(cfg, prompt_tokens + units["decode_tokens"], units["gen_moe_local"],
                  filled, len(units["prompts"]) + units["decode_tokens"])


def step_weight_bytes(cfg):
    """What one decode step must read whatever the batch: everything but the
    routed experts and the embedding, once, in bf16."""
    z = _z(cfg)
    return BF16 * (z["nM"] * mamba_params(cfg) + z["nA"] * (attn_matmul_params(cfg) + z["d"])
                   + z["nE"] * moe_other_params(cfg) + z["d"] * z["V"] + z["d"])


def state_bytes_per_token(cfg):
    """The recurrent state one live slot's step reads and writes: per Mamba-2
    layer the float32 scan state and the bf16 conv window, both ways."""
    z = _z(cfg)
    return z["nM"] * 2 * (z["H"] * z["P"] * z["N"] * F32 + (z["K"] - 1) * z["C"] * BF16)


def kernel_work(kind, cfg, units):
    """(operations, bytes) the runs of one program family needed.

    ``hybrid_decode``: the decode scans (``nns_hybrid_decode``).
    ``touched_experts_ffn``: every call of the small-batch expert kernel
    (``ops/expert_ffn.py``; decode steps and prefill chunks alike): a local
    choice's two products, each touched expert's ``up`` and ``down`` read
    once (the token rows, a few hundred KB a call, are left out)."""
    if kind == "touched_experts_ffn":
        return (units["gen_moe_local"] * 2 * expert_params(cfg),
                units["gen_moe_expert_reads"] * expert_params(cfg) * BF16)
    if kind != "hybrid_decode":
        raise ValueError(f"no kernel work function {kind!r}")
    z = _z(cfg)
    local = units["gen_moe_local"] - units["gen_moe_prefill_local"]
    reads = units["gen_moe_expert_reads"] - units["gen_moe_prefill_reads"]
    flops = _flops(cfg, units["decode_tokens"], local, units["filled"], units["decode_tokens"])
    nbytes = (units["steps"] * step_weight_bytes(cfg)
              + reads * expert_params(cfg) * BF16
              + units["decode_tokens"] * state_bytes_per_token(cfg)
              + units["filled"] * z["nA"] * 2 * z["kv"] * BF16)
    return flops, nbytes
