"""Operations and bytes the ALGORITHM needs, from a configuration's shapes.

The least work whatever implements it: a multiply-add is 2 operations, a
weight is read once per step in the served type (bf16, 2 bytes), a decode
step reads the K and V of the FILLED positions only, logits are computed only
where a token is picked.  A paged cache or a fused kernel therefore cannot
make these counts stale, and padding, recomputation and dead positions count
as waste (a lower share), never as work.
"""

from __future__ import annotations

BF16 = 2


def vit_tokens(cfg):
    return (cfg["size"] // cfg["patch"]) ** 2 + 1


def vit_params(cfg):
    d, f, p = cfg["d_model"], cfg["d_ff"], cfg["patch"]
    block = 3 * d * d + d * d + 2 * d * f + 4 * d           # projections + 2 LayerNorms
    return (p * p * 3 * d + d + d + vit_tokens(cfg) * d    # patch embed, cls, positions
            + cfg["layers"] * block + 2 * d + d * cfg["classes"] + cfg["classes"])


def vit_forward_flops(cfg):
    """Matmul operations of one frame's forward pass."""
    d, f, p, t = cfg["d_model"], cfg["d_ff"], cfg["patch"], vit_tokens(cfg)
    patch = 2 * (t - 1) * (p * p * 3) * d
    layer = 2 * t * d * 3 * d + 2 * t * d * d + 4 * t * d * f + 4 * t * t * d
    return patch + cfg["layers"] * layer + 2 * d * cfg["classes"]


def gpt_block_params(cfg):
    d, f = cfg["d_model"], cfg["d_ff"]
    return cfg["layers"] * (4 * d * d + 2 * d * f)


def gpt_params(cfg):
    d = cfg["d_model"]
    norms = (2 * cfg["layers"] + 1) * 2 * d
    return (gpt_block_params(cfg) + norms + cfg["vocab"] * d      # blocks, embedding
            + cfg["seq"] * d + d * cfg["vocab"])                  # positions, untied head


def gpt_token_flops(cfg, filled, picks=False):
    """Operations to process ONE token that attends over ``filled`` positions
    (itself included); ``picks`` adds the output head."""
    d = cfg["d_model"]
    ops = 2 * gpt_block_params(cfg) + cfg["layers"] * 4 * filled * d
    return ops + (2 * d * cfg["vocab"] if picks else 0)


def gpt_prompt_flops(cfg, n):
    """A prompt of n tokens: position i attends over i + 1; one pick at the end."""
    d = cfg["d_model"]
    return (n * 2 * gpt_block_params(cfg) + cfg["layers"] * 4 * d * n * (n + 1) // 2
            + 2 * d * cfg["vocab"])


def gpt_decode_weight_bytes(cfg):
    """What one decode step must read whatever the batch: the blocks and the
    output head, once, in bf16."""
    return (gpt_block_params(cfg) + cfg["d_model"] * cfg["vocab"]) * BF16


def gpt_kv_bytes(cfg, filled):
    """K and V of ``filled`` positions over all layers, bf16."""
    return 2 * filled * cfg["d_model"] * cfg["layers"] * BF16


def gpt_decode_steps(dispatches, completed, chunk, max_new):
    """Token steps behind ``dispatches`` decode dispatches: each scans
    ``chunk`` steps, but a stream's last scan is cut to what it has left
    ((max_new - 1) mod chunk; token 1 is the prefill's), and cuts the scan of
    every slot with it.  One cut scan per completed stream is the most there
    can be, so this never counts more steps than ran."""
    tail = (max_new - 1) % chunk
    cut = (chunk - tail) if tail else 0
    return max(0, dispatches * chunk - completed * cut)


def window_flops(cfg, units):
    """Operations behind what reached the users in a span of the run."""
    if cfg["family"] == "vit":
        return units["frames"] * vit_forward_flops(cfg)
    if cfg["family"] == "gpt2":
        d = cfg["d_model"]
        per_token = 2 * gpt_block_params(cfg) + 2 * d * cfg["vocab"]
        return (sum(gpt_prompt_flops(cfg, n) for n in units["prompts"])
                + units["decode_tokens"] * per_token
                + cfg["layers"] * 4 * d * units["filled"])
    raise ValueError(f"no work function for family {cfg['family']!r}")


def kernel_work(kind, cfg, units):
    """(operations, bytes) the steps of one kernel family needed."""
    if kind == "vit_step":
        # weights once per batch, each uint8 frame in, one (index, score) pair out
        nbytes = (units["invokes"] * vit_params(cfg) * BF16
                  + units["frames"] * (cfg["size"] ** 2 * 3 + 8))
        return units["frames"] * vit_forward_flops(cfg), nbytes
    if kind == "gpt_decode":
        d = cfg["d_model"]
        flops = (units["decode_tokens"] * (2 * gpt_block_params(cfg) + 2 * d * cfg["vocab"])
                 + cfg["layers"] * 4 * d * units["filled"])
        nbytes = (units["steps"] * gpt_decode_weight_bytes(cfg)
                  + gpt_kv_bytes(cfg, units["filled"]))
        return flops, nbytes
    raise ValueError(f"no kernel work function {kind!r}")


def least_seconds(flops, nbytes, peaks):
    """The roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it is."""
    tf, tb = flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
