"""``work.py`` against numbers worked by hand from the two configurations."""

import pytest

from benchmark import harness, work

VIT = harness.load_json(harness.HERE + "/configs/vit_l16_384.json")
GPT = harness.load_json(harness.HERE + "/configs/gpt2_large.json")
PEAKS = harness.load_peaks("TPU v5 lite")


def test_vit_l16_384_forward_flops():
    # 577 tokens, d 1024, ff 4096: per layer
    #   qkv 2*577*1024*3072 = 3,630,170,112    out 2*577*1024*1024 = 1,210,056,704
    #   mlp 4*577*1024*4096 = 9,680,453,632    attention 4*577*577*1024 = 1,363,677,184
    layer = 3_630_170_112 + 1_210_056_704 + 9_680_453_632 + 1_363_677_184
    patch = 2 * 576 * 768 * 1024          # 905,969,664
    head = 2 * 1024 * 1000
    assert work.vit_tokens(VIT) == 577
    assert work.vit_forward_flops(VIT) == 24 * layer + patch + head == 382_132_600_832


def test_vit_l16_384_params_and_bytes():
    block = 12 * 1024 * 1024 + 4 * 1024   # projections (no bias) + two LayerNorms
    n = 768 * 1024 + 1024 + 1024 + 577 * 1024 + 24 * block + 2 * 1024 + 1024 * 1000 + 1000
    assert work.vit_params(VIT) == n == 304_494_568
    flops, nbytes = work.kernel_work("vit_step", VIT, {"frames": 128, "invokes": 1})
    assert flops == 128 * 382_132_600_832
    assert nbytes == n * 2 + 128 * (384 * 384 * 3 + 8)
    least, bound = work.least_seconds(flops, nbytes, PEAKS)
    assert bound == "compute" and least == pytest.approx(flops / 197e12)


def test_gpt2_large_params():
    blocks = 36 * 12 * 1280 * 1280        # 707,788,800
    assert work.gpt_block_params(GPT) == blocks
    n = blocks + 73 * 2 * 1280 + 50257 * 1280 + 1024 * 1280 + 1280 * 50257
    assert work.gpt_params(GPT) == n == 837_944_320


def test_gpt2_large_token_and_prompt_flops():
    # one token over 100 filled positions: 2 ops per block weight + attention
    assert work.gpt_token_flops(GPT, 100) == 2 * 707_788_800 + 36 * 4 * 100 * 1280
    assert (work.gpt_token_flops(GPT, 100, picks=True) - work.gpt_token_flops(GPT, 100)
            == 2 * 1280 * 50257)
    # a prompt is its tokens one after another, with one pick at the end
    n = 48
    by_token = sum(work.gpt_token_flops(GPT, i + 1) for i in range(n)) + 2 * 1280 * 50257
    assert work.gpt_prompt_flops(GPT, n) == by_token


def test_gpt2_large_decode_step_is_memory_bound():
    # 16 slots, each over 500 filled positions, one step
    units = {"decode_tokens": 16, "filled": 16 * 500, "steps": 1}
    flops, nbytes = work.kernel_work("gpt_decode", GPT, units)
    weights = (707_788_800 + 1280 * 50257) * 2            # 1,544,235,520 bytes
    kv = 2 * 8000 * 1280 * 36 * 2                         # 1,474,560,000 bytes
    assert nbytes == weights + kv
    assert flops == 16 * (2 * 707_788_800 + 2 * 1280 * 50257) + 36 * 4 * 1280 * 8000
    least, bound = work.least_seconds(flops, nbytes, PEAKS)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)


def test_decode_steps_never_count_more_than_ran():
    # chunk 8, 64 new tokens: 63 decode tokens = 7 scans of 8 and one of 7
    assert work.gpt_decode_steps(8, 1, 8, 64) == 63
    assert work.gpt_decode_steps(10, 0, 8, 64) == 80
    assert work.gpt_decode_steps(10, 3, 8, 65) == 80      # 64 decode tokens: no cut scan
    assert work.gpt_decode_steps(0, 5, 8, 64) == 0


def test_window_flops_by_family():
    assert work.window_flops(VIT, {"frames": 3}) == 3 * 382_132_600_832
    units = {"prompts": [16, 48], "decode_tokens": 10, "filled": 700}
    want = (work.gpt_prompt_flops(GPT, 16) + work.gpt_prompt_flops(GPT, 48)
            + 10 * (2 * 707_788_800 + 2 * 1280 * 50257) + 36 * 4 * 1280 * 700)
    assert work.window_flops(GPT, units) == want
    with pytest.raises(ValueError):
        work.window_flops({"family": "mamba"}, {})


def test_an_unknown_device_kind_fails_by_name():
    with pytest.raises(harness.CellError, match="TPU v9"):
        harness.load_peaks("TPU v9")
    assert PEAKS["bf16_flops_per_s"] == 197e12 and PEAKS["hbm_bytes_per_s"] == 819e9
    assert "source" in PEAKS
