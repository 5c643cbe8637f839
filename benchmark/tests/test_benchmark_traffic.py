"""The traffic generators: the seed chooses contents, never the amount of work."""

import itertools

import numpy as np

from benchmark import harness
from benchmark.drivers import closed_loop_generate, flood_stream

MIX = harness.load_json(harness.HERE + "/traffic/chat_ladder16.json")
LADDER = [16, 24, 32, 48, 64, 64, 96, 96, 128, 128, 192, 192, 256, 384, 512, 768]


def test_the_ladder_is_the_issues_and_fits_the_model():
    assert MIX["prompt_lens"] == LADDER and MIX["callers"] == 16
    assert sum(LADDER) / 16 == 187.5
    assert max(LADDER) + 64 <= 1024
    # the warm requests touch every prefill program the ladder needs (chunk 32)
    tails = {n % 32 or 32 for n in LADDER} | {32}
    assert tails == {n % 32 or 32 for n in MIX["warm_prompt_lens"]}


def test_two_seeds_offer_the_same_lengths_in_the_same_order_to_every_caller():
    for caller in range(16):
        a = list(itertools.islice(closed_loop_generate.prompts(MIX, 50257, 3, caller), 40))
        b = list(itertools.islice(
            closed_loop_generate.prompts(MIX, 50257, 4000000007, caller), 40))
        lens = [p.shape[1] for p in a]
        assert lens == [p.shape[1] for p in b]
        assert lens == [LADDER[(caller + k) % 16] for k in range(40)]
        assert all(p.dtype == np.int32 and p.shape[0] == 1 for p in a)
        assert not any(np.array_equal(x, y) for x, y in zip(a, b))
        assert all(0 <= p.min() and p.max() < 50257 for p in a + b)


def test_callers_share_no_tokens_and_a_seed_repeats_itself():
    a = next(closed_loop_generate.prompts(MIX, 50257, 9, 5))
    b = next(closed_loop_generate.prompts(MIX, 50257, 9, 5))
    c = next(closed_loop_generate.prompts(MIX, 50257, 9, 6))
    assert np.array_equal(a, b)
    assert a.shape != c.shape or not np.array_equal(a, c)


def test_flood_frames_are_uint8_of_one_size_whatever_the_seed():
    a = flood_stream.make_frames(1, 8, 32)
    b = flood_stream.make_frames(4000000007, 8, 32)
    assert a.shape == b.shape == (8, 32, 32, 3) and a.dtype == b.dtype == np.uint8
    assert not np.array_equal(a, b)
    assert np.array_equal(a, flood_stream.make_frames(1, 8, 32))
    assert len({fr.tobytes() for fr in a}) == 8


def test_a_shared_head_is_a_parameter_of_the_same_generator():
    mix = {**MIX, "shared_head": 8}
    a = list(itertools.islice(closed_loop_generate.prompts(mix, 50257, 3, 0), 3))
    b = next(closed_loop_generate.prompts(mix, 50257, 3, 7))
    plain = list(itertools.islice(closed_loop_generate.prompts(MIX, 50257, 3, 0), 3))
    assert [p.shape[1] for p in a] == [8 + LADDER[k] for k in range(3)]
    assert all(np.array_equal(p[:, :8], b[:, :8]) for p in a)       # one head for all
    assert all(np.array_equal(p[:, 8:], q) for p, q in zip(a, plain))  # own tokens unchanged
