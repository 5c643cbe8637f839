"""The ``lfm2moe_pp2_rag_closed32`` cell at the tiny sizes of its own
``rehearsal`` block, on the CPU, and the arithmetic of its work functions.

As ``test_benchmark_cmda.py`` does for its cell: the result's keys, the
control (the reference in fp8 in the program's place comes out not correct
under the same limits), the timed path broken underneath (a token altered
where the engine emits it comes out not correct), the reference's weights
against the program's, the configuration against the published numbers, the
work counts against a count by hand.  No wall-clock time is asserted.
"""

import json

import numpy as np
import pytest

from benchmark import harness, work_lfm2_moe as work
from benchmark.run import run_cell

CELL = "lfm2moe_pp2_rag_closed32"


def rehearse(trace=False, control=None, seed=4000000007):
    lines = []
    cell = harness.load_cell(CELL).rehearsal()
    result, diag = run_cell(cell, seed, 3.0, trace, control, need_tpu=False, out=lines.append)
    return cell, result, diag, [json.loads(x) for x in lines]


@pytest.fixture(scope="module")
def untraced():
    return rehearse(control="fp8")


def test_the_rehearsal_reads_correct_with_the_contracts_keys(untraced):
    _, result, _, _ = untraced
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert set(result["metrics"]) == {"gen_gap_p95_ms", "setup_s"}
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    json.loads(json.dumps(result))


def test_the_control_in_the_programs_place_is_not_correct(untraced):
    _, result, diag, _ = untraced
    ctl = diag["control"]
    assert ctl["precision"] == "fp8" and ctl["correct_in_programs_place"] is False
    assert any(v > result["compared"][k]["limit"] for k, v in ctl["readings"].items())


def test_a_traced_run_reports_the_counter_metrics_and_no_device_metric():
    cell, result, diag, _ = rehearse(trace=True)
    assert result["correct"] is True
    names = set(result["metrics"])
    assert names <= {m["name"] for m in cell.per_layer}
    # the cell reports the accepted metrics of the layers it runs under their own names
    assert {"experts_touched_pct.lfm2", "kv_read_pct.gen", "moe_max_load.nemo",
            "tokens_per_step.gen", "prefill_chunks_per_req.gen"} <= names
    # no chip: no peak and no device plane, so the work readers return nothing
    assert not any("roofline" in k or "idle" in k or "mfu" in k for k in names)
    # off the TPU every step reads every row the leaves hold
    assert result["metrics"]["kv_read_pct.gen"]["value"] == 100.0
    c0, c1 = diag["counters"]["c0"], diag["counters"]["c1"]
    # every expert is held: every choice of the window was local, 2 a token and expert layer
    assert c1["gen_moe_local"] - c0["gen_moe_local"] > 0
    assert c1["gen_kv_rows_need"] - c0["gen_kv_rows_need"] < (
        c1["gen_kv_rows_held"] - c0["gen_kv_rows_held"])
    # prompts of 33 and 37 tokens in chunks of 16: a conv window crosses chunk boundaries
    assert cell.workload["prefill_chunk"] == 16 and max(cell.traffic["prompt_lens"]) == 37


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from nnstreamer_tpu.core import slots

    real = slots.SlotEngine._emit_frame

    def altered(self, s, toks, final, extra_meta=None):
        if toks is not None and toks.shape[1]:
            toks = np.array(toks)
            toks[0, 0] = (toks[0, 0] + 1) % 97
        return real(self, s, toks, final, extra_meta)

    monkeypatch.setattr(slots.SlotEngine, "_emit_frame", altered)
    _, result, _, _ = rehearse()
    assert result["correct"] is False
    gap = result["compared"]["token_gap_max"]
    assert gap["value"] > gap["limit"]


def test_the_reference_makes_the_programs_weights_without_the_program():
    import jax

    from benchmark.configs import ref_lfm2_moe as ref
    from nnstreamer_tpu.models import hybrid_lm

    cell = harness.load_cell(CELL).rehearsal()
    cfg = cell.config
    custom = cell.workload["custom"].format(seed=12345, **cfg)
    props = dict(part.split(":", 1) for part in custom.split(","))
    params = hybrid_lm.init_params(hybrid_lm.cfg_from_props(props), 12345)

    def flat(tree):
        return {jax.tree_util.keystr(k): np.asarray(v)
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}

    assert ref.pattern(cfg) == cfg["pattern"] == "CDCD*ECECECE*ECECECE*ECE"
    for i in range(len(cfg["pattern"])):
        mine, theirs = flat(ref.part(cfg, 12345, i)), flat(params["blocks"][i])
        assert mine.keys() == theirs.keys()
        for k, a in mine.items():
            b = theirs[k]
            if "experts" in k:      # the program stores an expert's width padded with zeros
                b = b[:, :a.shape[1]] if "down" in k else b[:, :, :a.shape[2]]
            assert np.array_equal(a, b), (i, k)
    assert set(params) == {"embed", "blocks", "norm_f"}
    for name in ("embed", "norm_f"):
        mine, theirs = flat(ref.part(cfg, 12345, name)), flat(params[name])
        assert all(np.array_equal(mine[k], theirs[k]) for k in mine)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import os

    path = os.path.join(harness.HERE, "configs", "ref_lfm2_moe.py")
    tree = ast.parse(open(path).read())
    names = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not any("nnstreamer" in n for n in names), names


def test_the_configuration_keeps_every_published_number_but_the_reduced():
    from benchmark.configs import ref_lfm2_moe as ref

    cell = harness.load_cell(CELL)
    cfg = cell.config
    published = {**cfg, **{k: v for k, v in cfg["published"].items() if k in cfg["reduced"]}}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    period = ["conv", "conv", "full_attention", "conv"]
    assert cfg["layer_types"] == period * 3 and cfg["num_hidden_layers"] == 12
    assert published["layer_types"] == period * 5 + ["conv", "full_attention", "conv", "conv"]
    assert len(published["layer_types"]) == published["num_hidden_layers"] == 24
    # the dialect's pattern says what layer_types and num_dense_layers say, and the cut
    # is the prefix of the model, letter for letter
    assert cfg["pattern"] == ref.pattern(cfg) == ref.pattern(published)[:24]
    assert cfg["d_model"] == cfg["hidden_size"] and cfg["vocab"] == cfg["vocab_size"]
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_experts"], cfg["num_experts_per_tok"], cfg["num_dense_layers"],
            cfg["conv_L_cache"], cfg["vocab_size"], cfg["rope_theta"]) == (
        2048, 7168, 1792, 64, 32, 8, 32, 4, 2, 3, 65536, 1000000)
    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"] and not cfg["conv_bias"]
    assert cfg["routed_scaling_factor"] == 1 and cfg["norm_eps"] == 1e-5
    # the parameter counts of the issue's arithmetic, from the shapes alone
    assert work.conv_params(cfg) == 16_783_360 and work.attn_params(cfg) == 10_485_888
    assert work.dense_params(cfg) == 44_040_192 and work.expert_params(cfg) == 11_010_048
    assert work.params(cfg) == 3_928_728_256
    assert round(work.params(published) / 1e6, 1) == 8339.9        # the published "8.3B"
    assert round(work.active_params(published) / 1e9, 2) == 1.56   # "A1.5B"
    assert work.expert_params(cfg) * work.BF16 == 22_020_096
    # the traffic the issue names: the ladder, the callers, the output length, the ramp
    assert cell.traffic["prompt_lens"] == [1024, 2048, 1024, 4096] * 8
    assert (cell.traffic["callers"], cell.traffic["ramp_s"], cell.traffic["warm_prompt_lens"]) == (
        32, 20, [1024, 3072])
    w = cell.workload
    assert (w["slots"], w["max_new"], w["chunk"], w["prefill_chunk"], cfg["seq"]) == (
        32, 512, 8, 1024, 8192)
    assert (w["trace_delay_s"], w["trace_s"], w["compare"]["sample"]) == (5, 2, 6)


def test_work_counts_by_hand_at_the_rehearsals_sizes():
    """d 64, dense width 96, expert width 32, 4 heads of 16 on 2 KV heads, 8
    experts top-2, 3 taps, vocabulary 97; the cell's own 12 layers (9 conv, 3
    attention; two leading dense layers, then 10 expert layers)."""
    cfg = harness.load_cell(CELL).rehearsal().config
    conv = 64 * 192 + 64 * 64 + 3 * 64
    attn = 64 * (64 + 2 * 32) + 64 * 64 + 2 * 16          # q, k, v, o and the QK norms
    dense, expert, router = 3 * 64 * 96, 3 * 64 * 32, 64 * 8 + 8
    other = 9 * conv + 3 * attn + 12 * 2 * 64 + 2 * dense + 10 * router
    assert (work.conv_params(cfg), work.attn_params(cfg)) == (conv, attn)
    assert (work.dense_params(cfg), work.expert_params(cfg)) == (dense, expert)
    assert work.other_params(cfg) == other
    assert work.params(cfg) == other + 10 * 8 * expert + 97 * 64 + 64
    assert work.active_params(cfg) == other + 10 * 2 * expert + 97 * 64 + 64
    assert work.step_weight_bytes(cfg) == 2 * (other + 64 * 97 + 64)
    per_conv = 2 * 4 * 64 * 64 + 2 * 3 * 64 + 2 * 64      # projections, taps, two gates
    per_attn = 2 * (64 * (64 + 2 * 32) + 64 * 64)
    assert work.token_flops(cfg) == 9 * per_conv + 3 * per_attn + 2 * 2 * dense + 10 * 2 * 64 * 8
    assert work.prompt_keys(cfg, 20) == 3 * 20 * 21 // 2 and work.prompt_keys(cfg, 5) == 45
    assert work.kv_row_bytes(cfg) == 2 * 32 * 2 and work.conv_state_bytes(cfg) == 2 * 64 * 2
    units = {"prompts": [20, 5], "decode_tokens": 100, "filled": 9_999, "steps": 30,
             "gen_moe_local": 700, "gen_moe_expert_reads": 300, "gen_moe_prefill_local": 60,
             "gen_moe_prefill_reads": 40, "gen_kv_rows_need": 5_000,
             "gen_kv_prefill_rows_need": 800, "gen_prefill_chunks": 3,
             "gen_prefill_tokens": 25, "gen_first_tokens": 2}
    head = 2 * 64 * 97
    keys = work.prompt_keys(cfg, 20) + work.prompt_keys(cfg, 5) + 5_000 + 100 * 3
    assert work.window_flops(cfg, units) == (
        125 * work.token_flops(cfg) + 700 * 2 * expert + 4 * 64 * keys + 102 * head)
    flops, nbytes = work.kernel_work("lfm2_moe_decode", cfg, units)
    assert flops == (100 * work.token_flops(cfg) + 640 * 2 * expert
                     + 4 * 64 * (5_000 + 300) + 100 * head)
    assert nbytes == (30 * work.step_weight_bytes(cfg) + 260 * expert * 2 + 5_300 * 128
                      + 100 * 9 * 256)
    # rows by position, never by what a kernel copied: `filled` is not read
    assert work.kernel_work("lfm2_moe_decode", cfg, dict(units, filled=1)) == (flops, nbytes)
    flops, nbytes = work.kernel_work("lfm2_moe_prefill", cfg, units)
    assert flops == 25 * work.token_flops(cfg) + 60 * 2 * expert + 4 * 64 * 800 + 2 * head
    assert nbytes == (3 * (work.step_weight_bytes(cfg) + 9 * 256) + 40 * expert * 2
                      + 25 * 3 * 128)
    assert work.kernel_work("touched_experts_ffn", cfg, units) == (
        700 * 2 * expert, 300 * expert * 2)
    assert work.kernel_work("decode_attention", cfg, units) == (4 * 64 * 5_300, 5_000 * 128)
    assert work.kernel_work("prefill_attention", cfg, units) == (4 * 64 * 800, 0)
    assert work.kernel_work("short_conv", cfg, units) == (
        100 * 9 * (2 * 3 * 64 + 2 * 64), 100 * 9 * 256)
    with pytest.raises(ValueError):
        work.kernel_work("cohere2_moe_decode", cfg, units)


def test_the_counted_readers_read_nothing_from_a_program_without_the_counters():
    """The parent commit cannot run the cell; a program without the ``gen_kv_*``
    counters or of another name gives the readers nothing and they do not
    raise; with them, the shares follow the work and stay under 100."""
    from benchmark.readers import counter_ratio, program_roofline_counted, window_mfu_counted

    cell = harness.load_cell(CELL)
    metrics = {m["name"]: m for m in cell.per_layer}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    class Session:
        def work_units(self, ta, tb, c0, c1):
            return {"prompts": [2048] * 150, "decode_tokens": 76_000, "filled": 1, "steps": 2_600}

        def facts(self):
            return {}

    class Ctx:
        def __init__(self, c0, c1):
            self.c0, self.c1, self.peaks, self.cell, self.notes = c0, c1, peaks, cell, {}
            self.session, self.facts = Session(), {}
            self.reduced = {"programs": {
                "jit_nns_lfm2_moe_decode(1)": {"runs": 12, "s": 1.1, "has_while": True},
                "jit_nns_lfm2_moe_prefill(2)": {"runs": 12, "s": 0.75, "has_while": True}}}

        def span(self, which):
            return self.c0, self.c1, 10.0, 61.0

    old = {"gen_decode_steps": 4, "gen_prefill_chunks": 3, "gen_moe_local": 5,
           "gen_moe_expert_reads": 5, "gen_moe_prefill_local": 1, "gen_moe_prefill_reads": 1}
    later = {k: v + 9 for k, v in old.items()}
    for reader, name in ((window_mfu_counted, "mfu_pct.lfm2"),
                         (program_roofline_counted, "decode_roofline.lfm2"),
                         (program_roofline_counted, "prefill_roofline.lfm2"),
                         (counter_ratio, "kv_read_pct.gen")):
        assert reader.read(metrics[name], Ctx(old, later)) is None, name
    c0 = {"gen_decode_steps": 0, **dict.fromkeys(work.COUNTERS, 0), "gen_moe_layer_steps": 0,
          "gen_kv_rows_read": 0, "gen_kv_rows_held": 0}
    c1 = {"gen_decode_steps": 325, "gen_moe_local": 4_300_000, "gen_moe_expert_reads": 860_000,
          "gen_moe_prefill_local": 1_230_000, "gen_moe_prefill_reads": 96_000,
          "gen_kv_rows_need": 525_000_000, "gen_kv_prefill_rows_need": 1_400_000_000,
          "gen_prefill_chunks": 300, "gen_prefill_tokens": 307_200, "gen_first_tokens": 150,
          "gen_moe_layer_steps": 29_000, "gen_kv_rows_read": 560_000_000,
          "gen_kv_rows_held": 2_040_000_000}
    ctx = Ctx(c0, c1)
    for name in ("decode_roofline.lfm2", "prefill_roofline.lfm2"):
        share = program_roofline_counted.read(metrics[name], ctx)
        note = ctx.notes[name]
        assert share == pytest.approx(100 * note["least_s"] / note["device_s"])
        assert 0 < share < 100, (name, share)
    assert ctx.notes["decode_roofline.lfm2"]["bound"] == "memory"
    assert 0 < window_mfu_counted.read(metrics["mfu_pct.lfm2"], ctx) < 100
    assert counter_ratio.read(metrics["experts_touched_pct.lfm2"], ctx) == pytest.approx(
        860_000 / 29_000 * 3.125)
