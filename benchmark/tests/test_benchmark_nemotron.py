"""The ``nemotron3n_ep2_chat_closed32`` cell at the tiny sizes of its own
``rehearsal`` block, on the CPU, and the arithmetic of its work functions.

As ``test_benchmark_rehearsal.py`` does for the cells the benchmark had: the
result's keys, the control (the reference in fp8 in the program's place comes
out not correct under the same limits), the timed path broken underneath (a
token altered where the engine emits it comes out not correct), the
reference's weights against the program's.  No wall-clock time is asserted.
"""

import json

import numpy as np
import pytest

from benchmark import harness, work_nemotron_h as work
from benchmark.run import run_cell

CELL = "nemotron3n_ep2_chat_closed32"


def rehearse(trace=False, control=None, seed=4000000007):
    lines = []
    cell = harness.load_cell(CELL).rehearsal()
    result, diag = run_cell(cell, seed, 3.0, trace, control, need_tpu=False, out=lines.append)
    return cell, result, diag, [json.loads(x) for x in lines]


@pytest.fixture(scope="module")
def untraced():
    return rehearse(control="fp8")


def test_the_rehearsal_reads_correct_with_the_contracts_keys(untraced):
    cell, result, _, _ = untraced
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
    assert {"experts_touched_pct.nemo", "moe_max_load.nemo", "tokens_per_step.gen"} <= names
    # no chip: no peak and no device plane, so the work readers return nothing
    assert not any("roofline" in k or "idle" in k or "mfu" in k for k in names)
    c0, c1 = diag["counters"]["c0"], diag["counters"]["c1"]
    assert c1["gen_moe_layer_steps"] > c0["gen_moe_layer_steps"]
    assert c1["gen_moe_prefill_reads"] <= c1["gen_moe_expert_reads"]


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

    from benchmark.configs import ref_nemotron_h as ref
    from nnstreamer_tpu.models import hybrid_lm

    cell = harness.load_cell(CELL).rehearsal()
    cfg = cell.config
    custom = cell.workload["custom"].format(seed=12345, **cfg)
    props = dict(part.split(":", 1) for part in custom.split(","))
    params = hybrid_lm.init_params(hybrid_lm.cfg_from_props(props), 12345)

    def flat(tree):
        return {jax.tree_util.keystr(k): np.asarray(v)
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}

    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        mine, theirs = flat(ref.part(cfg, 12345, i)), flat(params["blocks"][i])
        assert mine.keys() == theirs.keys()
        for k, a in mine.items():
            b = theirs[k]
            if "experts" in k:      # the program stores an expert's width padded with zeros
                b = b[:, :, :a.shape[2]] if "up" in k else b[:, :a.shape[1]]
            assert np.array_equal(a, b), (i, kind, k)
    for name in ("embed", "norm_f", "lm_head"):
        mine, theirs = flat(ref.part(cfg, 12345, name)), flat(params[name])
        assert all(np.array_equal(mine[k], theirs[k]) for k in mine)


def test_the_configuration_keeps_every_published_number_but_the_reduced():
    cfg = harness.load_cell(CELL).config
    published = {**cfg, **{k: v for k, v in cfg["published"].items() if k in cfg["reduced"]}}
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"] == 16
    assert published["hybrid_override_pattern"].startswith(cfg["hybrid_override_pattern"])
    assert len(published["hybrid_override_pattern"]) == published["num_hidden_layers"] == 52
    assert cfg["d_model"] == cfg["hidden_size"] and cfg["vocab"] == cfg["vocab_size"]
    assert cfg["router_experts"] == published["n_routed_experts"] == 128
    # the parameter counts of the issue's table, from the shapes alone
    whole = {**published, "router_experts": 128}
    assert round(work.params(whole) / 1e9, 1) == 31.6
    assert round(work.params(cfg) / 1e9, 2) == 5.28
    assert work.expert_params(cfg) * work.BF16 == 19_955_712


def test_work_counts_follow_the_counters_and_not_the_routing():
    cfg = harness.load_cell(CELL).config
    units = {"prompts": [32, 256], "decode_tokens": 1000, "filled": 150_000, "steps": 40,
             "gen_moe_local": 30_000, "gen_moe_expert_reads": 14_000,
             "gen_moe_prefill_local": 6_000, "gen_moe_prefill_reads": 900}
    flops, nbytes = work.kernel_work("hybrid_decode", cfg, units)
    more = dict(units, gen_moe_expert_reads=14_001)
    assert work.kernel_work("hybrid_decode", cfg, more)[1] - nbytes == 19_955_712
    more = dict(units, gen_moe_local=30_001)
    assert work.kernel_work("hybrid_decode", cfg, more)[0] - flops == 2 * work.expert_params(cfg)
    # the prefill chunks' reads are no work of the decode program
    less = dict(units, gen_moe_prefill_reads=901)
    assert nbytes - work.kernel_work("hybrid_decode", cfg, less)[1] == 19_955_712
    assert work.window_flops(cfg, units) > flops
    # the kernel's own work: every local choice, every expert read, prefill's too
    assert work.kernel_work("touched_experts_ffn", cfg, units) == (
        30_000 * 2 * work.expert_params(cfg), 14_000 * 19_955_712)
    with pytest.raises(ValueError):
        work.kernel_work("gpt_decode", cfg, units)


def test_the_counted_readers_read_nothing_from_a_program_without_the_counters():
    """The parent commit has no ``gen_moe_*`` counter: the readers return
    nothing and do not raise; with them, the share follows the work."""
    from benchmark.readers import program_roofline_counted, window_mfu_counted

    cell = harness.load_cell(CELL)
    metrics = {m["name"]: m for m in cell.per_layer}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    class Session:
        def work_units(self, ta, tb, c0, c1):
            return {"prompts": [64], "decode_tokens": 800, "filled": 90_000, "steps": 32}

    class Ctx:
        def __init__(self, c0, c1):
            self.c0, self.c1, self.peaks, self.cell, self.notes = c0, c1, peaks, cell, {}
            self.session = Session()
            self.reduced = {"programs": {"jit_nns_hybrid_decode(1)": {
                "runs": 2, "s": 0.8, "has_while": True}}}

        def span(self, which):
            return self.c0, self.c1, 10.0, 61.0

    old = {"gen_decode_steps": 4, "gen_joins": 3}
    for reader, name in ((window_mfu_counted, "mfu_pct.nemo"),
                         (program_roofline_counted, "decode_roofline.nemo")):
        assert reader.read(metrics[name], Ctx(old, {**old, "gen_decode_steps": 8})) is None
    c0 = {"gen_decode_steps": 4, **dict.fromkeys(work.COUNTERS, 0)}
    c1 = {"gen_decode_steps": 8, "gen_moe_local": 9000, "gen_moe_expert_reads": 5000,
          "gen_moe_prefill_local": 1000, "gen_moe_prefill_reads": 400}
    ctx = Ctx(c0, c1)
    share = program_roofline_counted.read(metrics["decode_roofline.nemo"], ctx)
    note = ctx.notes["decode_roofline.nemo"]
    assert note["bound"] == "memory" and note["runs_in_window"] == 4
    assert share == pytest.approx(100 * note["least_s"] / 0.8) and 0 < share < 100
    assert 0 < window_mfu_counted.read(metrics["mfu_pct.nemo"], ctx) < 1
