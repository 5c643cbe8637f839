"""Each cell end to end at the tiny sizes of its own ``rehearsal`` block, on
the CPU: the result's keys, the control (the reference in fp8 in the
program's place comes out not correct), and the timed path broken underneath
(an answer or a token altered where it is produced comes out not correct).

The look for a chip is skipped (``need_tpu=False``); everything else is the
code a chip run executes.  No wall-clock time is asserted.
"""

import json

import numpy as np
import pytest

from benchmark import harness
from benchmark.run import run_cell

CELLS = ["vit_l16_384_flood", "gpt2l_chat_closed16"]


def rehearse(name, trace=False, control=None, seed=4000000007):
    lines = []
    cell = harness.load_cell(name).rehearsal()
    result, diag = run_cell(cell, seed, 1.5, trace, control, need_tpu=False, out=lines.append)
    return cell, result, diag, [json.loads(x) for x in lines]


@pytest.fixture(scope="module", params=CELLS)
def untraced(request):
    return rehearse(request.param, control="fp8")


def test_the_last_line_has_exactly_the_contracts_keys(untraced):
    cell, result, _, lines = untraced
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    json.loads(json.dumps(result))                       # no NaN, no Infinity
    phases = lines[0]["setup_phases"]
    assert phases[0]["phase"] == "imports and device init"
    assert sum(p["s"] for p in phases) == pytest.approx(lines[0]["setup_s"], rel=0.05)


def test_the_timed_path_is_correct_and_every_number_has_its_limit(untraced):
    _, result, _, _ = untraced
    assert result["correct"] is True
    for row in result["compared"].values():
        assert set(row) == {"value", "limit"} and row["value"] <= row["limit"]


def test_the_control_in_the_programs_place_is_not_correct(untraced):
    _, result, diag, _ = untraced
    ctl = diag["control"]
    assert ctl["precision"] == "fp8" and ctl["correct_in_programs_place"] is False
    # and it fails by a number's limit, not by a crash
    assert any(v > result["compared"][k]["limit"] for k, v in ctl["readings"].items())


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reports_per_layer_metrics_by_their_own_names(name):
    cell, result, diag, _ = rehearse(name, trace=True)
    assert result["correct"] is True
    assert set(result["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "setup_s" not in result["metrics"] and result["metrics"]
    # no chip: no device plane in the trace, so the trace's readers return nothing
    assert not any("roofline" in k or "idle" in k for k in result["metrics"])
    assert diag["trace"]["c1"] != diag["trace"]["c0"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from nnstreamer_tpu.ops import labeling

    real = labeling.top1

    def off_by_one(scores, **kw):
        idx, score = real(scores, **kw)
        return (idx + 1) % scores.shape[-1], score

    monkeypatch.setattr(labeling, "top1", off_by_one)
    _, result, _, _ = rehearse("vit_l16_384_flood")
    assert result["correct"] is False
    assert result["compared"]["score_err_max"]["value"] > result["compared"]["score_err_max"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from nnstreamer_tpu.core import slots

    real = slots.SlotEngine._emit_frame

    def altered(self, s, toks, final, extra_meta=None):
        if toks is not None and toks.shape[1]:
            toks = np.array(toks)
            toks[0, 0] = (toks[0, 0] + 1) % 97
        return real(self, s, toks, final, extra_meta)

    monkeypatch.setattr(slots.SlotEngine, "_emit_frame", altered)
    _, result, _, _ = rehearse("gpt2l_chat_closed16")
    assert result["correct"] is False
    assert result["compared"]["token_gap_max"]["value"] > result["compared"]["token_gap_max"]["limit"]


def test_the_reference_makes_the_programs_weights_without_the_program():
    """Same seed, same paths, same initializers: bit-equal float32 trees."""
    import jax

    from benchmark.configs import ref_gpt2, ref_vit
    from nnstreamer_tpu.models import build

    def flat(tree):
        return {jax.tree_util.keystr(k): np.asarray(v)
                for k, v in jax.tree_util.tree_leaves_with_path(tree)}

    for name, ref, zoo in (("vit_l16_384_flood", ref_vit, "vit"),
                           ("gpt2l_chat_closed16", ref_gpt2, "transformer")):
        cfg = harness.load_cell(name).rehearsal().config
        props = {k: str(v) for k, v in cfg.items() if isinstance(v, (int, str))}
        _, params, _, _ = build(zoo, {**props, "seed": "12345"})
        mine, theirs = flat(ref.make_params(cfg, 12345)), flat(params["params"])
        assert mine.keys() == theirs.keys()
        assert all(np.array_equal(mine[k], theirs[k]) for k in mine)
