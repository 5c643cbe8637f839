"""The per-layer metrics that read the program's spans and wait counters: a
traced CPU rehearsal of each cell reports every one of its cell with a finite
value, and a program that has no spans makes the readers return nothing."""

import math

import pytest

from benchmark import harness
from benchmark.readers import counter_ratio, span_stat
from benchmark.tests.test_benchmark_rehearsal import rehearse

NEW = {
    "vit_l16_384_flood": ["bucket_fill_pct.stream", "stage_ms_per_batch.stream",
                          "stage_exposed_pct.stream", "window_wait_pct.stream",
                          "emit_ms_per_batch.stream"],
    "gpt2l_chat_closed16": ["lane_wait_ms_per_req.gen", "admit_wait_ms_per_req.gen",
                            "pump_host_ms_per_step.gen", "decode_sync_ms_per_step.gen"],
}


@pytest.fixture(scope="module", params=sorted(NEW))
def traced(request):
    return (request.param,) + rehearse(request.param, trace=True)


def test_a_traced_rehearsal_reports_every_new_metric_of_its_cell(traced):
    name, cell, result, diag, _ = traced
    listed = {m["name"] for m in cell.per_layer}
    for metric in NEW[name]:
        assert metric in listed
        value = result["metrics"][metric]["value"]
        assert math.isfinite(value) and value >= 0, metric
    for metric in NEW[name]:
        if metric.endswith("_pct.stream"):
            assert result["metrics"][metric]["value"] <= 100.0
    # the breakdown by span name rides in the diagnostics line
    table = diag["notes"]["spans"]
    assert all(row["n"] > 0 and row["self_s"] <= row["s"] + 1e-9 for row in table.values())
    assert any(k.startswith("nns.") for k in table)


def test_the_stream_cell_sees_its_batches_from_staging_to_the_sink(traced):
    name, _, result, diag, _ = traced
    if name != "vit_l16_384_flood":
        pytest.skip("the stream cell's spans")
    table = diag["notes"]["spans"]
    for span in ("nns.appsrc.push", "nns.feed.stage", "nns.filter.batch", "nns.filter.invoke",
                 "nns.decoder.batch", "nns.decoder.labels", "nns.sink.render"):
        assert span in table, span
    assert table["nns.filter.invoke"]["n"] >= table["nns.filter.batch"]["n"]
    # padding shows where the counters' own fill cannot see it
    assert result["metrics"]["bucket_fill_pct.stream"]["value"] <= 100.0


def test_the_generation_cell_names_every_phase_of_the_pump(traced):
    name, _, _, diag, _ = traced
    if name != "gpt2l_chat_closed16":
        pytest.skip("the generation cell's spans")
    table = diag["notes"]["spans"]
    for span in ("nns.query.route", "nns.slots.turn", "nns.slots.admit", "nns.slots.reset",
                 "nns.slots.prefill", "nns.slots.decode", "nns.slots.decode.dispatch",
                 "nns.slots.decode.sync", "nns.slots.emit", "nns.gen.admit_wait",
                 "nns.gen.lane_wait"):
        assert span in table, span
    c0, c1 = diag["counters"]["c0"], diag["counters"]["c1"]
    for key in ("gen_admit_wait_s", "gen_lane_wait_s", "gen_pump_host_s", "gen_first_tokens"):
        assert c1[key] >= c0[key] >= 0


class _Ctx:
    """What a reader sees of a run of a program that predates the spans."""

    def __init__(self, counters):
        self.c = counters
        self.notes, self.facts = {}, {}

    def span(self, which):
        return self.c, self.c, 1.0, 3.0


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_program_without_spans_or_counters_reads_as_nothing(cell, monkeypatch):
    import nnstreamer_tpu.core.tracer as tracer

    monkeypatch.delattr(tracer, "spans_between")          # the parent commit
    ctx = _Ctx({"gen_joins": 3, "gen_decode_steps": 9, "invokes": 4})
    metrics = {m["name"]: m for m in harness.load_cell(cell).per_layer}
    for name in NEW[cell]:
        reader = {"span_stat": span_stat, "counter_ratio": counter_ratio}[metrics[name]["reader"]]
        assert reader.read(metrics[name], ctx) is None, name


def test_a_wait_that_never_happened_reads_zero_once_its_path_ran(monkeypatch):
    import nnstreamer_tpu.core.tracer as tracer

    rec = tracer.SpanRecord
    ring = [rec("nns.feed.stage", 1.2, 1.4, "lane", None, None, {"seq": 1})]
    monkeypatch.setattr(tracer, "spans_between", lambda ta, tb: list(ring))
    metrics = {m["name"]: m for m in harness.load_cell("vit_l16_384_flood").per_layer}
    ctx = _Ctx({})
    assert span_stat.read(metrics["stage_exposed_pct.stream"], ctx) == 0.0
    assert span_stat.read(metrics["stage_ms_per_batch.stream"], ctx) == pytest.approx(200.0)
    assert span_stat.read(metrics["window_wait_pct.stream"], ctx) is None   # no batch ran
    ring.append(rec("nns.filter.batch", 1.5, 1.6, "f", None, None, {"seq": 1}))
    ring.append(rec("nns.pipeline.push_wait", 1.6, 2.6, None, None, None, {"element": "f"}))
    ring.append(rec("nns.pipeline.push_wait", 1.6, 2.6, None, None, None, {"element": "src"}))
    assert span_stat.read(metrics["window_wait_pct.stream"], ctx) == pytest.approx(50.0)
