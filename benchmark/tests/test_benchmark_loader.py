"""Cells, configurations, mixes and metrics are found by name, and names and
units outside the allowed characters are refused."""

import os

import pytest

from benchmark import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_resolves_everything_it_names(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.traffic["kind"] in ("flood_stream", "closed_loop_generate")
    harness.load_module("drivers", cell.traffic["kind"])
    harness.load_module("configs", cell.config["reference"])
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert name in m["workloads"]
        assert hasattr(harness.load_module("readers", m["reader"]), "read")
        assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert any("mfu" in m["name"].replace(".", "_").split("_") for m in cell.per_layer)
    tiny = cell.rehearsal()
    assert tiny.config["d_model"] < cell.config["d_model"] and tiny.name == cell.name


def test_the_benchmark_file_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"] and 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1 and all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    layers = harness.load_json(harness.HERE + "/layers.json")
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["layer"] in layers and m["moves"] in e2e
        assert os.path.exists(os.path.join(harness.HERE, "metrics", m["name"] + ".json"))
    for c in BENCH["configs"]:
        body = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert c["reduced"] == body["reduced"] == []
    gen = harness.load_cell("gpt2l_chat_closed16")
    assert gen.traffic["callers"] == gen.workload["slots"] == 16


def test_unknown_names_are_refused():
    with pytest.raises(harness.CellError, match="no workload"):
        harness.load_cell("no_such_cell")
    with pytest.raises(ModuleNotFoundError):
        harness.load_module("readers", "no_such_reader")


@pytest.mark.parametrize("bad", ["", "a b", "a/b", "a,b", "-lead", "x" * 65, "café", None])
def test_names_outside_the_allowed_characters_are_refused(bad):
    with pytest.raises(harness.CellError):
        harness.check_name(bad)
    with pytest.raises(harness.CellError):
        harness.load_cell(bad)


@pytest.mark.parametrize("good", ["ttft_p95_ms", "mfu.train", "0a", "_x", "a-b", "x" * 64])
def test_names_inside_them_pass(good):
    assert harness.check_name(good) == good


@pytest.mark.parametrize("bad", ["", "tokens per second", "µs", "x" * 17, "a,b", None])
def test_units_outside_the_allowed_characters_are_refused(bad):
    with pytest.raises(harness.CellError):
        harness.check_unit(bad)


@pytest.mark.parametrize("good", ["tokens/s", "%", "ms", "frames/s", "us", "GB/s"])
def test_units_inside_them_pass(good):
    assert harness.check_unit(good) == good


def test_percentile_is_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert harness.percentile(v, 90) == 90 and harness.percentile(v, 95) == 95
    assert harness.percentile([5.0], 90) == 5.0 and harness.percentile([], 90) is None
    assert harness.percentile([1, 2, 3, 1000], 95) == 1000    # the tail is the tail


def test_the_seed_folds_into_what_a_key_takes():
    assert harness.model_seed(7) == 7
    assert 0 <= harness.model_seed(4000000007) < 2**31 - 1
    assert harness.model_seed(2**31 - 1) == 0
