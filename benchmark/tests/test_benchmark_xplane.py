"""The trace reducer, on a small recorded TPU trace and on made-up intervals.

``data/tiny.xplane.pb`` was recorded on a TPU v5 lite by
``benchmark/tests/record_tiny_trace.py``: six runs of one jitted 1024x1024
bf16 program, each under a ``bench:step`` host span, with a ``bench:sleep``
host span of 5 ms between them.
"""

import os

import pytest

from benchmark import xplane

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(xplane.read(TINY))


def test_union_and_gaps_of_made_up_intervals():
    iv = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert xplane.union_ns(iv) == 25
    assert xplane.gaps(iv, 0, 40) == [(15, 20), (30, 40)]
    assert xplane.gaps([], 3, 9) == [(3, 9)]


def test_the_recorded_trace_has_one_chip_and_one_program(reduced):
    assert reduced["chips"] == 1
    assert len(reduced["programs"]) == 1
    (name, row), = reduced["programs"].items()
    assert xplane.program_of(name).startswith("jit_")
    assert row["runs"] == 6 and not row["has_while"]


def test_busy_is_the_union_of_operations_and_idle_is_the_rest(reduced):
    # six runs of about 12 us in a window of tens of ms: nearly all idle
    runs, seconds = xplane.program_runs(reduced, xplane.program_of(next(iter(reduced["programs"]))))
    per_run = seconds / runs
    assert 5e-6 < per_run < 50e-6
    assert 0 < reduced["busy_s"] <= 6 * per_run * 1.01
    assert reduced["busy_s"] / reduced["window_s"] < 0.05


def test_program_runs_finds_by_name_and_returns_nothing_otherwise(reduced):
    name = xplane.program_of(next(iter(reduced["programs"])))
    runs, seconds = xplane.program_runs(reduced, name)
    assert runs == 6 and seconds > 0
    assert xplane.program_runs(reduced, name, has_while=True) is None
    assert xplane.program_runs(reduced, "jit_no_such_program") is None


def test_long_gaps_are_named_by_what_the_host_was_doing(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) <= 10 and gaps == sorted(gaps, key=lambda g: -g[1])
    long_ones = [g for g in gaps if g[1] > 1e-3]
    assert len(long_ones) == 5                      # between six runs
    assert {g[0] for g in long_ones} == {"bench:sleep"}
    assert len(reduced["device_ops"]) <= 10 and reduced["device_ops"][0][1] > 0


def test_a_scan_marks_its_program_and_stays_out_of_the_op_ranking():
    trace = {"host": {}, "devices": {"/device:TPU:0": {
        "modules": [("jit_traced(1)", 0, 100), ("jit_traced(2)", 200, 260)],
        "ops": [("%while.1 = ...", 10, 90), ("%fusion.1", 10, 50), ("%fusion.1", 50, 90),
                ("%fusion.9", 200, 260)]}}}
    r = xplane.reduce(trace)
    assert r["programs"]["jit_traced(1)"]["has_while"]
    assert not r["programs"]["jit_traced(2)"]["has_while"]
    assert xplane.program_runs(r, "jit_traced", has_while=True) == (1, pytest.approx(100e-9))
    assert xplane.program_runs(r, "jit_traced", has_while=False) == (1, pytest.approx(60e-9))
    assert xplane.program_runs(r, "jit_traced") == (2, pytest.approx(160e-9))
    assert r["busy_s"] == pytest.approx(140e-9) and r["window_s"] == pytest.approx(250e-9)
    assert all(not name.startswith("%while") for name, _ in r["device_ops"])
