"""Perf-truth layer tests (tools/perf_truth.py + PERF_BASELINE.json).

Everything here is deterministic — tolerance MATH, baseline-file
contracts, trend-report labeling, and the conftest perf-block
contiguity pin.  The timing half (a live fast-subset check against the
committed baseline) lives in tests/test_perf_smoke.py under the perf
marker, inside the load-shielded perf block.
"""

import json
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _load_perf_truth():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import perf_truth
    finally:
        sys.path.pop(0)
    return perf_truth


def _load_bench():
    """One loader for bench.py (repo root is not a package)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_for_truth", str(REPO / "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


REQUIRED_AXES = {
    "fuse_speedup", "dispatch_overlap", "ingest_overlap",
    "pipeline_vs_raw", "slot_multiplex", "crc_bandwidth_mb_s",
}


class TestBaselineContract:
    def test_baseline_committed_with_required_axes(self):
        """Acceptance: PERF_BASELINE.json is committed with >= 6 axes
        covering fuse speedup, dispatch overlap, ingest overlap,
        pipeline_vs_raw, slot multiplex, and CRC bandwidth — each a
        median+MAD distribution from the shared bench harnesses."""
        pt = _load_perf_truth()
        base = pt.load_baseline()
        axes = base["axes"]
        assert REQUIRED_AXES <= set(axes), (
            f"baseline missing axes: {REQUIRED_AXES - set(axes)}")
        assert len(axes) >= 6
        for name, e in axes.items():
            assert e["median"] > 0, name
            assert e["mad"] >= 0, name
            assert len(e["samples"]) == e["k"] >= 2, name
            assert e["unit"], name
            # the committed floor field matches the live tolerance math
            assert e["floor"] == pytest.approx(
                pt.regression_floor(e), abs=1e-3), name
            # every harness is a shared bench.py / bench_wire.py entry
            assert e["harness"].split(".")[0] in ("bench", "bench_wire")

    def test_axis_catalog_matches_baseline(self):
        """Every committed axis still has a live harness (a renamed or
        dropped harness must regenerate the baseline, not silently stop
        being checked)."""
        pt = _load_perf_truth()
        base = pt.load_baseline()
        catalog = pt._axes()
        missing = set(base["axes"]) - set(catalog)
        assert not missing, f"baseline axes without a harness: {missing}"
        fast = {n for n, a in catalog.items() if a.fast}
        assert fast & set(base["axes"]), "no fast axis in the baseline"


class TestToleranceMath:
    def test_self_test_25pct_regression_detectable(self):
        """Acceptance: on the COMMITTED baseline, a value 25% below any
        axis median classifies as a regression and the median itself
        passes — the --self-test contract, pure math, no clocks."""
        pt = _load_perf_truth()
        problems = pt.self_test()
        assert not problems, "\n".join(problems)

    def test_tolerance_clamps(self):
        pt = _load_perf_truth()
        # huge MAD: capped at REL_MAX so 25% drops always trip
        assert pt.tolerance(10.0, 100.0) == pytest.approx(2.0)
        # zero MAD: floored at REL_MIN so jitter alone can't flake
        assert pt.tolerance(10.0, 0.0) == pytest.approx(0.8)
        # in-band MAD: the 4*MAD noise envelope governs
        assert pt.tolerance(10.0, 0.3) == pytest.approx(1.2)

    def test_injected_regression_fails_check(self, monkeypatch):
        """check() with a 30% handicap on a synthetic zero-variance
        baseline reports the regression; without the handicap it
        passes (and early-exits after one run)."""
        pt = _load_perf_truth()
        calls = {"n": 0}

        def fake_measure():
            calls["n"] += 1
            return 100.0

        fake_axis = pt.Axis("fuse_speedup", "bench.fake", "x",
                            True, 3, 3, fake_measure)
        monkeypatch.setattr(pt, "_axes",
                            lambda: {"fuse_speedup": fake_axis})
        monkeypatch.setattr(pt, "_force_cpu", lambda: None)
        baseline = {
            "captured_at": "2026-08-04T00:00:00Z",
            "axes": {"fuse_speedup": {
                "unit": "x", "harness": "bench.fake", "fast": True,
                "k": 3, "samples": [100.0] * 3, "median": 100.0,
                "mad": 0.0,
            }},
        }
        ok = pt.check(baseline=baseline, handicap=1.0, verbose=False)
        assert ok["ok"] and ok["axes"]["fuse_speedup"]["verdict"] == "ok"
        assert len(ok["axes"]["fuse_speedup"]["runs"]) == 1  # early exit
        calls["n"] = 0
        bad = pt.check(baseline=baseline, handicap=0.70, verbose=False)
        assert not bad["ok"]
        assert bad["axes"]["fuse_speedup"]["verdict"] == "regression"
        assert calls["n"] == 3  # all k runs consumed before reporting


class TestTrendReport:
    def test_rows_carry_platform_and_age(self, tmp_path):
        """Every row in the ledger names the platform it was taken on and
        its age; a row without a value is labeled failed."""
        pt = _load_perf_truth()
        fresh = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        (tmp_path / "BENCH_CPU.json").write_text(json.dumps([
            {"metric": "overhead_fps", "value": 40000.0, "unit": "fps",
             "platform": "cpu", "captured_at": fresh},
            {"metric": "broken_fps", "value": None, "unit": "fps",
             "platform": "cpu", "captured_at": fresh},
        ]))
        rep = pt.trend_report(root=str(tmp_path),
                              baseline_path=str(tmp_path / "missing.json"))
        by_metric = {h["metric"]: h for h in rep["history"]}
        assert by_metric["overhead_fps"]["platform"] == "cpu"
        assert by_metric["overhead_fps"]["age_days"] == pytest.approx(
            0.0, abs=0.1)
        assert by_metric["broken_fps"]["status"] == "failed (no value)"
        assert "overhead_fps" in pt.render_markdown(rep)

    def test_report_runs_on_real_repo(self):
        """The ledger walks the repo's actual BENCH_* rows (all of them
        CPU: no chip row is committed) and renders without error."""
        pt = _load_perf_truth()
        rep = pt.trend_report()
        assert rep["history"], "no bench history found in the repo"
        assert all(str(h["platform"]).startswith("cpu")
                   for h in rep["history"])
        md = pt.render_markdown(rep)
        assert "PERF_BASELINE.json" in md


class TestBenchHygiene:
    def test_failed_row_exits_nonzero_with_no_stand_in(self, capsys,
                                                       monkeypatch):
        """bench.py answers for a row it could not measure with an error
        row and a non-zero exit — never a stored value."""
        bench = _load_bench()

        def boom(deadline_ts):
            raise RuntimeError("no device")

        monkeypatch.setenv("BENCH_MODEL", "overhead")
        monkeypatch.setattr(bench, "overhead_row", boom)
        assert bench.main() == 1
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["value"] is None and "no device" in out["error"]
        assert out["platform"] == "cpu" and out["device_count"] >= 1
        assert "stale" not in out and "cpu_proxy" not in out

    def test_age_days_parses_and_rejects(self):
        bench = _load_bench()
        now = time.time()
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                              time.gmtime(now - 86400))
        assert bench.age_days(stamp, now=now) == pytest.approx(1.0,
                                                              abs=0.05)
        assert bench.age_days("unknown") is None
        assert bench.age_days("") is None

    def test_git_rev_of_the_harness_tree(self):
        """Perf-truth baselines align with commits via the harness git
        revision (a real checkout here, so non-None)."""
        rev = _load_bench().git_rev()
        assert rev and len(rev) >= 7


# ---------------------------------------------------------------------------
# Perf-block contiguity (PR-8 caveat pinned): the conftest load shield
# must keep perf-marked items in ONE contiguous block after any plugin
# (pytest-randomly included) reorders collection.
# ---------------------------------------------------------------------------
class _FakeItem:
    def __init__(self, name, perf):
        self.name = name
        self._perf = perf

    def get_closest_marker(self, name):
        return object() if (name == "perf" and self._perf) else None


def _drive_hookwrapper(items):
    import conftest

    gen = conftest.pytest_collection_modifyitems(None, items)
    next(gen)  # the pre-yield half (other plugins would reorder here)
    with pytest.raises(StopIteration):
        next(gen)


def test_perf_block_stays_contiguous():
    """Simulated post-shuffle order: perf items scattered through the
    list are gathered into one contiguous block at the first perf
    item's position, non-perf relative order preserved."""
    items = [
        _FakeItem("a", False), _FakeItem("p1", True), _FakeItem("b", False),
        _FakeItem("p2", True), _FakeItem("c", False), _FakeItem("p3", True),
    ]
    _drive_hookwrapper(items)
    names = [it.name for it in items]
    assert names == ["a", "p1", "p2", "p3", "b", "c"]
    # idempotent: re-running the shield does not move the block
    _drive_hookwrapper(items)
    assert [it.name for it in items] == names
    # degenerate cases: all-perf and no-perf lists stay untouched
    all_perf = [_FakeItem("x", True), _FakeItem("y", True)]
    _drive_hookwrapper(all_perf)
    assert [it.name for it in all_perf] == ["x", "y"]


def test_perf_block_contiguous_in_real_session(request):
    """The REAL collected session (whatever pytest-randomly did this
    run) holds its perf items contiguously."""
    items = request.session.items
    perf_idx = [
        i for i, it in enumerate(items)
        if it.get_closest_marker("perf") is not None
    ]
    if len(perf_idx) < 2:
        pytest.skip("fewer than 2 perf items collected in this run")
    assert perf_idx == list(range(perf_idx[0], perf_idx[0] + len(perf_idx))), (
        "perf-marked items are not contiguous — the conftest load "
        "shield regressed")
