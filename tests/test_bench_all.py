"""Sweep-runner logic tests (tools/bench_all.py).

The sweep runs one bench.py process per row, keeps every row it got, and
fails when any row has no value.  bench.py itself is faked — these tests
exercise the RUNNER, not the measurement.
"""

import importlib.util
import json
import os
import sys

import pytest

_BA = os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "tools", "bench_all.py"
)
_spec = importlib.util.spec_from_file_location("bench_all_module", _BA)
ba = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ba)


class _FakeRun:
    """Stands in for subprocess.run(bench.py): returns queued JSON rows."""

    def __init__(self, rows):
        self.rows = list(rows)
        self.calls = []

    def __call__(self, argv, capture_output, text, env):
        self.calls.append(dict(env))
        row = self.rows.pop(0) if self.rows else {"value": None,
                                                  "error": "exhausted"}

        class R:
            returncode = 0 if row.get("value") is not None else 1
            stdout = json.dumps(row) + "\n"
            stderr = ""

        return R()


@pytest.fixture
def runner(tmp_path, monkeypatch):
    out = str(tmp_path / "ROWS.json")
    monkeypatch.setattr(sys, "argv", ["bench_all.py", out])
    monkeypatch.chdir(tmp_path)

    def run(rows):
        fake = _FakeRun(rows)
        monkeypatch.setattr(ba.subprocess, "run", fake)
        rc = ba.main()
        with open(out) as f:
            return rc, json.load(f), fake

    return run, out


GOOD = {"metric": "m", "value": 100.0, "unit": "fps", "vs_baseline": None}


def test_all_rows_executed_and_written(runner):
    run, _ = runner
    rc, rows, fake = run([GOOD] * len(ba.ROWS))
    assert rc == 0
    assert len(rows) == len(ba.ROWS)
    assert all(r["value"] == 100.0 and "_env" in r for r in rows)
    assert [c["BENCH_MODEL"] for c in fake.calls] == [m for m, _ in ba.ROWS]


def test_failed_row_fails_the_sweep_but_keeps_the_rest(runner):
    """No chip, no stand-in: a row without a value is recorded as such,
    the remaining rows still run, and the sweep exits non-zero."""
    run, _ = runner
    bad = {"value": None, "error": "RuntimeError: no device"}
    rc, rows, fake = run([bad] + [GOOD] * (len(ba.ROWS) - 1))
    assert rc == 1
    assert len(rows) == len(ba.ROWS) == len(fake.calls)
    assert rows[0]["value"] is None and rows[1]["value"] == 100.0


def test_rows_include_block_int8_latency_and_host(runner):
    extras = [e for _, e in ba.ROWS]
    assert {"BENCH_RAW": "1", "BENCH_INGEST": "block"} in extras
    assert any(e.get("BENCH_QUANT") == "1" for e in extras)
    assert any(e.get("BENCH_BATCH_TIMEOUT") == "2" for e in extras)
    assert any(
        e.get("BENCH_INGEST") == "block" and e.get("BENCH_QUANT") == "1"
        for e in extras
    )
    assert any(e.get("BENCH_HOST") == "1" for e in extras)
