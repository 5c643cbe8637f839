"""The benchmark's common parts: loading a cell by name, the compile meter,
the set-up phase table, the profiler window, quantiles and the result line.

Everything here is the yardstick and belongs to no cell.  What belongs to one
configuration, traffic mix or per-layer metric sits in a data file of its own
(``configs/``, ``traffic/``, ``workloads/``, ``metrics/``) that ``load_cell``
finds by the name ``BENCHMARK.json`` gives it.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class CellError(ValueError):
    """A cell, configuration, mix or metric that cannot be loaded."""


def check_name(name):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise CellError(f"name {name!r}: letters, digits, '_', '.', '-' only, at most 64")
    return name


def check_unit(unit):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise CellError(f"unit {unit!r}: 1 to 16 of letters, digits, '_/%.-'")
    return unit


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name, bench, workload, config, traffic, end_to_end, per_layer):
        self.name = name
        self.bench = bench            # BENCHMARK.json
        self.workload = workload      # workloads/<cell>.json
        self.config = config          # configs/<config>.json
        self.traffic = traffic        # traffic/<mix>.json
        self.end_to_end = end_to_end  # BENCHMARK.json entries this cell reports
        self.per_layer = per_layer    # those entries merged with metrics/<metric>.json
        self.chips = int(workload.get("chips", 1))

    def rehearsal(self):
        """The same cell at the tiny sizes of the workload file's own
        ``rehearsal`` block (CPU rehearsals and the self-tests)."""
        block = self.workload.get("rehearsal", {})
        config = {**self.config, **block.get("config", {})}
        traffic = {**self.traffic, **block.get("traffic", {})}
        workload = {**self.workload, **block.get("workload", {})}
        return Cell(self.name, self.bench, workload, config, traffic,
                    self.end_to_end, self.per_layer)


def _metric_lists(bench, cell_name):
    def mine(m):
        return "workloads" not in m or cell_name in m["workloads"]

    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m["name"])
        check_unit(m["unit"])
        if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
            raise CellError(f"metric {m['name']}: bad 'better' or 'source'")
    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def load_cell(name, root=ROOT):
    """Resolve a cell by name: its BENCHMARK.json entry, then
    ``workloads/<cell>.json``, the configuration's file, ``traffic/<mix>.json``
    and ``metrics/<metric>.json`` for every per-layer metric that lists it."""
    check_name(name)
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    base = os.path.join(root, bench["paths"][0])
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if conf is None:
        raise CellError(f"workload {name}: no config {entry['config']!r}")
    check_name(entry["config"])
    check_name(entry["traffic"])
    workload = {**load_json(os.path.join(base, "workloads", name + ".json")), **entry}
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(base, "traffic", entry["traffic"] + ".json"))
    end_to_end, per_layer = _metric_lists(bench, name)
    merged = []
    for m in per_layer:
        merged.append({**load_json(os.path.join(base, "metrics", m["name"] + ".json")), **m})
    return Cell(name, bench, workload, config, traffic, end_to_end, merged)


def load_module(kind, name):
    """``drivers/<name>.py``, ``readers/<name>.py`` or ``configs/<name>.py``."""
    check_name(name)
    return importlib.import_module(f"benchmark.{kind}.{name}")


# ---------------------------------------------------------------------------
# set-up accounting
# ---------------------------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileMeter:
    """jax's own compile accounting: seconds in backend compile (cache
    retrieval on a hit), programs, persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.counts = {"compile_s": 0.0, "programs": 0, "cache_hits": 0, "cache_misses": 0}
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        key = _CACHE_EVENTS.get(event)
        if key:
            with self._lock:
                self.counts[key] += 1

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.counts["compile_s"] += float(duration)
                self.counts["programs"] += 1

    def snapshot(self):
        with self._lock:
            return dict(self.counts)


class Phases:
    """Set-up by phase: name, seconds, and what the compile meter saw."""

    def __init__(self, t_start, meter):
        self.t_start = t_start
        self.meter = meter
        self.rows = []
        self._last = t_start
        self._last_counts = meter.snapshot() if meter else {}

    def mark(self, name, **detail):
        now = time.perf_counter()
        row = {"phase": name, "s": now - self._last}
        if self.meter:
            counts = self.meter.snapshot()
            for k, v in counts.items():
                d = v - self._last_counts.get(k, 0)
                if d:
                    row[k] = d
            self._last_counts = counts
        row.update(detail)
        self.rows.append(row)
        self._last = now
        return row


# ---------------------------------------------------------------------------
# the profiler window
# ---------------------------------------------------------------------------
class TraceWindow:
    """Trace ``length_s`` seconds of the measured window, ``delay_s`` after
    its start, in a thread of its own.  ``snapshot`` is called right after the
    trace starts and right before it stops (a diagnostic: on a device whose
    queue is deep the device's events run on past the host's stop).  The xplane file goes under TMPDIR and is removed by
    ``cleanup``."""

    def __init__(self, delay_s, length_s, snapshot):
        self.delay_s, self.length_s, self.snapshot = delay_s, length_s, snapshot
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.c0 = self.c1 = None
        self.t0 = self.t1 = None
        self.error = None
        self._thread = threading.Thread(target=self._run, name="bench-trace", daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.delay_s)
            # host TraceMe events and the device; not every Python call (a
            # flooding pusher would make millions) and no HLO protos
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            self._say("start_trace")
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.t0 = time.perf_counter()
            self.c0 = self.snapshot()
            self._say("tracing")
            time.sleep(self.length_s)
            self.c1 = self.snapshot()
            self.t1 = time.perf_counter()
            # slow on a TPU: the tracer waits for every program already queued
            # on the device and then takes about ten seconds per traced
            # second to write the file; the load goes on meanwhile
            self._say("stop_trace")
            jax.profiler.stop_trace()
            self._say("stopped")
        except BaseException as e:  # noqa: BLE001 — reported by join()
            self.error = e

    @staticmethod
    def _say(what):
        print(f"bench-trace {time.perf_counter():.3f} {what}", file=sys.stderr, flush=True)

    def join(self, timeout=300.0):
        self._thread.join(timeout)
        if self._thread.is_alive():
            import faulthandler

            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            raise RuntimeError("the profiler did not stop")
        if self.error is not None:
            raise self.error

    def xplane_path(self):
        for dirpath, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(dirpath, f)
        raise RuntimeError(f"no xplane file under {self.dir}")

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def percentile(values, q):
    """Nearest-rank percentile over ALL values (no trimming): the smallest
    value with at least q % of the sample at or below it."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, min(len(s) - 1, -(-len(s) * q // 100) - 1))
    return float(s[int(k)])


def device_info(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def load_peaks(kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table["device_kinds"]:
        raise CellError(f"device kind {kind!r} is not in benchmark/peaks.json "
                        f"(known: {sorted(table['device_kinds'])})")
    return table["device_kinds"][kind]


def model_seed(seed):
    """The seed the program's ``seed:`` prop and the reference's init both
    take: ``jax.random.PRNGKey`` of a Python int past 2**31 is not portable,
    so the run's seed is folded into [0, 2**31 - 1)."""
    return int(seed) % (2**31 - 1)


def judge(compared):
    """``correct`` from the numbers compared: each has a value and a limit."""
    ok = True
    for row in compared.values():
        v = row["value"]
        ok = ok and v is not None and v == v and v <= row["limit"]
    return bool(ok)
