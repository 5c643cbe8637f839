#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's pipeline through the program's normal entry points, warms
every program the cell's traffic uses (set-up), measures for ``--seconds``,
frees the program, then checks a sample of what the timed path produced
against the plain float32 reference.  Standard output ends with one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
with ``--trace 1`` also ``breakdown``, and last ``compared`` (each number
compared beside its limit).  A line above it gives the set-up by phase.

No TPU, or fewer chips than the cell asks for: exit 3 and no result.
``--rehearse`` runs the cell at the tiny sizes of its own ``rehearsal`` block
on whatever device jax has (CPU rehearsals and the self-tests only).
``--control fp8`` also reads the control (the reference in fp8 in the
program's place); the driver's runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Ctx:
    """What a per-layer reader may read."""

    def __init__(self, cell, session, peaks, meter0, meter1, tracer, reduced, e2e):
        self.cell, self.session, self.peaks, self.e2e = cell, session, peaks, e2e
        self.meter0, self.meter1 = meter0, meter1
        self.tracer, self.reduced = tracer, reduced
        self.facts = session.facts()
        self.notes = {}

    def span(self, which):
        """(counters at start, counters at end, host time at start, at end)."""
        if which == "traced":
            t = self.tracer
            return t.c0, t.c1, t.t0, t.t1
        s = self.session
        return s.c0, s.c1, s.t0, s.t1


def run_cell(cell, seed, seconds, trace, control=None, need_tpu=True, t_start=None, out=print):
    """One run of one cell; returns the result object (the last line) and
    the diagnostics (the line above it)."""
    import jax

    from benchmark import harness, xplane
    from nnstreamer_tpu.core import compile_cache

    t_start = time.perf_counter() if t_start is None else t_start
    cache_dir = compile_cache.enable()
    devices = jax.devices()
    if need_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit(f"benchmark: cell {cell.name} needs {cell.chips} TPU chip(s); jax has "
                         f"{len(devices)} x {devices[0].platform}")
    devices = devices[:cell.chips]
    # a rehearsal has no chip and so no peak: the readers that need one return nothing
    peaks = harness.load_peaks(devices[0].device_kind) if need_tpu else None
    meter = harness.CompileMeter()
    phases = harness.Phases(t_start, meter)
    phases.mark("imports and device init", cache_dir=cache_dir)

    driver = harness.load_module("drivers", cell.traffic["kind"])
    session = driver.Session(cell, seed, phases)
    tracer = reduced = None
    try:
        session.setup()
        if trace:
            tracer = harness.TraceWindow(
                cell.workload["trace_delay_s"], min(cell.workload["trace_s"], seconds),
                session.counters)
        meter0 = meter.snapshot()
        t0 = session.window(seconds, tracer)
        meter1 = meter.snapshot()
        setup_s = t0 - t_start
        e2e = session.end_to_end()
        attempted, failed = session.attempted_failed()
        device = harness.device_info(devices)
    finally:
        session.close()
    gc.collect()
    out(json.dumps({"setup_s": setup_s, "setup_phases": phases.rows}))

    if tracer:
        t_read = time.perf_counter()
        reduced = xplane.reduce(xplane.read(tracer.xplane_path()))
        tracer.cleanup()
        if reduced and reduced["busy_s"] > 0:
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        elif need_tpu:
            raise SystemExit("benchmark: the trace holds no device operation")
        trace_read_s = time.perf_counter() - t_read

    t_check = time.perf_counter()
    compared, ctl = session.compare(control)
    check_s = time.perf_counter() - t_check

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics, notes = {}, {}
    if trace:
        ctx = Ctx(cell, session, peaks, meter0, meter1, tracer, reduced, e2e)
        for m in cell.per_layer:
            value = harness.load_module("readers", m["reader"]).read(m, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        notes = ctx.notes
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": units[m["name"]]}

    diag = {"facts": session.facts(), "counters": {"c0": session.c0, "c1": session.c1},
            "end_to_end": e2e, "programs_in_window": meter1["programs"] - meter0["programs"],
            "check_s": check_s,
            "check": getattr(session, "check_detail", {}), "notes": notes}
    if tracer:
        diag["trace"] = {"c0": tracer.c0, "c1": tracer.c1, "host_s": tracer.t1 - tracer.t0,
                         "read_s": trace_read_s,
                         "programs": reduced["programs"] if reduced else None}
    if ctl is not None:
        limits = {k: row["limit"] for k, row in compared.items()}
        in_place = {k: {"value": v, "limit": limits[k]} for k, v in ctl.items()}
        diag["control"] = {"precision": control, "readings": ctl,
                           "correct_in_programs_place": harness.judge(in_place)}
    out(json.dumps(diag))

    result = {"correct": harness.judge(compared), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    return result, diag


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None, choices=("fp8",))
    args = ap.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    if args.rehearse:
        cell = cell.rehearsal()
    try:
        result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace), args.control,
                          need_tpu=not args.rehearse, t_start=T_START)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 3
    for name, row in result["compared"].items():
        print(f"compared {name}: value {row['value']!r} limit {row['limit']!r}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon threads of stopped pipelines must not hold the exit
