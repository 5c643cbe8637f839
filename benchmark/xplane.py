"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax.  What a TPU
trace holds (looked at by hand, PERF.md section 3): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per run of a
compiled program, named ``jit_<function>(<fingerprint>)``) and ``XLA Ops``
(one event per operation inside it), and one plane ``/host:CPU`` with a line
per host thread.  Times are nanoseconds on one clock.
"""

from __future__ import annotations

import re

_FINGERPRINT = re.compile(r"\(\d+\)$")


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, t0, t1):
    """The idle (start, end) stretches of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def read(path):
    """Planes of an xplane file as plain lists:
    ``{"devices": {plane: {"modules": [...], "ops": [...]}}, "host": {line: [...]}}``
    with events as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    lines[key] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events]
            if lines.get("modules") or lines.get("ops"):
                devices[plane.name] = {"modules": lines.get("modules", []),
                                       "ops": lines.get("ops", [])}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host[line.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
    return {"devices": devices, "host": host}


def program_of(module_name):
    """``jit_call(123)`` -> ``jit_call``."""
    return _FINGERPRINT.sub("", module_name)


def _host_span_at(host, t):
    """The innermost host event (shortest) covering instant t, over all
    host threads; None where no thread had an event open."""
    best = None
    for events in host.values():
        for name, s, e in events:
            if s <= t < e and (best is None or e - s < best[1]):
                best = (name, e - s)
    return best[0] if best else None


def reduce(trace, top=10):
    """Busy and idle seconds, per-program device seconds, the operations that
    took most device time, and the longest idle gaps by what the host was
    doing at the middle of each.

    The window is from the first to the last device event over all chips;
    ``busy_s`` is the union of operation intervals, averaged over the chips.
    """
    devs = trace["devices"]
    if not devs:
        return None
    spans = [ev for d in devs.values() for ev in (d["ops"] or d["modules"])]
    t0 = min(s for _, s, _ in spans)
    t1 = max(e for _, _, e in spans)
    busy, programs, ops, all_gaps = [], {}, {}, []
    for d in devs.values():
        events = d["ops"] or d["modules"]
        iv = [(s, e) for _, s, e in events]
        busy.append(union_ns(iv))
        all_gaps += gaps(iv, t0, t1)
        # a while loop's own event spans its body's operations: leave it out
        # of the per-operation ranking, or the loop hides what is inside it
        for name, s, e in d["ops"]:
            if not name.startswith("%while"):
                ops[name] = ops.get(name, 0.0) + (e - s)
        for name, s, e in d["modules"]:
            row = programs.setdefault(name, {"runs": 0, "ns": 0.0, "has_while": False})
            row["runs"] += 1
            row["ns"] += e - s
        whiles = sorted((s, e) for name, s, e in d["ops"] if name.startswith("%while"))
        if whiles:
            for name, s, e in d["modules"]:
                if any(ws >= s and we <= e for ws, we in whiles):
                    programs[name]["has_while"] = True
    all_gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_span_at(trace["host"], (s + e) / 2) or "no host span", (e - s) / 1e9]
            for s, e in all_gaps[:top]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "chips": len(devs),
        "programs": {k: {"runs": v["runs"], "s": v["ns"] / 1e9, "has_while": v["has_while"]}
                     for k, v in programs.items()},
        "device_ops": [[name[:96], ns / 1e9] for name, ns in top_ops],
        "idle_gaps": idle,
    }


def program_runs(reduced, program, has_while=None):
    """(runs, device seconds) of the programs named ``program`` (the
    fingerprint left out); ``has_while`` keeps only those that hold, or do
    not hold, a while loop.  None where nothing matched."""
    runs, total = 0, 0.0
    for name, row in reduced["programs"].items():
        if program_of(name) != program:
            continue
        if has_while is not None and row["has_while"] != has_while:
            continue
        runs += row["runs"]
        total += row["s"]
    return (runs, total) if runs else None

