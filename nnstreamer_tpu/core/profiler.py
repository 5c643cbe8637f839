"""Profilers: the jax/XLA trace session and the incident-time thread
sampler.

**jax.profiler integration** (SURVEY §5.1: the reference delegates
tracing to GstShark/gst-instruments; the TPU-native equivalent is XLA's
own profiler, surfaced through the same kind of element properties).
One process-global trace session (the jax profiler is a singleton):
elements call :func:`trace_start`/:func:`trace_stop` and refcounting
keeps the session alive while any element wants it.  View traces with
TensorBoard or xprof (``trace-dir`` holds the .xplane.pb files).  While
a session is live the program's layer spans (:func:`span`, defined in
:mod:`~.tracer` and re-exported here) are written into the same trace.

**Incident-time thread profiler** (`Documentation/observability.md`
"Thread profiler"): a sampling wall-clock profiler over the NAMED
framework threads — segment dispatch workers (named after their head
element), the completion-window ``-reaper``, the ingest-lane ``-stage``
worker, slot-engine pumps, watchdogs.  :func:`profile_threads` samples
``sys._current_frames()`` at ~50 Hz for a bounded window and returns
collapsed top-stacks per thread, so "where did the 86% dispatch tax go"
is answerable from a flight-recorder dump without a chip or
TensorBoard.  The flight recorder (:mod:`~.telemetry`) attaches a
capture to every incident dump; call it directly for on-demand looks at
a live pipeline.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import Counter
from typing import Dict, Optional, Tuple

from .log import get_logger
from .tracer import record, span, spans_between  # noqa: F401 — the span API

log = get_logger("profiler")

_lock = threading.Lock()
_refs = 0
_dir: Optional[str] = None


def trace_start(trace_dir: str) -> bool:
    """Begin (or join) the global profiler trace; returns True if tracing."""
    global _refs, _dir
    with _lock:
        if _refs == 0:
            import jax

            try:
                jax.profiler.start_trace(trace_dir)
            except Exception as e:
                log.warning("profiler trace unavailable: %s", e)
                # a failed start can leave the jax singleton half-armed
                # (start_trace raised after claiming the session); reset
                # it so the next trace_start — possibly from a different
                # element with a different dir — enters the refs==0 path
                # against a clean singleton instead of refcounting on
                # top of stale state.  EXCEPT when the failure says the
                # session is already active: that one belongs to someone
                # ELSE (an operator's own TensorBoard capture) — a reset
                # would kill their in-progress trace mid-run.
                if "already" not in str(e).lower():
                    try:
                        jax.profiler.stop_trace()
                    except Exception:  # allow-silent: best-effort reset
                        pass           # of a never-started session
                _dir = None
                return False
            _dir = trace_dir
        elif trace_dir != _dir:
            log.warning(
                "profiler already tracing to %s; ignoring %s", _dir, trace_dir
            )
        _refs += 1
        return True


def trace_stop() -> None:
    """Drop one trace reference; the session ends at zero."""
    global _refs, _dir
    with _lock:
        if _refs == 0:
            return
        _refs -= 1
        if _refs == 0:
            import jax

            try:
                jax.profiler.stop_trace()
            except Exception as e:
                log.warning("profiler stop failed: %s", e)
            log.info("profiler trace written to %s", _dir)
            _dir = None


def trace_active() -> bool:
    """True while any element holds the global trace session open (the
    ``nns.profiler.active`` gauge reads the per-element view via
    ``health_info``; this is the process-wide one)."""
    return _refs > 0


# ---------------------------------------------------------------------------
# Incident-time thread profiler (sampling, wall-clock, host-side)
# ---------------------------------------------------------------------------
#: thread-name prefixes that are NOT framework threads (library pools,
#: pytest/debugger internals) — the same census rule the test-suite leak
#: check uses: every framework thread is explicitly named
THREAD_IGNORE: Tuple[str, ...] = (
    "MainThread", "Thread-", "ThreadPool", "Dummy", "asyncio", "pydevd",
    "raylet",
)


def framework_thread_names() -> Dict[int, str]:
    """{ident: name} for live framework threads (named, not ignored)."""
    return {
        t.ident: t.name
        for t in threading.enumerate()
        if t.ident is not None and t.is_alive()
        and not t.name.startswith(THREAD_IGNORE)
    }


def _collapse(frame, max_depth: int) -> str:
    """One thread's current stack as a collapsed ``a;b;c`` string,
    outermost first (flamegraph convention), frames as file:func."""
    parts = []
    f = frame
    while f is not None and len(parts) < max_depth:
        code = f.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:{code.co_name}")
        f = f.f_back
    parts.reverse()
    return ";".join(parts)


def profile_threads(duration_s: float = 0.25, hz: float = 50.0,
                    top: int = 5, max_depth: int = 48,
                    include=None) -> Dict:
    """Sample the named framework threads for ``duration_s`` at ``hz``.

    Pure-Python wall-clock sampling via ``sys._current_frames()``: no
    tracing hooks are installed, the profiled threads pay nothing, and a
    thread BLOCKED in a C call (a wedged device sync, a socket read) is
    still visible — its Python stack is parked on the blocking call,
    which is exactly the answer an incident needs.  The CALLING thread
    blocks for the window; keep it off latency-critical paths (the
    flight recorder's rate limit bounds it there).

    Returns ``{duration_s, hz, samples, threads: {name: {samples,
    top_stacks: [{stack, count}, ...]}}}`` — ``stack`` is the collapsed
    ``file:func;file:func;...`` form, outermost first.  ``include``
    restricts to thread names containing any of the given substrings.
    """
    hz = max(1.0, float(hz))
    n = max(1, int(float(duration_s) * hz))
    period = 1.0 / hz
    me = threading.get_ident()
    agg: Dict[str, Counter] = {}
    taken = 0
    t0 = time.perf_counter()
    for i in range(n):
        names = framework_thread_names()
        # two pipelines in one process can both own an element (and
        # thus a streaming thread) named e.g. "f": disambiguate
        # duplicates as "name#<ident>" so a stalled thread's stacks are
        # never blended with a healthy namesake's
        seen: Counter = Counter(names.values())
        frames = sys._current_frames()
        try:
            for ident, name in names.items():
                if ident == me:
                    continue
                if include is not None and not any(
                        s in name for s in include):
                    continue
                frame = frames.get(ident)
                if frame is None:
                    continue
                key = name if seen[name] == 1 else f"{name}#{ident}"
                agg.setdefault(key, Counter())[
                    _collapse(frame, max_depth)] += 1
        finally:
            del frames  # frame objects pin their locals; release now
        taken += 1
        if i + 1 < n:
            time.sleep(period)
    return {
        "duration_s": round(time.perf_counter() - t0, 4),
        "hz": hz,
        "samples": taken,
        "threads": {
            name: {
                "samples": sum(ctr.values()),
                "top_stacks": [
                    {"stack": s, "count": c}
                    for s, c in ctr.most_common(top)
                ],
            }
            for name, ctr in sorted(agg.items())
        },
    }
