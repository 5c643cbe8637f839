"""``gen_gap_p95_ms`` read at a point inside the program.

The driver's end-to-end metric is the 95th percentile of the gap between a
request's successive token frames at the client, over the later frame's
tokens, every gap whose later frame arrived in the window
(``drivers/closed_loop_generate.py``).  This reader takes the same definition
at an instant the program records once a frame, over the traced seconds
(``ctx.span("traced")``): ``params.span`` names records of one frame each,
whose ``request`` is the request they belong to and whose attribute
``tokens`` is the frame's tokens; ``params.edge`` (``t0`` or ``t1``) says
which end of a record is the frame's instant.  A frame with no tokens (a
stream's closing frame) is no frame here, as it is none at the client.  The
earlier frame of a gap may lie before the window where the program recorded
it; a gap whose earlier frame came before the profiler started has no record
and is left out, where the driver counts it.  A program without such records
(an older commit): nothing returned, never 0.
"""

import time

from .. import harness, spans


def gaps_ms(records, edge, ta, tb):
    """Every gap, in ms a token, between successive frames of one request
    whose later frame's ``edge`` falls in ``(ta, tb]``."""
    frames = {}
    for r in records:
        n = r.attrs.get("tokens", 0)
        if n and r.request is not None:
            frames.setdefault(r.request, []).append((getattr(r, edge), n))
    out = []
    for seq in frames.values():
        seq.sort()
        out.extend((b[0] - a[0]) / b[1] * 1e3
                   for a, b in zip(seq, seq[1:]) if ta < b[0] <= tb)
    return out


def read(metric, ctx):
    try:
        from nnstreamer_tpu.core.tracer import spans_between
    except ImportError:
        return None
    _, _, ta, tb = ctx.span("traced")
    if ta is None or tb is None:
        return None
    p = metric["params"]
    records = spans.named(spans_between(float("-inf"), time.perf_counter()), p["span"])
    return harness.percentile(gaps_ms(records, p["edge"], ta, tb), 95)
