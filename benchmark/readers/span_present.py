"""``span_stat``, where the span it reads ended in the traced window at all.

``span_stat``'s ``self_ms_per`` reads 0 where the program ran its ``per``
spans but has no span of the name measured: a program that predates that
span (an older commit) would print a false 0 beside a later one's reading.
This reader returns nothing there, and ``span_stat``'s number otherwise.
``params`` are ``span_stat``'s.
"""

from .. import spans
from . import span_stat


def read(metric, ctx):
    try:
        from nnstreamer_tpu.core.tracer import spans_between
    except ImportError:
        return None
    _, _, ta, tb = ctx.span("traced")
    if ta is None or tb is None:
        return None
    p = metric["params"]
    if not spans.named(spans_between(ta, tb), p["span"], p.get("where")):
        return None
    return span_stat.read(metric, ctx)
