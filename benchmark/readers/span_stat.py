"""A number from the program's own spans over the traced seconds.

While a profiler session is live the program records a span at every layer
boundary (``nnstreamer_tpu.core.tracer``); this reader takes those that fall
in the traced window (``ctx.span("traced")``) and reduces them with
``benchmark/spans.py``.  ``params``: ``span`` (a name or a list of names),
``where`` (attribute values a span must carry), and ``stat``:

* ``mean_ms``: mean duration of the spans that ended in the window;
* ``self_share_pct``: share of the window that is their self time;
* ``self_ms_per``: their summed self time over the number of ``per`` spans
  that ended in the window (a layer's host time per batch, children named
  in ``span`` so that each second is counted once);
* ``attr_ratio_pct``: 100 x summed attribute ``num`` over summed ``den``.

``given`` names the spans that show the measured path ran at all: with one
of them in the window, a wait that never happened reads 0 and not nothing.
A program without spans (an older commit), or no matching span: nothing
returned, never 0.
"""

import time

from .. import spans


def read(metric, ctx):
    try:
        from nnstreamer_tpu.core.tracer import spans_between
    except ImportError:
        return None
    _, _, ta, tb = ctx.span("traced")
    if ta is None or tb is None:
        return None
    # everything that overlaps the window: a span still open at its end
    # counts with its part inside
    records = [r for r in spans_between(ta, time.perf_counter()) if r.t0 < tb]
    if "spans" not in ctx.notes:
        ctx.notes["spans"] = spans.table(records, ta, tb)
    p = metric["params"]
    picked = spans.named(records, p["span"], p.get("where"))
    ended = [r for r in picked if ta <= r.t1 <= tb]
    stat = p["stat"]
    if stat == "mean_ms":
        return spans.mean_ms(ended)
    if stat == "attr_ratio_pct":
        return spans.attr_ratio_pct(ended, p["num"], p["den"])
    if stat == "self_ms_per":
        per = [r for r in spans.named(records, p["per"]) if ta <= r.t1 <= tb]
        if not per:
            return None
        return 1e3 * spans.self_seconds(records, picked, ta, tb) / len(per)
    if stat == "self_share_pct":
        if not spans.clipped(picked, ta, tb) and not spans.clipped(
                spans.named(records, p.get("given", ())), ta, tb):
            return None
        return spans.self_share_pct(records, picked, ta, tb)
    raise ValueError(f"metric {metric['name']}: unknown stat {stat!r}")
