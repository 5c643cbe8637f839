"""From the program's span records to numbers.

A record is what ``nnstreamer_tpu.core.tracer.spans_between`` returns:
anything with ``name``, ``t0``, ``t1`` (host seconds on one clock),
``thread`` (None for an interval that crossed threads) and ``attrs``.  Everything here is arithmetic on a list of them
over an interval ``[ta, tb]``; a span that crosses an edge of the interval
counts with the part inside it.

**Self time** of a span is its duration minus what the other spans on the
same thread cover inside it (the choosing-metrics guide, section 4), so the
self times of a layer and of its children add up to the layer's duration
and nothing is counted twice.
"""

from __future__ import annotations

from .xplane import union_ns


def named(records, names, where=None):
    """Records called one of ``names`` whose attributes hold every pair of
    ``where``."""
    names = set([names] if isinstance(names, str) else names)
    where = where or {}
    return [r for r in records if r.name in names
            and all(r.attrs.get(k) == v for k, v in where.items())]


def clipped(records, ta, tb):
    """(record, start, end) with the interval cut to ``[ta, tb]``; records
    wholly outside are left out."""
    out = []
    for r in records:
        s, e = max(r.t0, ta), min(r.t1, tb)
        if e > s:
            out.append((r, s, e))
    return out


def self_seconds(records, picked, ta, tb):
    """Summed self time, inside ``[ta, tb]``, of the ``picked`` records:
    each one's part of the interval less what OTHER records of ``records``
    on its thread cover there.  A record that covers a picked one whole
    (its parent) is not a child and takes nothing away; one that crossed
    threads (``thread`` None) has no children and is nobody's child."""
    by_thread = {}
    for r in records:
        if r.thread is not None:
            by_thread.setdefault(r.thread, []).append(r)
    total = 0.0
    for r, s, e in clipped(picked, ta, tb):
        inside = []
        for c in by_thread.get(r.thread, ()):
            if c is r or (c.t0 <= r.t0 and c.t1 >= r.t1):
                continue
            cs, ce = max(c.t0, s), min(c.t1, e)
            if ce > cs:
                inside.append((cs, ce))
        total += (e - s) - union_ns(inside)
    return total


def mean_ms(picked):
    """Mean whole duration, in ms, of the picked records."""
    if not picked:
        return None
    return 1e3 * sum(r.t1 - r.t0 for r in picked) / len(picked)


def self_share_pct(records, picked, ta, tb):
    """Share of ``[ta, tb]`` that is self time of the picked records."""
    if tb <= ta:
        return None
    return 100.0 * self_seconds(records, picked, ta, tb) / (tb - ta)


def attr_ratio_pct(picked, num, den):
    """100 x the sum of attribute ``num`` over the sum of ``den``, over the
    picked records that carry both."""
    both = [r for r in picked if num in r.attrs and den in r.attrs]
    total = sum(r.attrs[den] for r in both)
    if total <= 0:
        return None
    return 100.0 * sum(r.attrs[num] for r in both) / total


def table(records, ta, tb):
    """Per name: how many ended in ``[ta, tb]``, their summed seconds and
    self seconds inside it (the breakdown a traced run prints)."""
    rows = {}
    for r in records:
        if ta <= r.t1 <= tb:
            rows.setdefault(r.name, []).append(r)
    return {name: {"n": len(rs),
                   "s": sum(e - s for _, s, e in clipped(rs, ta, tb)),
                   "self_s": self_seconds(records, rs, ta, tb)}
            for name, rs in sorted(rows.items())}
