"""``readers/span_gap.py``: the driver's gap percentile read from records of
one frame each, on synthetic records (two requests, a stream's closing frame
with no tokens, gaps on both sides of the window's edges), against what
``drivers/closed_loop_generate.py`` computes from the same instants."""

import types

import pytest

from benchmark import harness
from benchmark.drivers.closed_loop_generate import Session
from benchmark.readers import span_gap

Rec = types.SimpleNamespace
TA, TB = 10.0, 12.0


def frames():
    """(request, instant, tokens) of every frame the two requests had."""
    a = [("a", 9.90, 8), ("a", 10.05, 8), ("a", 10.30, 8), ("a", 11.00, 8),
         ("a", 11.02, 2), ("a", 11.03, 0)]                 # 0: the closing frame
    b = [("b", 11.50, 8), ("b", 11.61, 8), ("b", 11.95, 8), ("b", 12.40, 8)]
    return a + b                                           # 12.40: after the window


def records(edge="t1", name="nns.query.recv"):
    out = []
    for req, t, n in frames():
        t0, t1 = (t, t + 0.004) if edge == "t0" else (t - 0.004, t)
        out.append(Rec(name=name, t0=t0, t1=t1, request=req, attrs={"tokens": n}, thread=None))
    out.append(Rec(name="nns.slots.turn", t0=10.5, t1=10.6, request=None, attrs={}, thread="p"))
    return out


def driver_p95():
    """The driver's number from the same instants: a client records only
    frames with tensors, and counts every gap whose later frame arrived in
    the window."""
    reqs = {}
    for req, t, n in frames():
        if n:
            reqs.setdefault(req, Rec(frames=[])).frames.append((t, n))
    fake = Rec(requests=list(reqs.values()), t0=TA, t1=TB,
               cell=Rec(workload={"request_timeout_s": 30}), _sent_in_window=lambda: [])
    return Session.end_to_end(fake)["gen_gap_p95_ms"]


class Ctx:
    def span(self, which):
        assert which == "traced"
        return {}, {}, TA, TB


@pytest.mark.parametrize("edge,name", [("t1", "nns.query.recv"), ("t0", "nns.gen.deliver")])
def test_the_reader_computes_the_drivers_percentile_at_its_edge(monkeypatch, edge, name):
    import nnstreamer_tpu.core.tracer as tracer

    recs = records(edge, name)
    monkeypatch.setattr(tracer, "spans_between", lambda ta, tb: list(recs))
    metric = {"name": "x", "params": {"span": name, "edge": edge}}
    got = span_gap.read(metric, Ctx())
    assert got == driver_p95()
    # by hand: a's gaps 10.05 (from 9.90, before the window), 10.30, 11.00
    # and the 2-token 11.02; b's 11.61 and 11.95; b's 12.40 is outside
    gaps = sorted(span_gap.gaps_ms(recs, edge, TA, TB))
    want = sorted([150 / 8, 250 / 8, 700 / 8, 20 / 2, 110 / 8, 340 / 8])
    assert gaps == pytest.approx(want)
    assert got == pytest.approx(harness.percentile(want, 95)) == pytest.approx(700 / 8)


def test_a_program_without_the_records_reads_as_nothing(monkeypatch):
    import nnstreamer_tpu.core.tracer as tracer

    metric = {"name": "x", "params": {"span": "nns.gen.deliver", "edge": "t0"}}
    monkeypatch.setattr(tracer, "spans_between",
                        lambda ta, tb: [r for r in records() if r.name == "nns.slots.turn"])
    assert span_gap.read(metric, Ctx()) is None
    monkeypatch.delattr(tracer, "spans_between")          # before spans existed
    assert span_gap.read(metric, Ctx()) is None


def test_a_self_time_per_turn_reads_nothing_where_the_span_never_ran(monkeypatch):
    """``readers/span_present.py``: ``span_stat``'s number where the span
    ended in the window; nothing, not ``span_stat``'s 0, on a program whose
    turns ran without it."""
    import nnstreamer_tpu.core.tracer as tracer
    from benchmark.readers import span_present, span_stat

    metric = {"name": "x", "params": {"span": "nns.slots.readback", "stat": "self_ms_per",
                                      "per": "nns.slots.turn"}}
    turns = [r for r in records() if r.name == "nns.slots.turn"]
    copies = Rec(name="nns.slots.readback", t0=10.51, t1=10.513, request=None, attrs={},
                 thread="p")

    def ctx():
        return types.SimpleNamespace(span=Ctx().span, notes={})

    monkeypatch.setattr(tracer, "spans_between",
                        lambda ta, tb: [r for r in turns if ta <= r.t1 <= tb])
    assert span_stat.read(metric, ctx()) == 0
    assert span_present.read(metric, ctx()) is None
    monkeypatch.setattr(tracer, "spans_between",
                        lambda ta, tb: [r for r in turns + [copies] if ta <= r.t1 <= tb])
    assert span_present.read(metric, ctx()) == pytest.approx(3.0)
    monkeypatch.delattr(tracer, "spans_between")          # before spans existed
    assert span_present.read(metric, ctx()) is None
