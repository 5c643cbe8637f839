"""The reduction from span records to numbers (``benchmark/spans.py``), on a
hand-written list: self time with nested and overlapping children, a span
cut by the interval's edge, shares, ratios, and intervals that crossed
threads."""

from collections import namedtuple

import pytest

from benchmark import spans

R = namedtuple("R", "name t0 t1 thread attrs")


def r(name, t0, t1, thread="t", **attrs):
    return R(name, t0, t1, thread, attrs)


#   t:   0    1    2    3    4    5    6    7    8    9    10
#  turn  [==================================================]
#  admit [====]
#  decode          [==============================]
#   dispatch       [====]
#   sync                [=========================]
#  compile                   [====]      (inside sync: overlaps nothing else)
RECORDS = [
    r("turn", 0, 10),
    r("admit", 0, 1),
    r("decode", 2, 8, k=8),
    r("dispatch", 2, 3),
    r("sync", 3, 8),
    r("compile", 4, 5),
    r("sync", 12, 14),              # a later turn's, no parent recorded
    r("other", 0, 10, thread="u"),  # another thread covers nothing of "t"
    r("wait", 1, 9, thread=None),   # crossed threads: nobody's child
]


def pick(name):
    return spans.named(RECORDS, name)


def test_self_time_is_the_duration_less_what_children_cover():
    assert spans.self_seconds(RECORDS, pick("turn"), 0, 20) == pytest.approx(3)
    assert spans.self_seconds(RECORDS, pick("decode"), 0, 20) == pytest.approx(0)
    assert spans.self_seconds(RECORDS, pick("sync"), 0, 20) == pytest.approx(6)
    assert spans.self_seconds(RECORDS, pick("other"), 0, 20) == pytest.approx(10)
    # the self times of a layer and of everything under it add up to it
    names = ["turn", "admit", "decode", "dispatch", "sync", "compile"]
    whole = spans.self_seconds(RECORDS, spans.named(RECORDS, names), 0, 10)
    assert whole == pytest.approx(10)


def test_overlapping_children_are_counted_once():
    recs = [r("p", 0, 10), r("a", 1, 6), r("b", 4, 8)]
    assert spans.self_seconds(recs, spans.named(recs, "p"), 0, 10) == pytest.approx(3)


def test_a_span_cut_by_the_edge_counts_with_the_part_inside():
    # the interval [2.5, 6]: turn has 3.5 s inside, all under decode
    assert spans.self_seconds(RECORDS, pick("turn"), 2.5, 6) == pytest.approx(0)
    # sync has [3, 6] inside, of which compile covers [4, 5]
    assert spans.self_seconds(RECORDS, pick("sync"), 2.5, 6) == pytest.approx(2)
    assert spans.self_share_pct(RECORDS, pick("sync"), 2.5, 6) == pytest.approx(
        100 * 2 / 3.5)
    assert [(s, e) for _, s, e in spans.clipped(pick("sync"), 2.5, 6)] == [(3, 6)]
    assert spans.self_share_pct(RECORDS, pick("sync"), 6, 6) is None


def test_an_interval_that_crossed_threads_is_whole_and_takes_nothing_away():
    assert spans.self_seconds(RECORDS, pick("wait"), 0, 20) == pytest.approx(8)
    with_it = spans.self_seconds(RECORDS, pick("turn"), 0, 20)
    without = [x for x in RECORDS if x.name != "wait"]
    assert with_it == spans.self_seconds(without, spans.named(without, "turn"), 0, 20)


def test_means_ratios_and_the_filter_on_attributes():
    assert spans.mean_ms(pick("sync")) == pytest.approx(3500)
    assert spans.mean_ms([]) is None
    inv = [r("inv", 0, 1, frames=128, bucket=128), r("inv", 1, 2, frames=37, bucket=64),
           r("inv", 2, 3, frames=1)]                    # no bucket: left out
    assert spans.attr_ratio_pct(inv, "frames", "bucket") == pytest.approx(100 * 165 / 192)
    assert spans.attr_ratio_pct(inv[2:], "frames", "bucket") is None
    waits = [r("w", 0, 1, element="f"), r("w", 1, 2, element="src"), r("x", 2, 3, element="f")]
    assert [x.t0 for x in spans.named(waits, ["w", "x"], {"element": "f"})] == [0, 2]


def test_the_table_counts_what_ended_inside():
    table = spans.table(RECORDS, 0, 10)
    assert set(table) == {"turn", "admit", "decode", "dispatch", "sync", "compile",
                          "other", "wait"}
    assert table["sync"] == {"n": 1, "s": pytest.approx(5), "self_s": pytest.approx(4)}
    assert table["turn"]["self_s"] == pytest.approx(3)
