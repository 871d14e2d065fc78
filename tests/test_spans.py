"""The program's span helper: totals, self time, counters, the change
between two snapshots, and spans that end by an exception."""
import pytest

from repro.runtime import spans as spans_lib
from repro.runtime.spans import Spans, delta


@pytest.fixture
def clock(monkeypatch):
    """perf_counter as a counter: each read is one second later."""
    t = {"now": 0.0}

    def tick():
        t["now"] += 1.0
        return t["now"]

    monkeypatch.setattr(spans_lib, "perf_counter", tick)
    return t


def test_totals_and_self_time(clock):
    sp = Spans()
    with sp.span("outer"):             # reads 1 ... 6
        with sp.span("inner"):         # reads 2, 3
            pass
        with sp.span("inner", rid=7):  # reads 4, 5
            pass
    with sp.span("inner"):             # reads 7, 8
        pass
    snap = sp.snapshot()["spans"]
    assert snap["outer"] == {"count": 1, "total_s": 5.0, "self_s": 3.0}
    assert snap["inner"] == {"count": 3, "total_s": 3.0, "self_s": 3.0}
    assert sp.total("outer") == 5.0 and sp.total("absent") == 0.0


def test_rename_before_the_end_takes_the_new_name(clock):
    sp = Spans()
    with sp.span("engine.decode") as s:
        s.name = "engine.compile"
    assert s.seconds == 1.0
    assert set(sp.snapshot()["spans"]) == {"engine.compile"}


def test_a_span_ended_by_an_exception_is_counted_and_closed(clock):
    sp = Spans()
    with pytest.raises(RuntimeError):
        with sp.span("outer"):
            with sp.span("inner"):
                raise RuntimeError("device lost")
    with sp.span("after"):
        pass
    snap = sp.snapshot()["spans"]
    assert snap["outer"]["count"] == snap["inner"]["count"] == 1
    assert snap["outer"]["self_s"] == snap["outer"]["total_s"] - snap["inner"]["total_s"]
    # "after" opened at the top level: no open span took its time
    assert snap["after"]["self_s"] == snap["after"]["total_s"] == 1.0


def test_counters_and_delta(clock):
    sp = Spans()
    with sp.span("a"):
        pass
    sp.count("scanned", 40)
    before = sp.snapshot()
    with sp.span("b"):
        pass
    sp.count("scanned", 2)
    sp.count("admitted")
    d = delta(before, sp.snapshot())
    assert d == {"spans": {"b": {"count": 1, "total_s": 1.0, "self_s": 1.0}},
                 "counts": {"scanned": 2, "admitted": 1}}
    assert delta(before, before) == {"spans": {}, "counts": {}}
