"""The runtime side of the instrumentation seam: spans and counters cost a
shared no-op while no recorder is on, and record nesting, parents, counts
and published counters while one is."""
import threading

import pytest

from repro.core import events


def test_off_records_nothing_and_shares_the_null_span():
    assert events.span("engine.admit") is events.span("rpc.drain")
    with events.span("engine.admit"):
        events.count("engine.ticks")
    with events.recording() as rec:
        pass
    assert rec.spans == [] and rec.deltas.get("engine.ticks", 0) == 0


def test_on_records_nesting_parents_and_counters():
    with events.recording() as rec:
        with events.span("outer"):
            events.count("hits")
            with events.span("inner"):
                events.count("hits", 2)
        with events.span("named", parent="elsewhere"):
            pass
    names = [(n, p) for n, _, _, p in rec.spans]
    assert names == [("inner", "outer"), ("outer", None),
                     ("named", "elsewhere")]
    (_, s_in, e_in, _), (_, s_out, e_out, _) = rec.spans[:2]
    assert s_out <= s_in <= e_in <= e_out
    assert rec.counters == {"hits": 3}
    assert rec.deltas["hits"] == 3
    # off again: the null span, no counting into the old recorder
    assert events.span("outer") is events.span("x")
    events.count("hits")
    assert rec.counters == {"hits": 3}


def test_spans_on_other_threads_record_without_a_parent():
    with events.recording() as rec:
        with events.span("tick"):
            def drain():
                with events.span("drain"):
                    pass
            t = threading.Thread(target=drain)
            t.start()
            t.join()
    assert sorted((n, p) for n, _, _, p in rec.spans) == [
        ("drain", None), ("tick", None)]


def test_published_counters_change_over_the_recording():
    box = {"n": 5}
    events.publish("test_source", lambda: {"n": box["n"]})
    try:
        with events.recording() as rec:
            box["n"] += 4
        assert rec.before["test_source.n"] == 5
        assert rec.deltas["test_source.n"] == 4
    finally:
        events._PUBLISHED.pop("test_source")


def test_one_recorder_at_a_time():
    with events.recording():
        with pytest.raises(RuntimeError):
            with events.recording():
                pass
