"""The trace reduction on a small synthetic trace."""
import pytest

from bench import trace

MS = 1_000_000


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [("%k.1", 0, 4 * MS, 0), ("%copy.2", 2 * MS, 6 * MS, 0),
           ("%k.1", 8 * MS, 9 * MS, 0), ("%late", 12 * MS, 20 * MS, 0)]
    r = trace.reduce(ops, [], (1 * MS, 10 * MS))
    assert r["window_s"] == pytest.approx(9e-3)
    assert r["busy_s"] == pytest.approx(6e-3)       # [1,6] and [8,9]
    assert r["op_s"] == pytest.approx({"%k.1": 4e-3, "%copy.2": 4e-3})


def test_idle_gaps_go_to_the_innermost_open_span():
    ops = [("%k", 2 * MS, 3 * MS, 0), ("%k", 7 * MS, 8 * MS, 0)]
    spans = [("window", 0, 10 * MS), ("query", 1 * MS, 9 * MS),
             ("lower", 1 * MS, 2 * MS), ("dse_call", 2 * MS, 9 * MS)]
    r = trace.reduce(ops, spans, (0, 10 * MS))
    assert r["busy_s"] == pytest.approx(2e-3)
    assert r["idle_s"] == pytest.approx(
        {"window": 2e-3, "lower": 1e-3, "dse_call": 5e-3})
    assert sum(r["idle_s"].values()) + r["busy_s"] == pytest.approx(1e-2)


def test_busy_averages_over_devices_and_gaps_outside_spans_are_none():
    ops = [("%a", 0, 4 * MS, 0), ("%a", 0, 2 * MS, 1)]
    r = trace.reduce(ops, [], (0, 8 * MS), devices=2)
    assert r["busy_s"] == pytest.approx(3e-3)
    assert r["idle_s"] == pytest.approx({"none": 4e-3})
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                          ["c", 2.0]]
