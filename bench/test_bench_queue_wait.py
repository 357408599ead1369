"""``queue_wait_ms.train`` on hand-built spans: the mean of the
``repro/data/queue_wait`` spans that start in the window, and None (left
out of the line) where the program emits none."""
import pytest

from bench import harness as H
from bench import program_spans as PS
from bench import trace_reduce as TR

MS = 1e6


def compute(trace):
    return H.load_module("metrics", "queue_wait_ms.train").compute(
        {}, trace, {})


def _trace():
    ops = {0: [("fusion.1", 0, 40 * MS), ("fusion.2", 50 * MS, 100 * MS)]}
    return TR.Trace(ops, [], (0, 100 * MS))


def _span(name, a, b, step):
    return PS.Span(name, 1, a * MS, b * MS, {"step": step})


def test_mean_of_the_window_queue_waits(monkeypatch):
    spans = [
        _span("repro/data/queue_wait", -30, -5, 0),   # before the window
        _span("repro/data/render", 0, 10, 1),
        _span("repro/data/queue_wait", 10, 40, 1),
        _span("repro/data/render", 40, 50, 2),
        _span("repro/data/queue_wait", 50, 60, 2),
        _span("repro/data/queue_wait", 95, 130, 3),   # starts inside
        _span("repro/data/queue_wait", 100, 110, 4),  # at its end: out
    ]
    monkeypatch.setattr(PS, "for_trace", lambda tr, root=None: spans)
    assert compute(_trace()) == pytest.approx((30 + 10 + 35) / 3)


def test_without_queue_wait_spans_reads_none(monkeypatch):
    spans = [_span("repro/data/render", 0, 10, 1),
             _span("repro/data/put", 10, 12, 1)]
    monkeypatch.setattr(PS, "for_trace", lambda tr, root=None: spans)
    assert compute(_trace()) is None
    monkeypatch.setattr(PS, "for_trace", lambda tr, root=None: [])
    assert compute(_trace()) is None
