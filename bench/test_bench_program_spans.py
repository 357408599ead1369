"""The program-span metrics: on hand-built device ops and spans with known
answers, on a trace without the program's spans (None), and on a small
CPU trace of the training loop (bench/testdata/record_loop_trace.py)."""
import os
import shutil

import pytest

from bench import harness as H
from bench import program_spans as PS
from bench import trace_reduce as TR

TESTDATA = os.path.join(H.BENCH, "testdata")
LOOP_TRACE = os.path.join(TESTDATA, "cpu_loop_trace.xplane.pb")
OTHER_TRACE = os.path.join(TESTDATA, "cpu_trace.xplane.pb")
METRICS = ("dispatch_ms.train", "idle_in_dispatch_share.train",
           "batch_render_ms.train", "batch_put_ms.train",
           "idle_in_render_share.train")
MS = 1e6


def compute(name, trace):
    return H.load_module("metrics", name).compute({}, trace, {})


def _hand_built():
    """Window 0-100 ms; the device idles 20-40 and 60-80 (40 ms)."""
    ops = {0: [("fusion.1", 0, 20 * MS), ("fusion.2", 40 * MS, 60 * MS),
               ("fusion.3", 80 * MS, 100 * MS)]}
    trace = TR.Trace(ops, [], (0, 100 * MS))

    def span(name, thread, a, b, step):
        return PS.Span(name, thread, a * MS, b * MS, {"step": step})
    spans = [
        span("repro/train/dispatch", 0, -8, -2, 0),   # before the window
        span("repro/train/dispatch", 0, 20, 30, 1),   # half of 20-40
        span("repro/train/dispatch", 0, 60, 80, 2),   # all of 60-80
        span("repro/data/render", 1, 0, 30, 2),       # 20-30 of the idle
        span("repro/data/render", 1, 55, 65, 3),      # 60-65 of the idle
        span("repro/data/put", 1, 30, 34, 2),
        span("repro/data/put", 1, 65, 71, 3),
    ]
    return trace, spans


def test_each_metric_on_hand_built_spans(monkeypatch):
    trace, spans = _hand_built()
    monkeypatch.setattr(PS, "for_trace", lambda tr, root=None: spans)
    assert compute("dispatch_ms.train", trace) == pytest.approx(15.0)
    assert compute("idle_in_dispatch_share.train", trace) == \
        pytest.approx(100 * 30 / 40)
    assert compute("batch_render_ms.train", trace) == pytest.approx(20.0)
    assert compute("batch_put_ms.train", trace) == pytest.approx(5.0)
    assert compute("idle_in_render_share.train", trace) == \
        pytest.approx(100 * 15 / 40)


def test_idle_gap_half_under_dispatch_reads_half():
    ops = {0: [("a", 0, 10 * MS), ("b", 30 * MS, 40 * MS)]}
    trace = TR.Trace(ops, [], (0, 40 * MS))
    spans = [PS.Span("repro/train/dispatch", 0, 10 * MS, 20 * MS, {})]
    assert PS.idle_share_under(trace, spans, "repro/train/dispatch") == \
        pytest.approx(50.0)
    # two devices: the same span covers half of each one's idle time
    ops[1] = [("a", 0, 15 * MS), ("b", 25 * MS, 40 * MS)]
    got = PS.idle_share_under(TR.Trace(ops, [], (0, 40 * MS)), spans,
                              "repro/train/dispatch")
    assert got == pytest.approx(100 * (10 + 5) / (20 + 10))


def test_no_program_spans_reads_none(monkeypatch, tmp_path):
    trace, _ = _hand_built()
    monkeypatch.setattr(PS, "for_trace", lambda tr, root=None: [])
    assert all(compute(m, trace) is None for m in METRICS)
    # a recorded trace without the program's spans, found by its window
    monkeypatch.undo()
    monkeypatch.setattr(H, "RUNS_DIR", str(tmp_path))
    shutil.copy(OTHER_TRACE, tmp_path / "run.xplane.pb")
    trace = TR.load(OTHER_TRACE)
    assert PS.for_trace(trace) == []
    assert all(compute(m, trace) is None for m in METRICS)


def test_recorded_loop_trace_reads_every_metric(monkeypatch, tmp_path):
    """The metrics find the run's trace under the runs directory by its
    window's start, beside another run's, and all read a value."""
    monkeypatch.setattr(H, "RUNS_DIR", str(tmp_path))
    os.makedirs(tmp_path / "a" / "trace")
    os.makedirs(tmp_path / "b" / "trace")
    shutil.copy(OTHER_TRACE, tmp_path / "a" / "trace" / "x.xplane.pb")
    shutil.copy(LOOP_TRACE, tmp_path / "b" / "trace" / "x.xplane.pb")
    trace = TR.load(LOOP_TRACE)
    got = {m: compute(m, trace) for m in METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["dispatch_ms.train"] > 0 and got["batch_render_ms.train"] > 0
    for m in ("idle_in_dispatch_share.train", "idle_in_render_share.train"):
        assert 0 <= got[m] <= 100

    spans = PS.for_trace(trace)
    main = {s.thread for s in spans if s.name.startswith("repro/train/")}
    loader = {s.thread for s in spans if s.name.startswith("repro/data/")}
    assert len(main) == 1 and len(loader) == 1 and main != loader
    out = PS.breakdown(trace, spans)
    assert out["steps"] >= 2
    # the main thread's phases add up to the step period
    assert out["main_sum_ms"] == pytest.approx(out["step_period_ms"],
                                               rel=0.05)
