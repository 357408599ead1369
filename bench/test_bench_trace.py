"""Trace reduction: interval arithmetic, and a small trace recorded on
the CPU (bench/testdata/record_trace.py)."""
import os

import pytest

from bench import trace_reduce as TR

HERE = os.path.dirname(os.path.abspath(__file__))
CPU_TRACE = os.path.join(HERE, "testdata", "cpu_trace.xplane.pb")


def test_union_merges_overlaps_and_clips():
    got = TR.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 21)], lo=1, hi=11)
    assert got == [(1, 4), (5, 11)]
    assert TR.length(got) == 9


def test_subtract_leaves_uncovered_parts():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (5, 25), (29, 40)]
    assert TR.subtract(a, b) == [(0, 2), (3, 5), (25, 29)]
    assert TR.subtract(a, []) == a
    assert TR.subtract([(0, 1)], [(0, 5)]) == []


def _synthetic():
    ops = {
        0: [("fusion.1", 0, 40), ("all-reduce.3", 30, 60),
            ("fusion.2", 50, 55), ("_fused_fwd_kernel", 70, 80)],
        1: [("fusion.1", 0, 20), ("all-gather.1", 20, 70)],
    }
    spans = [("bench/window", 0, 0), ("bench/data_wait", 58, 72),
             ("bench/dispatch", 60, 61)]
    return TR.Trace(ops, spans, (0, 100))


def test_busy_is_union_per_device():
    tr = _synthetic()
    assert TR.busy_s(tr) == {0: 70e-9, 1: 70e-9}
    assert TR.mean_busy_s(tr) == pytest.approx(70e-9)


def test_op_time_by_name_averages_devices():
    got = TR.op_seconds(_synthetic())
    assert got["fusion.1"] == pytest.approx((40 + 20) / 2 * 1e-9)
    assert got["all-gather.1"] == pytest.approx(25e-9)
    assert TR.op_counts(_synthetic())["fusion.1"] == 1


def test_exposed_collectives_exclude_overlapped_compute():
    got = TR.exposed_collective_s(_synthetic())
    # device 0: all-reduce 30-60, compute 0-40 and 50-55 -> 40-50, 55-60
    assert got[0] == pytest.approx(15e-9)
    # device 1: all-gather 20-70, no compute after 20
    assert got[1] == pytest.approx(50e-9)


def test_idle_gaps_are_labelled_by_covering_span():
    gaps = TR.idle_gaps(_synthetic(), top=3)
    # device 0 idles 60-70 and 80-100; device 1 idles 70-100
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 10e-9])
    assert gaps[2][0] == "bench/data_wait"
    assert gaps[0][0] == "bench/data_wait"   # 70-72 of 70-100
    assert gaps[1][0] == "no bench span"


def test_recorded_cpu_trace():
    tr = TR.load(CPU_TRACE)
    assert tr.devices and all(tr.devices.values())
    names = {s[0] for s in tr.spans}
    assert {"bench/window", "bench/gap"} <= names
    assert tr.window[0] == min(s[1] for s in tr.spans
                               if s[0] == "bench/window")
    busy = TR.mean_busy_s(tr)
    assert 0 < busy < tr.window_s
    gaps = TR.idle_gaps(tr, top=3)
    # three 20 ms host sleeps, each inside its span, no op running
    assert [g[0] for g in gaps] == ["bench/gap"] * 3
    assert all(0.018 < g[1] < 0.1 for g in gaps)
    assert any(n.startswith("dot") for n in TR.op_seconds(tr))
    assert TR.top_ops(tr, 2)[0][1] >= TR.top_ops(tr, 2)[1][1]
