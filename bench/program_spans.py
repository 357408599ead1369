"""The program's own spans in a traced run, and what the program-span
metrics read from them.

The program opens ``jax.profiler.TraceAnnotation("repro/<name>",
step=...)`` around its phases (``src/repro/obs/trace.py``): the train
loop's ``repro/train/{data_wait,dispatch,wait,ckpt_stall,log}`` on the
main thread, and the prefetch thread's ``repro/data/render`` (with the
per-host ``repro/data/host_block`` inside) and ``repro/data/put``. They are
host events of the ``.xplane.pb`` that ``trace_reduce.load`` read for the
run. The ``Trace`` a metric is handed carries no path, so ``for_trace``
finds the file again under ``harness.RUNS_DIR``: the one whose
``bench/window`` span starts at ``trace.window[0]``. A run of a program
that emits no such spans gives an empty list, and the metrics read None.

    python3 bench/program_spans.py <run.xplane.pb>

prints the traced window's breakdown as one JSON object: per step, the
main thread's spans against the step period, and the device's idle time
under each span.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import harness as H  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402

PREFIX = "repro/"
MAIN = ("repro/train/data_wait", "repro/train/dispatch", "repro/train/wait",
        "repro/train/log", "repro/train/ckpt_stall")
PREFETCH = ("repro/data/render", "repro/data/host_block", "repro/data/put")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    thread: int          # the row of the host plane it was recorded on
    start: float         # ns, on the trace's clock
    end: float
    args: dict


def load(path: str) -> tuple:
    """(start of the ``bench/window`` span or None, [Span]) of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, spans, row = None, [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name, row, e.start_ns, e.end_ns,
                                      dict(e.stats)))
                elif e.name == TR.SPAN_PREFIX + "window" and \
                        (window is None or e.start_ns < window):
                    window = e.start_ns
            row += 1
    spans.sort(key=lambda s: s.start)
    return window, spans


_LOADED: dict = {}


def for_trace(trace, root: str | None = None) -> list:
    """The program's spans of the run whose trace ``trace`` is: found
    under ``root`` (default ``harness.RUNS_DIR``) by the start of its
    window, newest file first; [] where no trace there matches or it
    holds none."""
    paths = glob.glob(os.path.join(root or H.RUNS_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        key = (path, os.path.getmtime(path))
        if key not in _LOADED:
            _LOADED[key] = load(path)
        window, spans = _LOADED[key]
        if window is not None and window == trace.window[0]:
            return spans
    return []


def in_window(spans, names, window) -> list:
    """The spans named in ``names`` (a name or a tuple) that start inside
    ``window``."""
    names = (names,) if isinstance(names, str) else tuple(names)
    lo, hi = window
    return [s for s in spans if s.name in names and lo <= s.start < hi]


def mean_ms(spans, name, window):
    """Mean duration of the ``name`` spans starting in ``window``, in ms;
    None where there are none."""
    sel = in_window(spans, name, window)
    if not sel:
        return None
    return sum(s.end - s.start for s in sel) / len(sel) / 1e6


def idle_intervals(trace) -> dict:
    """{device: [(start, end)]} of the window's device idle time."""
    lo, hi = trace.window
    return {d: TR.subtract([(lo, hi)],
                           TR.union([(s, e) for _, s, e in ops], lo, hi))
            for d, ops in trace.devices.items()}


def idle_share_under(trace, spans, names):
    """Share of the window's device idle time (all devices) during which
    a thread was inside one of the ``names`` spans that start in the
    window, in %; None where there are no such spans or no idle time."""
    sel = in_window(spans, names, trace.window)
    gaps = idle_intervals(trace).values()
    total = sum(TR.length(g) for g in gaps)
    if not sel or total <= 0:
        return None
    cover = TR.union([(s.start, s.end) for s in sel])
    uncovered = sum(TR.length(TR.subtract(g, cover)) for g in gaps)
    return 100.0 * (total - uncovered) / total


def breakdown(trace, spans) -> dict:
    """The window per step: the steps whose ``dispatch`` starts in it,
    their period (between the first and the last ``dispatch`` start), the
    ms per step of each program span of those steps, how many of each
    span start in the window per step and their mean ms, the device's
    idle time per step, the share of it under each span, and the thread
    rows each span ran on."""
    w = trace.window
    dispatch = in_window(spans, "repro/train/dispatch", w)
    steps = {s.args.get("step") for s in dispatch}
    n = max(1, len(steps))
    out = {"window_s": trace.window_s, "steps": len(steps)}
    if len(dispatch) >= 2:
        out["step_period_ms"] = (dispatch[-1].start - dispatch[0].start) \
            / (len(dispatch) - 1) / 1e6
    out["per_step_ms"] = {
        name: sum(s.end - s.start for s in spans
                  if s.name == name and s.args.get("step") in steps) / n / 1e6
        for name in MAIN + PREFETCH}
    out["starts_per_step"] = {name: len(in_window(spans, name, w)) / n
                              for name in MAIN + PREFETCH}
    out["main_sum_ms"] = sum(out["per_step_ms"][m] for m in MAIN[:4])
    out["mean_ms"] = {m: mean_ms(spans, m, w) for m in MAIN + PREFETCH}
    idle = sum(TR.length(g) for g in idle_intervals(trace).values()) \
        / max(1, len(trace.devices))
    out["idle_ms_per_step"] = idle / 1e6 / n
    out["idle_share_under"] = {m: idle_share_under(trace, spans, m)
                               for m in MAIN + PREFETCH}
    out["idle_share_under"]["repro/train/*"] = idle_share_under(
        trace, spans, MAIN)
    out["threads"] = {m: sorted({s.thread for s in in_window(spans, m, w)})
                      for m in MAIN + PREFETCH}
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0]
    _, spans = load(path)
    print(json.dumps(breakdown(TR.load(path), spans), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
