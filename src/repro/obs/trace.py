"""Program spans on the profiler's clock, kept also in a ring buffer.

``with span(tracer, "train/dispatch", step=i):`` opens a
``jax.profiler.TraceAnnotation`` named ``repro/train/dispatch`` with the
span's arguments, whatever ``tracer`` is. Any profiler capture — a
``jax.profiler.start_trace`` or an operator's client attached to
``jax.profiler.start_server`` — then shows the program's spans on the same
clock as the device's ops, each on the row of the thread that ran it. With
no profiler attached a TraceMe costs about a microsecond.

Where a ``Tracer`` is given, the span also goes into its bounded ring
buffer (``capacity`` events, the newest win, ``dropped`` counts the
evicted), timestamped from ``time.time_ns()``. ``to_chrome_trace()``
renders the buffer as Chrome ``trace_event`` JSON (the ``{"traceEvents":
[...]}`` object form) that loads in Perfetto / ``chrome://tracing``; the
health monitor's flight recorder dumps it. ``pid`` is the OS process id
and ``tid`` a small per-tracer id of the recording OS thread.

Every span carries the step (or request) it belongs to as an argument, so
that the spans of one step can be joined across threads.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from jax import profiler as _profiler

REQUIRED_EVENT_KEYS = ("ph", "ts", "dur", "pid", "tid", "name")
PREFIX = "repro/"          # profiler name of span ``name``: PREFIX + name


class Tracer:
    """Ring-buffered span recorder with Chrome ``trace_event`` export."""

    def __init__(self, capacity: int = 8192):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._events: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._tids: dict = {}
        self._pid = os.getpid()
        self.dropped = 0

    # -- recording ---------------------------------------------------------
    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
            return tid

    def _append(self, ph: str, name: str, t0_ns: int, t1_ns: int,
                args: dict) -> None:
        event = {"ph": ph, "name": str(name), "ts": t0_ns / 1e3,
                 "dur": (t1_ns - t0_ns) / 1e3, "pid": self._pid,
                 "tid": self._tid()}
        if ph == "i":
            event["s"] = "t"
        if args:
            event["args"] = {k: _jsonable(v) for k, v in args.items()}
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(event)

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration marker (anomaly, checkpoint published)."""
        now = time.time_ns()
        self._append("i", name, now, now, args)

    # -- export ------------------------------------------------------------
    def events(self) -> list:
        """The buffered events, oldest first (copies — safe to mutate)."""
        with self._lock:
            return [dict(e) for e in self._events]

    def to_chrome_trace(self) -> dict:
        """Chrome ``trace_event`` object form of the buffered events. The
        top-level ``metadata`` object reports ``dropped`` (events evicted
        past ``capacity`` — a nonzero value means the timeline is
        truncated at the old end) alongside ``capacity`` and the exported
        event count."""
        with self._lock:
            events = [dict(e) for e in self._events]
            dropped = self.dropped
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "metadata": {"dropped": dropped, "capacity": self.capacity,
                             "events": len(events)}}

    def export(self, path: str) -> str:
        """Write ``to_chrome_trace()`` JSON to ``path``; returns the
        path (point Perfetto's "Open trace file" at it)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
        return path


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


@contextlib.contextmanager
def span(tracer: Optional[Tracer], name: str, **args):
    """A ``TraceAnnotation`` named ``PREFIX + name`` with ``args`` around
    the ``with`` body, recorded into ``tracer``'s ring buffer as well when
    ``tracer`` is a ``Tracer``. Yields ``tracer``."""
    with _profiler.TraceAnnotation(PREFIX + name, **args):
        if tracer is None:
            yield None
            return
        t0 = time.time_ns()
        try:
            yield tracer
        finally:
            tracer._append("X", name, t0, time.time_ns(), args)
