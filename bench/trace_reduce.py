"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- busy and idle time of each device: the union of its op intervals,
  clipped to the window, and what is left of the window;
- device time by op name;
- exposed collective time: collective op time on a device during which no
  other op of that device runs;
- the longest idle gaps, each labelled by the benchmark's own host span
  (``jax.profiler.TraceAnnotation`` names starting ``bench/``) that
  covers most of it.

Device ops are the innermost events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane (a loop's event spans its body's events, which
are counted instead); their names are the HLO instruction text. A trace recorded on the CPU has no device
plane; there the ops are the events of the XLA CPU client's threads, so
the same reduction can be checked without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|psum")
SPAN_PREFIX = "bench/"


@dataclasses.dataclass
class Trace:
    devices: dict        # device id -> [(name, start_ns, end_ns)], leaf ops
    spans: list          # [(name, start_ns, end_ns)] benchmark host spans
    window: tuple        # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _cpu_op(name: str, dur: float) -> bool:
    return dur > 0 and not name.startswith(("end: ", "ThreadpoolListener"))


def leaves(ops) -> list:
    """The ops that contain no other op: a loop or a call whose body's
    ops are events of their own is dropped, so time is not counted
    twice. ``ops`` sorted by start."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ops)
    stack = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(ops, parent) if not p]


def short_name(name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> '%fusion.12'."""
    return name.split(" = ", 1)[0]


def options():
    """Profiler options: device ops and the benchmark's own host spans,
    without Python function tracing or HLO protos."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    o.enable_hlo_proto = False
    return o


def load(path: str, window_span: str = SPAN_PREFIX + "window") -> Trace:
    """Read a trace. The window runs from the start of the benchmark's
    ``window_span`` host span (the trace's first event where there is
    none) to the end of the last device op."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans, cpu_ops = {}, [], []
    for plane in pd.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif line.name.startswith("tf_XLAPjRtCpuClient") \
                            and _cpu_op(e.name, e.duration_ns):
                        cpu_ops.append((e.name, e.start_ns, e.end_ns))
    if not devices and cpu_ops:
        devices = {0: cpu_ops}
    devices = {d: leaves(ops) for d, ops in devices.items()}
    starts = [s for n, s, _ in spans if n == window_span]
    all_ops = [o for ops in devices.values() for o in ops]
    t0 = min(starts) if starts else min(
        [o[1] for o in all_ops] + [s[1] for s in spans])
    t1 = max(o[2] for o in all_ops) if all_ops else t0
    return Trace(devices, spans, (t0, max(t0, t1)))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals, lo=None, hi=None) -> list:
    """Sorted disjoint union of (start, end) intervals, clipped to
    [lo, hi] where given."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def busy_s(tr: Trace) -> dict:
    """{device: seconds in the window during which any op ran}."""
    lo, hi = tr.window
    return {d: length(union([(s, e) for _, s, e in ops], lo, hi)) / 1e9
            for d, ops in tr.devices.items()}


def mean_busy_s(tr: Trace) -> float:
    b = busy_s(tr)
    return sum(b.values()) / len(b) if b else 0.0


def op_seconds(tr: Trace) -> dict:
    """{op name: device seconds in the window}, averaged over devices."""
    lo, hi = tr.window
    tot = {}
    for ops in tr.devices.values():
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                tot[name] = tot.get(name, 0.0) + d / 1e9
    n = max(1, len(tr.devices))
    return {k: v / n for k, v in tot.items()}


def exposed_collective_s(tr: Trace) -> dict:
    """{device: seconds of collective ops not overlapped by other ops}."""
    lo, hi = tr.window
    out = {}
    for d, ops in tr.devices.items():
        coll = union([(s, e) for n, s, e in ops if COLLECTIVE.search(n)],
                     lo, hi)
        other = union([(s, e) for n, s, e in ops
                       if not COLLECTIVE.search(n)], lo, hi)
        out[d] = length(subtract(coll, other)) / 1e9
    return out


def idle_gaps(tr: Trace, top: int = 10) -> list:
    """The ``top`` longest idle gaps over all devices, as
    [label, seconds]; the label is the benchmark host span covering most
    of the gap, or 'no bench span'."""
    lo, hi = tr.window
    gaps = []
    for ops in tr.devices.values():
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        gaps.extend(subtract([(lo, hi)], busy))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        best, cover = "no bench span", 0.0
        for name, a, b in tr.spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        out.append([best, (e - s) / 1e9])
    return out


def top_ops(tr: Trace, top: int = 10) -> list:
    """The ``top`` op names (short) by device seconds in the window."""
    tot = {}
    for name, sec in op_seconds(tr).items():
        tot[short_name(name)] = tot.get(short_name(name), 0.0) + sec
    ops = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[n, s] for n, s in ops[:top]]


def op_counts(tr: Trace) -> dict:
    """{op name: calls starting in the window}, averaged over devices."""
    lo, hi = tr.window
    cnt = {}
    for ops in tr.devices.values():
        for name, s, _ in ops:
            if lo <= s < hi:
                cnt[name] = cnt.get(name, 0) + 1
    n = max(1, len(tr.devices))
    return {k: v / n for k, v in cnt.items()}


_OPERANDS = re.compile(r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
_SHAPE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


def kernel_operands(name: str):
    """Operand shapes of a Pallas kernel's op (``tpu_custom_call``), as
    tuples, or None for any other op."""
    if "tpu_custom_call" not in name:
        return None
    m = _OPERANDS.search(name)
    if not m:
        return None
    return [tuple(int(x) for x in s.split(",") if x)
            for s in _SHAPE.findall(m.group(1))]
