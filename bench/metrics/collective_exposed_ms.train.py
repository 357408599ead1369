"""Collective time per step during which no other op ran on the chip,
on the worst chip, in ms per step."""
from bench import trace_reduce as TR


def compute(data, trace, peaks):
    exp = TR.exposed_collective_s(trace)
    if not exp or not data.get("steps") or data.get("chips", 1) < 2:
        return None
    return 1e3 * max(exp.values()) / data["steps"]
