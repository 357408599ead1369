"""Share of the traced classify window in which no op ran on the device
(averaged over chips), in %."""
from bench import trace_reduce as TR


def compute(data, trace, peaks):
    if trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - TR.mean_busy_s(trace) / trace.window_s)
