"""Share of the traced window's device idle time (no op running,
trace_reduce) during which the prefetch thread was rendering a batch
(``repro/data/render``; bench/program_spans.py), in %."""
from bench import program_spans as PS


def compute(data, trace, peaks):
    return PS.idle_share_under(trace, PS.for_trace(trace),
                               "repro/data/render")
