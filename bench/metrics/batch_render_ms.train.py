"""Mean time the prefetch thread spent rendering one global batch in
numpy (the program's ``repro/data/render`` spans starting in the traced
window; bench/program_spans.py), in ms per batch."""
from bench import program_spans as PS


def compute(data, trace, peaks):
    return PS.mean_ms(PS.for_trace(trace), "repro/data/render",
                      trace.window)
