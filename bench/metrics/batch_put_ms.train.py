"""Mean time the prefetch thread spent handing one rendered batch to the
device (``device_put_global``: the program's ``repro/data/put`` spans
starting in the traced window; bench/program_spans.py), in ms per
batch."""
from bench import program_spans as PS


def compute(data, trace, peaks):
    return PS.mean_ms(PS.for_trace(trace), "repro/data/put", trace.window)
