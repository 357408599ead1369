"""Mean time the prefetch thread waited for room in the loader's queue
with a finished batch (the program's ``repro/data/queue_wait`` spans
starting in the traced window; bench/program_spans.py), in ms per batch.
Near 0 the loader sets the loop's pace; well above 0 it runs ahead."""
from bench import program_spans as PS


def compute(data, trace, peaks):
    return PS.mean_ms(PS.for_trace(trace), "repro/data/queue_wait",
                      trace.window)
