"""Mean time the train loop's main thread spent in the call into the
jitted step, until it returned (the program's ``repro/train/dispatch``
spans starting in the traced window; bench/program_spans.py), in ms per
step."""
from bench import program_spans as PS


def compute(data, trace, peaks):
    return PS.mean_ms(PS.for_trace(trace), "repro/train/dispatch",
                      trace.window)
