"""Device memory the timed training step holds on each chip while it
runs: the compiled step's arguments, temporaries and outputs that do not
reuse a donated argument (its memory analysis), in GiB."""


def compute(data, trace, peaks):
    b = data.get("step_footprint_bytes")
    return b / 2 ** 30 if b else None
