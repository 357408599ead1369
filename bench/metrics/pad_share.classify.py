"""Share of the image tower's batch rows that were padding (the
micro-batcher's ``padded_examples`` over encoded plus padded, window
delta), in %."""


def compute(data, trace, peaks):
    tot = data["encoded_examples"] + data["padded_examples"]
    return 100.0 * data["padded_examples"] / tot if tot else None
