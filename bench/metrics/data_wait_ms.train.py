"""Mean time a window step waited for its batch (run log ``data_wait_s``:
host clock around the loop's ``next(stream)``), in ms per step."""


def compute(data, trace, peaks):
    w = data.get("data_wait_s")
    return 1e3 * sum(w) / len(w) if w else None
