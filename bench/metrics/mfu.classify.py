"""Useful operations served over what the chip could do while busy: the
requests' real images through the image tower and the similarity against
every class (bench/flops.py), over device busy time from the trace times
the bf16 peak, in %. Padding rows and idle time do not count."""
from bench import trace_reduce as TR


def compute(data, trace, peaks):
    busy = TR.mean_busy_s(trace)
    n_img = sum(data["sizes"])
    if busy <= 0 or not n_img:
        return None
    ops = n_img * (data["image_flops"]
                   + 2 * data["classes"] * data["embed_dim"])
    return 100.0 * ops / (busy * data["chips"] * peaks["bf16_flops_per_s"])
