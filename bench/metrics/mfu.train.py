"""Model FLOP utilisation of training: three forwards' operations per
pair (bench/flops.py; Algorithm 1's recomputed forward not counted) times
the window's pairs per second, over the chips' bf16 peak, in %."""


def compute(data, trace, peaks):
    if not data.get("pairs_per_s"):
        return None
    return 100.0 * data["flops_per_pair"] * data["pairs_per_s"] / (
        data["chips"] * peaks["bf16_flops_per_s"])
