"""The contrastive-loss kernels' share of their roofline: the least time
the chip needs for their calls in the window (operations over the bf16
peak or bytes over HBM bandwidth, whichever is larger; bench/flops.py)
over the device time of those calls, in %.

Each call sweeps one (rows, rows) tile of the similarity matrix: the
whole batch on one chip, one chip's rows against one gathered chunk when
the loss is split over chips. A call is a Pallas kernel op whose first
two operands are (rows, embed_dim) embeddings: three operands (X, Y,
1/tau) is the forward sweep, more (the log-sum-exps too) the backward."""
from bench import flops
from bench import trace_reduce as TR


def compute(data, trace, peaks):
    rows, d = data.get("loss_rows_local"), data.get("embed_dim")
    if not rows:
        return None
    need, took = 0.0, 0.0
    counts = TR.op_counts(trace)
    for name, sec in TR.op_seconds(trace).items():
        shapes = TR.kernel_operands(name)
        if not shapes or len(shapes) < 3 or shapes[0] != (rows, d) \
                or shapes[1] != (rows, d):
            continue
        cost = flops.contrastive_fwd_cost if len(shapes) == 3 \
            else flops.contrastive_bwd_cost
        ops, nbytes = cost(rows, rows, d)
        need += counts[name] * max(ops / peaks["bf16_flops_per_s"],
                                   nbytes / peaks["hbm_bytes_per_s"])
        took += sec
    return 100.0 * need / took if took > 0 else None
