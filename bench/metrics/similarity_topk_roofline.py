"""The fused similarity→top-k kernel's share of its roofline over the
window's classify calls: the least time the chip needs for each call
(operations over the bf16 peak or bytes over HBM bandwidth, whichever is
larger, at that request's image count; bench/flops.py) over the device
time of the calls, in %. A call is a Pallas kernel op whose second
operand is the padded (classes, embed_dim) class matrix; read only where
the trace holds one call per request answered."""
from bench import flops
from bench import trace_reduce as TR


def _is_topk(name, data):
    shapes = TR.kernel_operands(name)
    return bool(shapes) and len(shapes) >= 2 and len(shapes[1]) == 2 \
        and shapes[1][1] == data["embed_dim"] \
        and shapes[1][0] >= data["classes"]


def compute(data, trace, peaks):
    counts, secs = TR.op_counts(trace), TR.op_seconds(trace)
    names = [n for n in counts if _is_topk(n, data)]
    calls = sum(counts[n] for n in names)
    took = sum(secs.get(n, 0.0) for n in names)
    if not calls or calls != len(data["sizes"]) or took <= 0:
        return None
    need = 0.0
    for b in data["sizes"]:
        ops, nbytes = flops.topk_cost(b, data["classes"], data["embed_dim"],
                                      data["k"])
        need += max(ops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * need / took
