"""Contrastive-kernel perf bench: reference vs legacy 4-pass vs fused 2-pass.

Times three implementations of the paper's contrastive loss (DESIGN.md §5) —

  ref    : materializing jnp oracle (``ref.loss_and_grads_ref``)
  old4   : legacy blockwise path, 4 Pallas launches (2 fwd + 2 bwd sweeps)
  fused2 : single-pass blockwise path, 2 Pallas launches (DESIGN.md §2.3)

— for forward and forward+backward over B ∈ {512, 2048, 8192} and
D ∈ {256, 1024}, reporting µs/call and effective GB/s against the ideal
Θ(B·D) traffic model (X/Y reads + gradient writes; the B×B matrix is free
in the blockwise paths). On accelerators the kernels run compiled
(interpret=False); on CPU they run jit-compiled in interpret mode.

``run(json_path=...)`` additionally emits BENCH_kernels.json, the committed
perf trajectory that scripts/check_bench.py regresses against.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from benchmarks.common import csv_line, write_json  # noqa: F401 (run.py API)
from benchmarks.common import timeit_min as _timeit
from repro.kernels.contrastive_loss import ops, ref
from repro.kernels.contrastive_loss.ops import pick_blocks

SHAPES = [(512, 256), (512, 1024), (2048, 256), (2048, 1024),
          (8192, 256), (8192, 1024)]
LOG_TAU = -1.0


def _ideal_bytes(b, d, itemsize, with_grads):
    reads = 2 * b * d * itemsize              # X and Y streamed once
    writes = 2 * b * 4                        # row/col LSE
    if with_grads:
        writes += 2 * b * d * 4               # dX, dY (fp32)
    return reads + writes


def _paths(b, d, interpret):
    """name -> (fwd_fn, fwdbwd_fn), all jitted, taking (x, y, log_tau)."""
    bm, bn = pick_blocks(b, d, 4)
    fused = lambda x, y, t: ops.fused_contrastive_loss(   # noqa: E731
        x, y, t, interpret, bm, bn)
    return {
        "ref": (
            jax.jit(ref.loss_ref),
            jax.jit(ref.loss_and_grads_ref),
        ),
        "old4": (
            jax.jit(lambda x, y, t: ops.fused_loss_and_lse_4pass(
                x, y, t, interpret, bm, bn)[0]),
            jax.jit(lambda x, y, t: ops.fused_contrastive_loss_4pass(
                x, y, t, interpret, bm, bn)),
        ),
        "fused2": (
            jax.jit(fused),
            jax.jit(jax.value_and_grad(fused, argnums=(0, 1, 2))),
        ),
    }


def run(json_path: str | None = None, shapes=None) -> dict:
    interpret = jax.default_backend() == "cpu"
    entries = {}
    for b, d in (shapes or SHAPES):
        k1, k2 = jax.random.split(jax.random.key(b + d))
        x = jax.random.normal(k1, (b, d), jnp.float32)
        y = jax.random.normal(k2, (b, d), jnp.float32)
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        y = y / jnp.linalg.norm(y, axis=1, keepdims=True)
        log_tau = jnp.asarray(LOG_TAU)
        iters = 2 if b >= 8192 else 5
        # on compiled backends ops._bwd falls back to the legacy two-sweep
        # backward when the dY carrier won't fit VMEM (DESIGN.md §2.3);
        # record the launch count so a fused2 entry that actually measured
        # the fallback (3 launches) is visible in the committed trajectory.
        fused_launches = 2 if ops.backward_sweep(
            b, d, 4, interpret=interpret) == "fused" else 3
        for name, (fwd, fwdbwd) in _paths(b, d, interpret).items():
            for tag, fn in (("fwd", fwd), ("fwdbwd", fwdbwd)):
                us = _timeit(fn, x, y, log_tau, iters=iters)
                gbps = _ideal_bytes(b, d, 4, tag == "fwdbwd") / (us * 1e-6) / 1e9
                key = f"{name}/B{b}_D{d}/{tag}"
                entries[key] = {"us": round(us, 1), "gbps": round(gbps, 3)}
                if name == "fused2" and tag == "fwdbwd":
                    entries[key]["launches"] = fused_launches
                csv_line(f"kernels/{key}", us, f"{gbps:.3f}GB/s")

    result = {
        "meta": {
            "backend": jax.default_backend(),
            "interpret": interpret,
            "shapes": [list(s) for s in (shapes or SHAPES)],
            "traffic_model": "ideal 2BD reads + grad writes (DESIGN.md §5)",
        },
        "entries": entries,
    }
    if json_path:
        write_json(json_path, result)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write BENCH_kernels.json-style output here")
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes only (CI sanity, not a baseline)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(json_path=args.json,
        shapes=[(512, 256), (512, 1024)] if args.smoke else None)


if __name__ == "__main__":
    main()
