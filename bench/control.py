#!/usr/bin/env python3
"""The readings that set a cell's limits, on the chip, at the cell's size.

    python3 bench/control.py --workload basic-s.train --seeds 11 12 13

Training cells: for each seed, the reference runs in the program's place
in the control numerics (float8 operands and gradients: one step below
the bfloat16 the configuration states; the program has no such path of
its own), and once with each fault a step can have (half of the batch
left out; on several chips, the exchange between chips left out).
Classify cells: the program has a path one step below the float32 it
serves in, its own ``bf16`` precision policy; the control is a run of the
cell at its own load with that path switched on, beside a run at the
configuration's own precision on the same seed. Each prints every number
the cell could compare, against the float32 reference, as one JSON line.
The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness as H  # noqa: E402


def train_batches(cfg, tr, seed, chips, steps):
    """The batches the trainer feeds its first ``steps`` steps: its own
    loader, built as ``train_distributed`` builds it."""
    import numpy as np
    from repro.configs import get_arch, smoke_dual_variant
    from repro.data import world_for_tower
    from repro.data.sharded import HostLayout, ShardedLoader, load_tokenizer
    pc = get_arch(cfg["arch"])
    if cfg.get("smoke"):
        pc = smoke_dual_variant(pc)
    world = world_for_tower(np.random.default_rng(seed), pc.image_tower,
                            n_classes=16, noise=0.2)
    loader = ShardedLoader(world, load_tokenizer("v1"), tr["batch"],
                           layout=HostLayout(n_hosts=chips), seed=seed,
                           text_len=tr["text_len"])
    return [loader.global_batch_at(s) for s in range(steps)]


def train_readings(cfg, wl, seed, devices, control="fp8"):
    import jax
    import numpy as np
    from bench.drivers.train import COMPARED_STEPS, gaps
    from bench.reference import model as M
    from bench.reference import train as RT
    chips = len(devices)
    mesh = jax.sharding.Mesh(np.array(devices), ("rows",)) \
        if chips > 1 else None
    batches = train_batches(cfg, wl["traffic"], seed, chips,
                            COMPARED_STEPS)
    rows = cfg["reference"]["block_rows"]
    ref = RT.run_steps(cfg, seed, batches, block_rows=rows, mesh=mesh)
    med = float(np.median(list(ref["grad0"].values())))
    moving = {k for k, v in ref["grad0"].items() if v >= 1e-3 * med}
    runs = {control: dict(num=M.NUMERICS[control])}
    runs["half_batch"] = dict(fault="half_batch")
    if chips > 1:
        runs["no_exchange"] = dict(fault=f"no_exchange:{chips}")
    out = []
    for name, kw in runs.items():
        got = RT.run_steps(cfg, seed, batches, block_rows=rows, mesh=mesh,
                           **kw)
        out.append({
            "seed": seed, "run": name,
            "loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(got["loss"], ref["loss"])),
            "grad_gap": gaps(got["grad0"], ref["grad0"])[0],
            "change_gap": gaps(got["change"], ref["change"], moving)[0]})
    return out


def classify_readings(cfg, wl, seed, devices, seconds,
                      runs=("bf16",)) -> list:
    """Runs of the cell at its own load, one for each serving precision
    in ``runs`` (the configuration's own gives the program's readings);
    every number the cell could compare, from each run."""
    import time
    from bench import run as R
    out = []
    for prec in runs:
        ctx = R.Ctx(wl, dict(cfg, precision=dict(cfg["precision"],
                                                  serve=prec)),
                    seed, seconds, False, time.time(), devices,
                    os.path.join(H.RUNS_DIR, wl["name"] + ".control"))
        rec = H.load_module("drivers", wl["kind"]).run(ctx)
        out.append(dict({"seed": seed, "run": prec, "failed": rec.failed},
                        **rec.data["readings"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window of a classify control run")
    args = ap.parse_args(argv)
    wl = H.load_workload(args.workload)
    cfg = H.load_config(wl["config"])
    sys.path.insert(0, H.SRC)
    H.enable_compile_cache()
    try:
        devs = H.require_chips(int(wl["chips"]))
    except H.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        seed = H.program_seed(seed)
        if wl["kind"] == "train":
            lines = train_readings(cfg, wl, seed, devs)
        else:
            lines = classify_readings(cfg, wl, seed, devs, args.seconds,
                                      (cfg["precision"]["serve"], "bf16"))
        for line in lines:
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
