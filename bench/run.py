#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload basic-s.train --seed 7 --seconds 30 \\
        --trace 0

Loads ``bench/workloads/<cell>.json``, its configuration and its driver,
refuses to run anywhere but on as many TPU chips as the cell asks for,
runs set-up and then a window of ``--seconds``, checks what the window
produced against the plain reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, ``breakdown`` (traced runs) and ``checks``
(each compared number beside its limit; also the last lines on standard
error). Exits non-zero, printing no result, when it cannot run.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness as H  # noqa: E402


def process_start() -> float:
    """Wall-clock time this process started (Linux), else import time."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return min(T_IMPORT, time.time() - age)
    except (OSError, ValueError, IndexError):
        return T_IMPORT


@dataclasses.dataclass
class Ctx:
    """What a driver is given."""
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    run_dir: str


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, *, require_tpu=True, workload_dirs=None, config_dirs=None,
        manifest=None, t_start=None, peaks=None) -> dict:
    """One run; returns the result line. ``require_tpu=False`` skips the
    look for a chip (tests drive the rest of a run on the CPU, with the
    ``peaks`` they pass)."""
    t_start = process_start() if t_start is None else t_start
    if manifest is None:
        if not os.path.isfile(H.MANIFEST):
            raise H.BenchError(f"no manifest at {H.MANIFEST}")
        manifest = H.load_json(H.MANIFEST)
    wl = H.load_workload(args.workload, workload_dirs)
    cfg = H.load_config(wl["config"], config_dirs)
    e2e, layer = H.cell_metrics(manifest, args.workload)
    if not os.path.isdir(os.path.join(H.SRC, "repro")):
        raise H.BenchError(f"the program under test is not in this "
                           f"checkout ({H.SRC}/repro)")
    if H.SRC not in sys.path:
        sys.path.insert(0, H.SRC)
    H.enable_compile_cache()
    import jax
    if require_tpu:
        devs = H.require_chips(int(wl["chips"]))
        peaks = H.load_peaks(devs[0].device_kind)
    else:
        devs = jax.devices()[:int(wl["chips"])]
    H.log(f"{args.workload}: {len(devs)} x {devs[0].device_kind}, seed "
          f"{args.seed}, {args.seconds:g} s, trace {args.trace}")
    driver = H.load_module("drivers", wl["kind"])
    run_dir = os.path.join(H.RUNS_DIR, args.workload)
    ctx = Ctx(wl, cfg, H.program_seed(args.seed), float(args.seconds),
              bool(args.trace), t_start, devs, run_dir)
    rec = driver.run(ctx)
    device = rec.data["device"]
    layer_values, breakdown = None, None
    if args.trace:
        from bench import trace_reduce as TR
        tr = TR.load(TR.find_xplane(rec.trace_dir))
        device = dict(device, busy_s=TR.mean_busy_s(tr),
                      window_s=tr.window_s)
        layer_values = {}
        for m in layer:
            mod = H.load_module("metrics", m["name"])
            v = mod.compute(rec.data, tr, peaks)
            if v is not None:
                layer_values[m["name"]] = {"value": H.fmt(v),
                                           "unit": m["unit"]}
        breakdown = {"device_ops": TR.top_ops(tr),
                     "idle_gaps": TR.idle_gaps(tr)}
    for note in rec.notes:
        H.log(note)
    return H.result_line(rec, e2e, layer_values, device, breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = run(args)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
