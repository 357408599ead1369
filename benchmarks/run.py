# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
#
# ``--json`` additionally runs the committed perf benches (contrastive
# kernels + zero-shot serving), rewrites BENCH_kernels.json /
# BENCH_serving.json, and gates the fresh numbers against the previously
# committed content via scripts.check_bench (>1.3x, plus the serving
# bench's intra-run must_beat invariants).
import argparse
import importlib
import json
import os
import sys
import traceback

# suite name -> (module, one-line description shown in --help)
SUITES = {
    "table2": ("benchmarks.table2_memory",
               "step time/memory: DP vs GradAccum (paper Table 2)"),
    "table4": ("benchmarks.table4_batch",
               "batch-size ablation (paper Table 4)"),
    "zeroshot": ("benchmarks.zero_shot",
                 "zero-shot accuracy sweep (paper Tables 1/3 analog)"),
    "theory": ("benchmarks.theory_bound",
               "Theorems 1-2 generalization gap vs B"),
    "roofline": ("benchmarks.roofline_table",
                 "roofline aggregation over dryrun outputs"),
    "kernels": ("benchmarks.kernel_bench",
                "contrastive loss kernels: ref vs 4-pass vs fused "
                "(gated, DESIGN.md §5)"),
    "serving": ("benchmarks.serving_bench",
                "similarity->top-k kernel + e2e classify "
                "(gated, DESIGN.md §6.4)"),
    "distributed": ("benchmarks.distributed_bench",
                    "cross-shard global-batch loss, simulated mesh "
                    "(gated, DESIGN.md §7.5)"),
    "tower": ("benchmarks.tower_bench",
              "encode path per attention backend: naive vs chunked vs "
              "pallas (gated, DESIGN.md §8)"),
    "data": ("benchmarks.data_bench",
             "host-side input pipeline: generation, augmentation "
             "overhead, prefetch depth sweep (gated, DESIGN.md §9.4)"),
    "ckpt": ("benchmarks.ckpt_bench",
             "checkpoint save stall: blocking vs async manager, plus "
             "verified restore (gated, DESIGN.md §10.5)"),
    "decode": ("benchmarks.decode_bench",
               "continuous-batching decode vs one-at-a-time legacy "
               "serving (gated, DESIGN.md §12.5)"),
}
TABLES = {name: mod for name, (mod, _) in SUITES.items()}

# slow full-sweep benches only run when selected explicitly (or via --json)
_OPT_IN = {"kernels", "serving", "distributed", "tower", "data", "ckpt",
           "decode"}

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# gated perf-trajectory files: bench module -> committed baseline JSON
GATED = {
    "kernels": os.path.join(_ROOT, "BENCH_kernels.json"),
    "serving": os.path.join(_ROOT, "BENCH_serving.json"),
    "distributed": os.path.join(_ROOT, "BENCH_distributed.json"),
    "tower": os.path.join(_ROOT, "BENCH_tower.json"),
    "data": os.path.join(_ROOT, "BENCH_data.json"),
    "ckpt": os.path.join(_ROOT, "BENCH_ckpt.json"),
    "decode": os.path.join(_ROOT, "BENCH_decode.json"),
}


def _run_bench_json(name: str, json_path: str) -> int:
    """Run bench ``name`` and gate it against the checked-out JSON. On pass
    the file is refreshed (committing it is how the perf trajectory ratchets
    forward — review its git diff, since sub-threshold drift accumulates by
    design); on failure the baseline is kept and the fresh numbers go to
    ``<file>.new``, so re-running can't silently accept a regression by
    comparing it against itself. Returns rc."""
    from scripts import check_bench

    mod = importlib.import_module(TABLES[name])
    baseline = None
    if os.path.exists(json_path):
        with open(json_path) as f:
            baseline = json.load(f)
    fresh = mod.run()
    if baseline is None:
        failures = check_bench.must_beat_failures(fresh)
        for line in failures:
            print(f"check_bench[{name}]: REGRESSION {line}", file=sys.stderr)
        if failures:
            mod.write_json(json_path + ".new", fresh)
            return 1
        mod.write_json(json_path, fresh)
        print(f"run.py --json: no prior baseline; wrote initial "
              f"{json_path}", file=sys.stderr)
        return 0
    print(f"check_bench[{name}]: {check_bench.summarize(fresh, baseline)}")
    failures = check_bench.compare(fresh, baseline)
    for line in failures:
        print(f"check_bench[{name}]: REGRESSION {line}", file=sys.stderr)
    if failures:
        mod.write_json(json_path + ".new", fresh)
        print(f"run.py --json: baseline kept; fresh (regressed) numbers in "
              f"{json_path}.new", file=sys.stderr)
        return 1
    mod.write_json(json_path, fresh)
    if os.path.exists(json_path + ".new"):
        os.remove(json_path + ".new")  # stale output of an older failed run
    print(f"check_bench[{name}]: OK")
    return 0


def main() -> None:
    suites = "\n".join(f"  {n:<12} {d}" + ("  [opt-in]" if n in _OPT_IN
                                           else "")
                       for n, (_, d) in sorted(SUITES.items()))
    ap = argparse.ArgumentParser(
        description="run the repo's benchmark suites "
                    "(CSV: name,us_per_call,derived)",
        epilog=f"registered suites:\n{suites}\n\n[opt-in] suites only run "
               "with --only <name> or --json (they are slow full sweeps "
               "and carry the perf-regression gate)",
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--only", choices=sorted(TABLES), default=None,
                    help="run a single suite")
    ap.add_argument("--json", action="store_true",
                    help="run the gated perf benches, rewrite BENCH_*.json, "
                         "and fail on >1.3x regression vs the committed files")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = 0
    for name, mod_name in TABLES.items():
        if args.only and name != args.only:
            continue
        if name in _OPT_IN and (args.json or args.only != name):
            continue  # opt-in only; with --json the gate runs it instead
        try:
            importlib.import_module(mod_name).run()
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        gated = [n for n in GATED if args.only in (None, n)]
        if not gated:
            print(f"run.py: --json ignored with --only {args.only} "
                  "(no perf gate covers it)", file=sys.stderr)
        for name in gated:
            failed += _run_bench_json(name, GATED[name])
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
