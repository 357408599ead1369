"""What every cell's run shares: finding a cell's files by name, the chip
check, the persistent compile cache, the peak table, the checks that
decide ``correct``, and the result line.

A cell is one file ``bench/workloads/<cell>.json``; it names its
configuration (``bench/configs/<config>.json``) and its driver kind
(``bench/drivers/<kind>.py``). A per-layer metric is one file
``bench/metrics/<metric>.py``. Which metrics a cell reports comes from
``BENCHMARK.json``. Adding a cell or a metric adds files; no file here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# run logs and traces, at a fixed path inside the checkout
RUNS_DIR = os.path.join(ROOT, ".bench_cache", "runs")


class BenchError(RuntimeError):
    """A run that cannot be made: missing files, no chip, bad manifest."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_file(kind_dir: str, name: str, ext: str, dirs=None) -> str:
    """``<dir>/<name><ext>`` from the first of ``dirs`` (default: the
    benchmark's own ``kind_dir``) that holds it."""
    for d in dirs or [os.path.join(BENCH, kind_dir)]:
        path = os.path.join(d, name + ext)
        if os.path.isfile(path):
            return path
    raise BenchError(f"no {kind_dir} file {name + ext!r} in {dirs or kind_dir}")


def load_workload(name: str, dirs=None) -> dict:
    wl = load_json(find_file("workloads", name, ".json", dirs))
    if wl.get("name") != name:
        raise BenchError(f"workload file of {name!r} names {wl.get('name')!r}")
    return wl


def load_config(name: str, dirs=None) -> dict:
    return load_json(find_file("configs", name, ".json", dirs))


def load_module(kind_dir: str, name: str, dirs=None):
    """The Python file ``<kind_dir>/<name>.py`` as a module (names may
    hold dots, so it is loaded by path)."""
    path = find_file(kind_dir, name, ".py", dirs)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind_dir}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that ``cell`` reports."""
    def mine(m, e2e_names):
        if "workloads" in m:
            return cell in m["workloads"]
        return m.get("moves") is None or m["moves"] in e2e_names
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    return e2e, [m for m in manifest["per_layer"] if mine(m, names)]


def load_peaks(device_kind: str, path=None) -> dict:
    table = load_json(path or os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in the peak "
                         f"table {sorted(table['devices'])}")
    return table["devices"][device_kind]


def program_seed(seed: int) -> int:
    """The seed handed to the program and the reference: any whole number
    the benchmark is given, folded into 62 bits."""
    if seed < 0:
        raise BenchError(f"--seed {seed} must be a whole number >= 0")
    return int(seed) % (1 << 62)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache as the program's launchers set
    it (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else a fixed directory in the checkout), with JAX's own
    thresholds for what it keeps."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.launch import compile_cache
    return compile_cache.enable()


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; a BenchError on any other platform or
    on fewer chips (no fallback)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX platform is {devs[0].platform!r} "
                         f"({devs[0].device_kind}); the benchmark runs on "
                         f"TPU chips only")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} TPU chips, JAX sees "
                         f"{len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": int(max(s.get("peak_bytes_in_use", 0)
                                         for s in stats))}


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where ``value <= limit`` (NaN fails)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)   # NaN compares False


@dataclasses.dataclass
class Record:
    """What a driver hands back: the window's counts, its end-to-end
    numbers, the checks, and whatever the per-layer metrics read."""
    attempted: int
    failed: int
    e2e: dict
    checks: list
    data: dict = dataclasses.field(default_factory=dict)
    trace_dir: str | None = None
    notes: list = dataclasses.field(default_factory=list)


def fmt(x: float) -> float:
    """A number for the result line (NaN/inf become None)."""
    x = float(x)
    return x if math.isfinite(x) else None


def result_line(rec: Record, e2e_entries, layer_values: dict, device: dict,
                breakdown=None) -> dict:
    """The last line of standard output; the checks come last."""
    if layer_values is None:
        metrics = {m["name"]: {"value": fmt(rec.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in e2e_entries if m["name"] in rec.e2e}
    else:
        metrics = layer_values
    out = {"correct": bool(rec.checks) and all(c.ok for c in rec.checks),
           "attempted": int(rec.attempted), "failed": int(rec.failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": fmt(c.value), "limit": c.limit}
                     for c in rec.checks}
    return out


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
