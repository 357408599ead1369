"""Cross-shard global-batch contrastive loss bench (DESIGN.md §7.5).

Times one full loss+gradient evaluation at the same GLOBAL batch three
ways — multi-host simulated via a local host-platform device mesh:

  dist_ref/...        single-device fused loss on the full global batch
                      (the oracle the distributed paths must reproduce;
                      also the host-drift ref anchor for check_bench)
  dist_allgather/...  shard_map all-gather variant: every shard computes
                      the full (B, B) problem redundantly
  dist_chunked/...    shard_map chunked-negatives variant: each shard
                      computes only its row block + column partials

The simulated mesh needs its own process (jax locks the device count at
first init), so ``run()`` re-executes this module in a subprocess with
``--xla_force_host_platform_device_count`` and collects the entries via
``--emit``. That child is pinned to ``JAX_PLATFORMS=cpu``: it never
measures the chip, and its timings are host interpret-mode numbers, not
device metrics (``chip_smoke.py --chips 4`` is the on-chip check of the
same loss). ``run(json_path=...)`` writes BENCH_distributed.json, the
committed perf trajectory gated by scripts/check_bench.py through
``benchmarks/run.py --json`` exactly like the kernel and serving benches.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import csv_line, write_json  # noqa: F401 (run.py API)

R = 4                       # simulated data-parallel degree
SHAPES = [(2048, 256)]      # (global batch, embed dim)
ITERS = 3


def _bench_entries() -> dict:
    """Subprocess body: requires >= R simulated devices."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed_loss as dl
    from repro.core.contrastive import fused_kernel_loss
    from repro.launch.mesh import make_mesh

    assert jax.device_count() >= R, jax.devices()
    interpret = jax.default_backend() == "cpu"
    entries = {}
    for b, d in SHAPES:
        kx, ky = jax.random.split(jax.random.key(0))
        x = jax.random.normal(kx, (b, d), jnp.float32)
        x = x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        y = jax.random.normal(ky, (b, d), jnp.float32)
        y = y / jnp.linalg.norm(y, axis=-1, keepdims=True)
        tau = jnp.asarray(0.3)

        def ref_loss(x, y, tau):
            return fused_kernel_loss(x, y, tau, interpret=interpret)[0]

        mesh = make_mesh((R,), ("data",))
        fns = {"dist_ref": jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2)))}
        for method in dl.METHODS:
            loss_fn = dl.make_global_loss_fn(mesh, method,
                                             interpret=interpret)
            fns[f"dist_{method}"] = jax.jit(jax.value_and_grad(
                lambda x, y, t, loss_fn=loss_fn: loss_fn(x, y, t)[0],
                argnums=(0, 1, 2)))

        from benchmarks.common import timeit_min
        with mesh:
            for name, fn in fns.items():
                us = timeit_min(fn, x, y, tau, iters=ITERS)
                entry = {
                    "us": round(us, 1),
                    "desc": f"loss+grad global B={b} D={d} "
                            f"({'1 device' if name == 'dist_ref' else f'{R}-shard mesh'})",
                    # absolute timings of R threads time-slicing one host
                    # CPU jitter well past the 1.3x threshold run-to-run;
                    # only the intra-run must_beat below gates (the
                    # kernels bench owns the absolute perf trajectory)
                    "ungated": True,
                }
                if name == "dist_chunked":
                    # the whole point of the scheme: per-shard work drops
                    # R/2x vs computing the full problem on every shard —
                    # an intra-run invariant, immune to host drift
                    entry["must_beat"] = f"dist_allgather/R{R}_B{b}_D{d}"
                entries[f"{name}/R{R}_B{b}_D{d}"] = entry
    return entries


def run(json_path: str | None = None) -> dict:
    """Spawn the simulated-mesh bench subprocess, print CSV lines, return
    (and optionally write) the BENCH_distributed.json payload."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        emit = f.name
    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={R}")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            [root, os.path.join(root, "src"),
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.distributed_bench",
             "--emit", emit],
            env=env, cwd=root, capture_output=True, text=True, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"distributed_bench subprocess failed:\n{proc.stderr[-3000:]}")
        with open(emit) as f:
            entries = json.load(f)
    finally:
        os.unlink(emit)

    for name, e in sorted(entries.items()):
        csv_line(name, e["us"], e["desc"])
    payload = {
        "meta": {
            "bench": "distributed_contrastive_loss",
            # the subprocess is pinned to JAX_PLATFORMS=cpu: a simulated
            # mesh always measures host-CPU interpret mode, whatever
            # accelerator the parent process would default to
            "interpret": True,
            "backend": "cpu",
            "simulated_devices": R,
            "iters": ITERS,
        },
        "entries": entries,
    }
    if json_path:
        write_json(json_path, payload)
    return payload


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--emit", default=None,
                    help="(internal) run the bench in THIS process and "
                         "write raw entries to PATH — requires the "
                         "simulated-device XLA flag to be set")
    ap.add_argument("--json", default=None,
                    help="write the full BENCH payload to PATH")
    args = ap.parse_args()
    if args.emit:
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "src"))
        entries = _bench_entries()
        with open(args.emit, "w") as f:
            json.dump(entries, f)
        return
    run(args.json)


if __name__ == "__main__":
    main()
