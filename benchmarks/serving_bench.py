"""Zero-shot serving perf bench: fused similarity→top-k vs the materializing
matmul+argsort reference, the §13 planet-scale retrieval paths, plus
end-to-end classify latency through the ZeroShotService (DESIGN.md §6.4).

Kernel comparison at n_classes ∈ {1k, 16k, 100k} (b=128, d=256, k=5):

  topk_ref   : jnp matmul -> stable argsort -> slice (materializes (b, n))
  topk_fused : blockwise Pallas kernel, running top-k in VMEM scratch

The 100k fused entry carries ``must_beat: topk_ref`` — scripts/check_bench.py
fails the gate if the kernel ever stops beating the reference at the label
scale the subsystem exists for.

Planet-scale entries (DESIGN.md §13.5):

  topk_fused_extrap/N1000000 : EXTRAPOLATED single-device latency at N=1M —
      10x a fresh same-process topk_fused/N100000 sweep (the kernel's cost
      is linear in class blocks, measured super-linear in interpret mode,
      so 10x UNDERSTATES the single-device cost — a conservative target).
  topk_sharded/N1000000      : the real N=1M exact sweep over an 8-way
      simulated data mesh (subprocess, same pattern as distributed_bench);
      carries ``must_beat: topk_fused_extrap/N1000000`` — the headline
      invariant: sharding must beat single-device scaling at 1M rows.
  topk_twostage/N10000000    : coarse→fine at N=10M synthetic clustered
      gallery (block-seeded, streamed through the gather callback — the
      matrix never fully materializes); reports recall@5 vs a streaming
      exact oracle at the pruned setting.
  topk_twostage/N100000_*    : two-stage at the committed 100k scale —
      ``nprobe_all`` asserts bit-identical-to-fused (recall 1.0 by
      construction), ``nprobe8`` measures the pruned latency/recall trade.

End-to-end entries time a warm classify() (micro-batcher + registry hit +
fused kernel) on a smoke dual encoder. e2e, extrap/sharded (subprocess
thread scheduling) and twostage (host-side coarse/gather stages) entries
are ``ungated`` for 1.3x drift — the must_beat invariants still gate.

``run(json_path=...)`` emits BENCH_serving.json, the committed perf
trajectory regressed by scripts/check_bench.py via benchmarks/run.py --json.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, write_json
from benchmarks.common import timeit_min as _timeit
from repro.kernels.similarity_topk import ops as topk_ops
from repro.kernels.similarity_topk import ref as topk_ref

N_CLASSES = (1_000, 16_000, 100_000)
B, D, K = 128, 256, 5
E2E_BATCH = 16
MUST_BEAT_N = 100_000

# -- §13 planet-scale shapes ----------------------------------------------
SHARD_DEVICES = 8           # simulated data-parallel degree (subprocess)
SHARD_N = 1_000_000
SHARD_BC = 131_072          # per-shard class block: ONE interpret grid
                            # step per shard at N=1M/8 (DESIGN.md §13.5)
EXTRAP_FACTOR = SHARD_N // MUST_BEAT_N
TWOSTAGE_N = 10_000_000
TWOSTAGE_BLOCKS = 1_000     # synthetic gallery: 1000 blocks x 10000 rows
TWOSTAGE_D = 64
TWOSTAGE_B = 16
TWOSTAGE_NPROBE = 4
TWOSTAGE_SIGMA = 0.15       # intra-block noise scale around each centroid


def _unit(key, rows, d):
    z = jax.random.normal(key, (rows, d), jnp.float32)
    return z / jnp.linalg.norm(z, axis=1, keepdims=True)


def _kernel_entries(entries, n_classes, interpret):
    for n in n_classes:
        k1, k2 = jax.random.split(jax.random.key(n))
        x = _unit(k1, B, D)
        c = _unit(k2, n, D)
        iters = 2 if n >= 100_000 else 3
        ref_fn = jax.jit(lambda x, c: topk_ref.similarity_topk_ref(x, c, K))
        fused_fn = jax.jit(lambda x, c: topk_ops.similarity_topk(
            x, c, K, interpret=interpret))
        ref_key, fused_key = f"topk_ref/N{n}", f"topk_fused/N{n}"
        entries[ref_key] = {"us": round(_timeit(ref_fn, x, c, iters=iters), 1)}
        entries[fused_key] = {
            "us": round(_timeit(fused_fn, x, c, iters=iters), 1)}
        entries[fused_key]["speedup_vs_ref"] = round(
            entries[ref_key]["us"] / entries[fused_key]["us"], 2)
        if n == MUST_BEAT_N:
            entries[fused_key]["must_beat"] = ref_key
        for key in (ref_key, fused_key):
            csv_line(f"serving/{key}", entries[key]["us"],
                     f"b={B};d={D};k={K}")


def _e2e_entries(entries, interpret):
    """Warm classify() latency through the full service stack."""
    import tempfile

    from benchmarks.common import tiny_dual_cfg
    from repro.data import load_tokenizer, world_for_tower
    from repro.data.synthetic import render_images
    from repro.models import dual_encoder as de
    from repro.serving import ZeroShotService

    cfg = tiny_dual_cfg()
    rng = np.random.default_rng(0)
    world = world_for_tower(rng, cfg.image_tower, n_classes=32)
    tok = load_tokenizer()
    params = de.init_params(cfg, jax.random.key(0))
    imgs = render_images(world, rng.integers(0, 32, E2E_BATCH), rng)

    with tempfile.TemporaryDirectory() as td, \
            ZeroShotService(cfg, params, tok, registry_dir=td,
                            max_delay_ms=1.0, interpret=interpret) as svc:
        svc.classify(imgs, world.class_names, k=5)   # compile + class matrix
        lat = []
        for _ in range(5):
            t0 = time.perf_counter()
            svc.classify(imgs, world.class_names, k=5)
            lat.append(time.perf_counter() - t0)
        us = min(lat) * 1e6
        # ungated: this times the threaded micro-batcher's deadline waits and
        # scheduler, not a kernel — it jitters 2x run-to-run on shared hosts
        # and would make the 1.3x gate flappy; the topk_* entries carry it.
        entries[f"e2e/classify_b{E2E_BATCH}"] = {
            "us": round(us, 1),
            "img_per_s": round(E2E_BATCH / (us * 1e-6), 1),
            "ungated": True,
        }
        csv_line(f"serving/e2e/classify_b{E2E_BATCH}", us,
                 f"{E2E_BATCH / (us * 1e-6):.1f}img/s")


def _sharded_entries_body() -> dict:
    """Subprocess body (needs the simulated-device XLA flag): the N=1M
    exact sharded sweep vs the extrapolated single-device target."""
    from repro.serving import retrieval as rtv

    assert jax.device_count() >= SHARD_DEVICES, jax.devices()
    k1, k2 = jax.random.split(jax.random.key(SHARD_N))
    x = _unit(k1, B, D)
    c = _unit(k2, SHARD_N, D)
    mesh = rtv.default_data_mesh(SHARD_DEVICES)
    sm = rtv.shard_matrix(c, mesh)

    # sanity: the sharded path is bit-identical to the single-device kernel
    # at the committed 100k scale (the full suite lives in the tests)
    c100k = c[:MUST_BEAT_N]
    v_ref, i_ref = jax.block_until_ready(
        topk_ops.similarity_topk(x, c100k, K, interpret=True))
    sm100k = rtv.shard_matrix(c100k, mesh)
    v_sh, i_sh = rtv.sharded_similarity_topk(x, sm100k, K, interpret=True)
    assert jnp.array_equal(v_ref, v_sh) and jnp.array_equal(i_ref, i_sh), \
        "sharded sweep diverged from the single-device kernel at N=100k"

    # the extrapolation anchor: a FRESH default-tuned single-device 100k
    # sweep in this same process, scaled linearly to N=1M
    fused_fn = jax.jit(lambda x, c: topk_ops.similarity_topk(
        x, c, K, interpret=True))
    fused_100k_us = _timeit(fused_fn, x, c100k, iters=3)
    extrap_key = f"topk_fused_extrap/N{SHARD_N}"
    sharded_key = f"topk_sharded/N{SHARD_N}"
    entries = {extrap_key: {
        "us": round(EXTRAP_FACTOR * fused_100k_us, 1),
        "desc": f"{EXTRAP_FACTOR}x fresh topk_fused/N{MUST_BEAT_N} "
                f"(conservative single-device N={SHARD_N} estimate)",
        # derived from a fresh sub-50ms-floor-adjacent sweep each run;
        # the drift gate is owned by topk_fused/N100000
        "ungated": True,
    }}

    def sharded_fn(x):
        return rtv.sharded_similarity_topk(x, sm, K, interpret=True,
                                           bc=SHARD_BC)
    us = _timeit(sharded_fn, x, iters=2)
    entries[sharded_key] = {
        "us": round(us, 1),
        "desc": f"exact N={SHARD_N} sweep, {SHARD_DEVICES}-shard mesh, "
                f"per-shard bc={SHARD_BC}",
        "speedup_vs_extrap": round(entries[extrap_key]["us"] / us, 2),
        # S threads time-slicing one host CPU jitter past the 1.3x gate;
        # the must_beat invariant below is the gate (host-drift immune)
        "ungated": True,
        "must_beat": extrap_key,
    }
    return entries


def _sharded_entries(entries: dict) -> None:
    """Spawn the simulated-mesh subprocess (same pattern as
    benchmarks/distributed_bench.py: jax locks the device count at first
    init, so the parent process cannot host the mesh itself).

    CPU only: the child is pinned to ``JAX_PLATFORMS=cpu`` and starts after
    this process has imported JAX, so it never measures the chip (a parent
    that holds the chip would starve it). Its numbers are host interpret
    timings, not device metrics."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        emit = f.name
    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={SHARD_DEVICES}")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            [root, os.path.join(root, "src"),
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.serving_bench",
             "--emit-sharded", emit],
            env=env, cwd=root, capture_output=True, text=True, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sharded bench subprocess failed:\n{proc.stderr[-3000:]}")
        with open(emit) as f:
            emitted = json.load(f)
    finally:
        os.unlink(emit)
    for name, e in sorted(emitted.items()):
        entries[name] = e
        csv_line(f"serving/{name}", e["us"], e["desc"])


def _twostage_block(block: int, centroids: np.ndarray) -> np.ndarray:
    """Regenerate one synthetic gallery block from its seed: rows clustered
    around the block centroid — the gather-callback storage model (the
    10M-row matrix never materializes)."""
    m = TWOSTAGE_N // TWOSTAGE_BLOCKS
    rng = np.random.default_rng(10_000 + block)
    rows = centroids[block] + TWOSTAGE_SIGMA * rng.standard_normal(
        (m, TWOSTAGE_D)).astype(np.float32)
    return rows / np.linalg.norm(rows, axis=1, keepdims=True)


def _twostage_10m_entries(entries: dict, interpret) -> None:
    """Coarse→fine at N=10M: index known by construction (the generator's
    centroids ARE the block structure), rows streamed per block."""
    from repro.serving import retrieval as rtv

    p, m = TWOSTAGE_BLOCKS, TWOSTAGE_N // TWOSTAGE_BLOCKS
    rng = np.random.default_rng(999)
    cent = rng.standard_normal((p, TWOSTAGE_D)).astype(np.float32)
    cent /= np.linalg.norm(cent, axis=1, keepdims=True)
    index = rtv.CentroidIndex(
        centroids=cent,
        members=np.arange(TWOSTAGE_N, dtype=np.int32).reshape(p, m),
        counts=np.full(p, m, np.int32), n=TWOSTAGE_N)
    # queries near (but not on) random block centroids — the regime the
    # coarse stage exists for
    qi = rng.integers(0, p, TWOSTAGE_B)
    q = cent[qi] + TWOSTAGE_SIGMA * rng.standard_normal(
        (TWOSTAGE_B, TWOSTAGE_D)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)

    def gather(ids):
        blocks = np.unique(ids // m)
        chunks = {b: _twostage_block(b, cent) for b in blocks}
        return np.concatenate(
            [chunks[b][ids[ids // m == b] % m] for b in blocks])

    t0 = time.perf_counter()
    vals, gidx, info = rtv.two_stage_topk(
        q, gather, index, K, nprobe=TWOSTAGE_NPROBE, interpret=interpret,
        bc=SHARD_BC)
    us = (time.perf_counter() - t0) * 1e6

    # streaming exact oracle: per-block top-K merge in numpy
    best_v = np.full((TWOSTAGE_B, K), -np.inf, np.float32)
    best_i = np.full((TWOSTAGE_B, K), -1, np.int64)
    for blk in range(p):
        s = (q @ _twostage_block(blk, cent).T).astype(np.float32)
        top = np.argpartition(-s, K - 1, axis=1)[:, :K]
        cv = np.concatenate([best_v, np.take_along_axis(s, top, axis=1)], 1)
        ci = np.concatenate([best_i, top + blk * m], 1)
        keep = np.argpartition(-cv, K - 1, axis=1)[:, :K]
        best_v = np.take_along_axis(cv, keep, axis=1)
        best_i = np.take_along_axis(ci, keep, axis=1)
    recall = float(np.mean([
        len(set(gidx[r]) & set(best_i[r])) / K for r in range(TWOSTAGE_B)]))
    entries[f"topk_twostage/N{TWOSTAGE_N}"] = {
        "us": round(us, 1),
        "desc": f"coarse→fine, {p} blocks, nprobe={TWOSTAGE_NPROBE}, "
                f"b={TWOSTAGE_B} d={TWOSTAGE_D}, block-streamed gallery",
        "recall_at_k": round(recall, 4),
        "prune_ratio": round(info["prune_ratio"], 4),
        "ungated": True,   # host-side coarse/gather stages drift with load
    }
    csv_line(f"serving/topk_twostage/N{TWOSTAGE_N}", us,
             f"recall@{K}={recall:.3f};prune={info['prune_ratio']:.4f}")


def _twostage_100k_entries(entries: dict, interpret) -> None:
    """Two-stage at the committed 100k scale: nprobe=all must reproduce
    the fused kernel bit-for-bit (the exactness escape hatch), nprobe=8
    records the pruned latency/recall trade."""
    from repro.serving import retrieval as rtv

    n = MUST_BEAT_N
    k1, k2 = jax.random.split(jax.random.key(n))
    # TWOSTAGE_B queries, not B: the probe-union across a batch is what
    # survives pruning, and the coarse stage targets interactive batch
    # sizes (a 128-query union touches ~every block — no prune left)
    x = np.asarray(_unit(k1, TWOSTAGE_B, D))
    c = np.asarray(_unit(k2, n, D))
    index = rtv.build_centroid_index(c, iters=2)
    v_ref, i_ref = topk_ops.similarity_topk(
        jnp.asarray(x), jnp.asarray(c), K, interpret=interpret)
    v_ref, i_ref = np.asarray(v_ref), np.asarray(i_ref)

    for nprobe, tag in (("all", "nprobe_all"), (8, "nprobe8")):
        t0 = time.perf_counter()
        vals, gidx, info = rtv.two_stage_topk(
            x, c, index, K, nprobe=nprobe, interpret=interpret)
        us = (time.perf_counter() - t0) * 1e6
        recall = float(np.mean([
            len(set(gidx[r]) & set(i_ref[r])) / K
            for r in range(TWOSTAGE_B)]))
        if nprobe == "all":
            assert np.array_equal(vals, v_ref) and \
                np.array_equal(gidx, i_ref), \
                "nprobe=all diverged from the fused kernel"
            assert recall == 1.0
        entries[f"topk_twostage/N{n}_{tag}"] = {
            "us": round(us, 1),
            # uniform random gallery = the WORST case for coarse pruning
            # (no cluster structure to exploit); the N=10M entry measures
            # the clustered regime the index is built for
            "desc": f"two-stage N={n} nprobe={nprobe} "
                    f"({index.n_blocks} blocks, uniform gallery)",
            "recall_at_k": round(recall, 4),
            "prune_ratio": round(info["prune_ratio"], 4),
            "ungated": True,
        }
        csv_line(f"serving/topk_twostage/N{n}_{tag}", us,
                 f"recall@{K}={recall:.3f};prune={info['prune_ratio']:.4f}")


def run(json_path: str | None = None, n_classes=None, e2e: bool = True,
        planet_scale: bool = True):
    interpret = jax.default_backend() == "cpu"
    entries: dict = {}
    _kernel_entries(entries, n_classes or N_CLASSES, interpret)
    if planet_scale:
        _sharded_entries(entries)
        _twostage_100k_entries(entries, interpret)
        _twostage_10m_entries(entries, interpret)
    if e2e:
        _e2e_entries(entries, interpret)
    result = {
        "meta": {
            "backend": jax.default_backend(),
            "interpret": interpret,
            "kernel_shape": {"b": B, "d": D, "k": K},
            "n_classes": list(n_classes or N_CLASSES),
            "sharded": {"devices": SHARD_DEVICES, "n": SHARD_N,
                        "bc": SHARD_BC},
            "twostage": {"n": TWOSTAGE_N, "blocks": TWOSTAGE_BLOCKS,
                         "d": TWOSTAGE_D, "nprobe": TWOSTAGE_NPROBE},
        },
        "entries": entries,
    }
    if json_path:
        write_json(json_path, result)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write BENCH_serving.json-style output here")
    ap.add_argument("--smoke", action="store_true",
                    help="small label spaces only (CI sanity, not a baseline)")
    ap.add_argument("--emit-sharded", default=None, metavar="PATH",
                    help="(internal) run the sharded-mesh bench in THIS "
                         "process and write raw entries to PATH — requires "
                         "the simulated-device XLA flag to be set")
    args = ap.parse_args()
    if args.emit_sharded:
        entries = _sharded_entries_body()
        with open(args.emit_sharded, "w") as f:
            json.dump(entries, f)
        return
    print("name,us_per_call,derived")
    run(json_path=args.json,
        n_classes=[1_000, 4_000] if args.smoke else None,
        e2e=not args.smoke, planet_scale=not args.smoke)


if __name__ == "__main__":
    main()
