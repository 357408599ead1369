#!/usr/bin/env python3
"""Smoke test of the BASIC-S main path on a TPU, in one process.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # the cross-chip paths, on four chips

One chip (the default) runs, through the entry points a user calls:

  kernels   the fused contrastive loss and its gradients (compiled Pallas,
            B=1024, D=512, fp32 and bf16) against ``core.contrastive``
            under matmul precision ``highest``; ``similarity_topk`` at
            1,000 classes, k=5, against its reference;
  train     ``repro.launch.train_distributed.train`` on BASIC-S at its
            published widths (no smoke variant), ``--loss chunked``,
            4 microbatches, caption length 64, 5 steps (batch 768: 1024
            does not fit one chip's HBM, see TRAIN_BATCH);
  serve     ``ZeroShotService`` at full width over a 1,000-class
            vocabulary, classify requests of 64 images each;
  backends  the attention backend each tower resolved to and which
            contrastive backward sweep (fused or legacy) ran.

``--chips 4`` runs only what exists across chips: the chunked global-batch
loss over a data=4 mesh against the single-device fused loss, 3 trainer
steps at data=4, and ``sharded_similarity_topk`` against the fused top-k.

The script refuses to run anywhere but a TPU. Timings it prints are smoke
readings (one process, few steps), not benchmark numbers. The last line of
stdout is ``{"ok": true, "device": {...}}``; a failed phase exits non-zero
without it. The compile cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                                       # noqa: E402
import jax.numpy as jnp                                          # noqa: E402
import numpy as np                                               # noqa: E402

from repro.configs import get_arch                               # noqa: E402
from repro.core import distributed_loss as dl                    # noqa: E402
from repro.core.contrastive import (contrastive_loss,            # noqa: E402
                                    fused_kernel_loss)
from repro.data import load_tokenizer, world_for_tower           # noqa: E402
from repro.data.synthetic import render_images                   # noqa: E402
from repro.kernels.contrastive_loss import ops as cl_ops         # noqa: E402
from repro.kernels.similarity_topk import ops as topk_ops        # noqa: E402
from repro.kernels.similarity_topk import ref as topk_ref        # noqa: E402
from repro.launch import compile_cache                           # noqa: E402
from repro.launch import train_distributed as td                 # noqa: E402
from repro.launch.mesh import make_local_mesh, make_mesh         # noqa: E402
from repro.models import attention as attn                       # noqa: E402
from repro.models import dual_encoder as de                      # noqa: E402
from repro.serving import ZeroShotService                        # noqa: E402
from repro.serving import retrieval as rtv                       # noqa: E402

ARCH = "basic-s"
LOSS_B, LOSS_D = 1024, 512
# max |got - want| / max |want| against the `highest`-precision reference;
# a wrong kernel (diagonal, normalization, a missed tile) is off by O(1)
LOSS_RTOL, GRAD_RTOL = 1e-3, 2e-2
# One chip trains batch 768: at 1024 the compiled step's memory analysis
# (1.94 GiB arguments + 14.30 GiB temporaries) exceeds the chip's 15.75 GiB
# limit, and on a v5e it ran at ~4.5 s/step against 1.55 s at 768 (1.80 +
# 11.65 GiB). Four chips keep 1024 (256 per chip).
TRAIN_BATCH = {1: 768, 4: 1024}
TRAIN_ARGV = ["--arch", ARCH, "--num-micro", "4", "--seq", "64",
              "--loss", "chunked", "--log-every", "1"]
SERVE_CLASSES, SERVE_BATCH, SERVE_REQUESTS, TOP_K = 1000, 64, 4, 5
RUN_DIR = os.path.join(ROOT, ".chip_smoke")          # git-ignored


def log(msg):
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def unit_rows(key, b, d, dtype=jnp.float32):
    z = jax.random.normal(key, (b, d), jnp.float32)
    return (z / jnp.linalg.norm(z, axis=1, keepdims=True)).astype(dtype)


def exact_rows(key, n, d):
    """Rows of multiples of 1/8 in [-1/2, 1/2]: every product and partial
    sum is exact in bf16 and fp32, so any matmul precision reproduces the
    reference logits bit for bit, ties included."""
    return jax.random.randint(key, (n, d), -4, 5).astype(jnp.float32) / 8


def check_device(n_chips: int) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); refusing to run elsewhere")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, found {len(devs)}")
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    log(f"device: {dev.device_kind}, {len(devs)} device(s), HBM limit "
        f"{limit} bytes, compile cache {compile_cache.enable()}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def loss_and_grads(loss_fn, x, y, tau, *, highest=False):
    """(loss, (dX, dY, dtau)) of ``loss_fn(x, y, tau) -> (loss, metrics)``;
    ``highest`` runs it under matmul precision ``highest``."""
    fn = jax.jit(jax.value_and_grad(lambda x, y, t: loss_fn(x, y, t)[0],
                                    argnums=(0, 1, 2)))
    if highest:
        with jax.default_matmul_precision("highest"):
            return jax.block_until_ready(fn(x, y, tau))
    return jax.block_until_ready(fn(x, y, tau))


def check_close(tag, got, want):
    """Assert loss and gradients agree within LOSS_RTOL / GRAD_RTOL."""
    (gl, gg), (wl, wg) = got, want
    errs = {"loss": rel_err(gl, wl)}
    errs.update({n: rel_err(g, w) for n, g, w in
                 zip(("dX", "dY", "dtau"), gg, wg)})
    log(f"{tag}: loss {float(gl):.6f} vs {float(wl):.6f} "
        + " ".join(f"{n}_rel={e:.3e}" for n, e in errs.items()))
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in gg)
    assert errs["loss"] <= LOSS_RTOL, errs
    assert max(errs["dX"], errs["dY"], errs["dtau"]) <= GRAD_RTOL, errs


def check_contrastive(b=LOSS_B, d=LOSS_D, *, interpret=False):
    """Fused Pallas loss + gradients vs the materializing reference."""
    tau = jnp.float32(0.07)
    kx, ky = jax.random.split(jax.random.key(0))
    for dtype in (jnp.float32, jnp.bfloat16):
        x, y = unit_rows(kx, b, d, dtype), unit_rows(ky, b, d, dtype)
        sweep = cl_ops.backward_sweep(b, d, jnp.dtype(dtype).itemsize,
                                      interpret=interpret)
        check_close(
            f"kernels: contrastive {jnp.dtype(dtype).name} B={b} D={d} "
            f"backward={sweep} vs reference",
            loss_and_grads(lambda x, y, t: fused_kernel_loss(
                x, y, t, interpret=interpret), x, y, tau),
            loss_and_grads(contrastive_loss, x, y, tau, highest=True))


def check_topk(n=SERVE_CLASSES, b=SERVE_BATCH, d=LOSS_D, k=TOP_K, *,
               interpret=False):
    """Fused similarity→top-k vs the stable-argsort reference: identical
    indices and values on exact-arithmetic inputs."""
    kq, kc = jax.random.split(jax.random.key(1))
    for dtype in (jnp.float32, jnp.bfloat16):
        q = exact_rows(kq, b, d).astype(dtype)
        c = exact_rows(kc, n, d).astype(dtype)
        v, i = jax.block_until_ready(
            topk_ops.similarity_topk(q, c, k, interpret=interpret))
        vr, ir = topk_ref.similarity_topk_ref(q, c, k)
        ties = int(np.sum(np.diff(np.asarray(vr), axis=1) == 0))
        log(f"kernels: similarity_topk {jnp.dtype(dtype).name} b={b} n={n} "
            f"d={d} k={k} indices_equal={np.array_equal(i, ir)} "
            f"values_equal={np.array_equal(v, vr)} tied_pairs={ties}")
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))


def check_train(argv, run_dir=RUN_DIR):
    """The trainer's own entry point; timings from its runlog, where each
    step's ``device_step_s`` ends when the loss is on the host."""
    os.makedirs(run_dir, exist_ok=True)
    runlog = os.path.join(run_dir, "runlog.jsonl")
    if os.path.exists(runlog):
        os.remove(runlog)
    args = td.parser().parse_args(argv + ["--run-dir", run_dir])
    mesh = make_local_mesh()        # the mesh train_contrastive builds
    log(f"train: {' '.join(argv)} (mesh {dict(mesh.shape)}, devices "
        f"{[d.id for d in mesh.devices.flat]})")
    t0 = time.perf_counter()
    losses = td.train(args)
    wall = time.perf_counter() - t0
    with open(runlog) as f:
        steps = [r for r in map(json.loads, f) if r.get("kind") == "step"]
    dev_s = [r["device_step_s"] for r in steps]
    steady = float(np.median(dev_s[1:])) if len(dev_s) > 1 else float("nan")
    log(f"train: losses {losses}")
    log(f"train: first step {dev_s[0]:.3f}s (compile + run), compile "
        f"~{dev_s[0] - steady:.3f}s, steady {steady:.4f}s/step over "
        f"{len(dev_s) - 1} steps, {args.batch / steady:.1f} pairs/s, "
        f"wall {wall:.1f}s (smoke reading, not a benchmark)")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"train: device 0 peak_bytes_in_use {peak}")
    assert len(losses) == args.steps, losses
    assert all(np.isfinite(losses)), losses
    return losses


def check_serve(cfg, *, n_classes=SERVE_CLASSES, batch=SERVE_BATCH,
                requests=SERVE_REQUESTS, k=TOP_K, seed=0):
    """Zero-shot classify through the service, driven the way
    ``launch/serve_zeroshot.py`` drives it."""
    rng = np.random.default_rng(seed)
    world = world_for_tower(rng, cfg.image_tower, n_classes=n_classes)
    tok = load_tokenizer("v1")
    params = de.init_params(cfg, jax.random.key(seed))
    with ZeroShotService(cfg, params, tok) as svc:
        t0 = time.perf_counter()
        svc.classify(render_images(world, rng.integers(0, n_classes, batch),
                                   rng), world.class_names, k=k)
        first = time.perf_counter() - t0
        lat = []
        for _ in range(requests):
            imgs = render_images(world, rng.integers(0, n_classes, batch), rng)
            t0 = time.perf_counter()
            res = svc.classify(imgs, world.class_names, k=k)
            lat.append(time.perf_counter() - t0)
            assert res.indices.shape == (batch, k), res.indices.shape
            assert res.values.shape == (batch, k), res.values.shape
            assert np.isfinite(res.values).all()
            assert (np.diff(res.values, axis=1) <= 0).all()
            assert ((0 <= res.indices) & (res.indices < n_classes)).all()
        stats = svc.stats()
    log(f"serve: {n_classes} classes, {requests} x {batch} images, k={k}: "
        f"first classify {first:.3f}s (compile + class matrix), warm p50 "
        f"{np.median(lat) * 1e3:.1f}ms max {max(lat) * 1e3:.1f}ms "
        f"(smoke reading), batcher {stats['batcher']}")
    assert stats["batcher"]["worker_errors"] == 0, stats["batcher"]


def report_backends(cfg, *, train_batch=TRAIN_BATCH[1], seq=64,
                    interpret=False):
    """Print every backend choice the phases above made."""
    img, txt = cfg.image_tower, cfg.text_tower
    for name, tower, s in (("image", img, img.frontend_len),
                           ("text", txt, seq)):
        got = attn.resolve_backend(tower.attn_impl, seq=s,
                                   head_dim=tower.resolved_head_dim)
        log(f"backends: {name} tower attn_impl={tower.attn_impl!r} seq={s} "
            f"head_dim={tower.resolved_head_dim} -> {got}")
    n_data = len(make_local_mesh().devices.flat)
    b_local = train_batch // n_data
    sweep = cl_ops.backward_sweep(b_local, cfg.embed_dim, 4,
                                  interpret=interpret)
    log(f"backends: train contrastive loss B_local={b_local} "
        f"D={cfg.embed_dim} fp32 -> {sweep} backward sweep")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def check_global_loss(n_chips, b=LOSS_B, d=LOSS_D):
    """Chunked cross-chip loss + gradients vs the single-device fused loss
    on the same global batch, and both vs the reference. The two kernel
    paths agree to the kernels' matmul precision, not to fp32 rounding:
    each rounds fp32 matmul operands like one bf16 pass (dX ~4e-3 from
    the reference on the chip), and the chunked path adds the
    positive-pair term in fp32 outside the kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((n_chips,), ("data",), devices=jax.devices()[:n_chips])
    kx, ky = jax.random.split(jax.random.key(0))
    x, y = unit_rows(kx, b, d), unit_rows(ky, b, d)
    tau = jnp.float32(0.07)
    ref = loss_and_grads(contrastive_loss, x, y, tau, highest=True)
    fused = loss_and_grads(fused_kernel_loss, x, y, tau)
    shard = NamedSharding(mesh, P("data"))
    xs, ys = jax.device_put(x, shard), jax.device_put(y, shard)
    log(f"global loss: x shards on devices "
        f"{[(s.device.id, s.index[0].start) for s in xs.addressable_shards]}")
    with mesh:
        chunked = loss_and_grads(dl.make_global_loss_fn(mesh, "chunked"),
                                 xs, ys, tau)
    log(f"global loss: dX shards on devices "
        f"{sorted(s.device.id for s in chunked[1][0].addressable_shards)}")
    tag = f"global loss: chunked data={n_chips} B={b} D={d}"
    check_close(f"{tag} vs single-device fused", chunked, fused)
    check_close(f"{tag} vs reference", chunked, ref)
    check_close("global loss: single-device fused vs reference", fused, ref)


def check_sharded_topk(n_chips, n=SERVE_CLASSES, b=SERVE_BATCH, d=LOSS_D,
                       k=TOP_K):
    kq, kc = jax.random.split(jax.random.key(1))
    q, c = exact_rows(kq, b, d), exact_rows(kc, n, d)
    want_v, want_i = topk_ops.similarity_topk(q, c, k)
    mesh = rtv.default_data_mesh(n_chips)
    sm = rtv.shard_matrix(c, mesh)
    shards = sm.array.addressable_shards
    log(f"sharded top-k: class rows on devices "
        f"{[(s.device.id, s.index[0].start) for s in shards]}")
    got_v, got_i = jax.block_until_ready(
        rtv.sharded_similarity_topk(q, sm, k))
    log(f"sharded top-k: {n_chips} shards n={n} b={b} k={k} indices_equal="
        f"{np.array_equal(got_i, want_i)} values_equal="
        f"{np.array_equal(got_v, want_v)}")
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


# ---------------------------------------------------------------------------


def run_phases(phases) -> list:
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except (Exception, SystemExit) as e:     # report, run the rest
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAIL ({type(e).__name__}: {e}) "
                f"after {time.perf_counter() - t0:.1f}s")
        else:
            log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip paths, on 4 chips")
    args = ap.parse_args(argv)
    device = check_device(args.chips)
    train_argv = TRAIN_ARGV + ["--batch", str(TRAIN_BATCH[args.chips])]
    if args.chips == 1:
        cfg = get_arch(ARCH)
        phases = [
            ("kernels", lambda: (check_contrastive(), check_topk())),
            ("train", lambda: check_train(train_argv + ["--steps", "5"])),
            ("serve", lambda: check_serve(cfg)),
            ("backends", lambda: report_backends(cfg)),
        ]
    else:
        phases = [
            ("global_loss", lambda: check_global_loss(args.chips)),
            ("train", lambda: check_train(train_argv + ["--steps", "3"])),
            ("sharded_topk", lambda: check_sharded_topk(args.chips)),
        ]
    failed = run_phases(phases)
    if failed:
        log(f"chip_smoke: FAILED phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
