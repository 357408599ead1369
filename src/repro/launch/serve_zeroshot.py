"""Zero-shot serving launcher: the ZeroShotService under synthetic traffic.

  PYTHONPATH=src python -m repro.launch.serve_zeroshot --smoke \
      --classes 64 --batch 16 --requests 8 --k 5

Builds a BASIC dual encoder, precomputes the class matrix through the
registry (persisted under --registry-dir when given, so a second launch
skips the text tower entirely), then pushes --requests classify batches
through the micro-batcher + fused similarity→top-k path and reports
latency/throughput.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_arch, smoke_variant
from repro.data import load_tokenizer, world_for_tower
from repro.data.synthetic import render_images
from repro.launch import compile_cache
from repro.models import dual_encoder as de
from repro.serving import ZeroShotService


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="basic-s")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink towers to test size (CPU interpret mode)")
    ap.add_argument("--classes", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--registry-dir", default=None)
    ap.add_argument("--tokenizer", default="v1",
                    help="tokenizer artifact version "
                         "(artifacts/tokenizer_<v>.json)")
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--retrieval", default="fused",
                    choices=("fused", "sharded", "twostage"),
                    help="top-k sweep: single-device fused kernel, "
                         "mesh-sharded exact, or coarse→fine two-stage "
                         "(DESIGN.md §13)")
    ap.add_argument("--nprobe", default=None,
                    help="twostage blocks probed per query (int or 'all' "
                         "= exact; default all)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="arm the serving SLO tracker: per-request latency "
                         "target in ms (windowed p99 + error-budget burn "
                         "under serve/slo_*; DESIGN.md §14.3)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live /metrics (Prometheus), /healthz (SLO "
                         "readiness) and /snapshot.json on 127.0.0.1:PORT "
                         "(0 = ephemeral) for the whole run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()
    nprobe = None if args.nprobe in (None, "all") else int(args.nprobe)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = dataclasses.replace(
            cfg, image_tower=smoke_variant(cfg.image_tower),
            text_tower=smoke_variant(cfg.text_tower), embed_dim=64)

    rng = np.random.default_rng(args.seed)
    world = world_for_tower(rng, cfg.image_tower, n_classes=args.classes)
    # the committed artifact: its hash rides in the registry fingerprint,
    # so serving and eval key their cached class matrices to THIS vocab
    tok = load_tokenizer(args.tokenizer)
    params = de.init_params(cfg, jax.random.key(args.seed))

    slo_s = args.slo_ms / 1e3 if args.slo_ms else None
    with ZeroShotService(cfg, params, tok,
                         registry_dir=args.registry_dir,
                         max_delay_ms=args.max_delay_ms,
                         retrieval=args.retrieval, nprobe=nprobe,
                         latency_slo_s=slo_s) as svc:
        server = None
        if args.metrics_port is not None:
            server = svc.serve_metrics(port=args.metrics_port)
            print(f"obs: serving /metrics /healthz /snapshot.json on "
                  f"{server.url}")
        t0 = time.time()
        svc.classify(render_images(world, rng.integers(
            0, args.classes, args.batch), rng), world.class_names, k=args.k)
        print(f"first classify (compile + class matrix): {time.time()-t0:.2f}s")

        lat = []
        hits = 0
        for _ in range(args.requests):
            cls = rng.integers(0, args.classes, args.batch)
            imgs = render_images(world, cls, rng)
            t0 = time.time()
            res = svc.classify(imgs, world.class_names, k=args.k)
            lat.append(time.time() - t0)
            hits += int(np.sum(res.indices[:, 0] == cls))
        n = args.requests * args.batch
        print(f"warm: p50 {np.median(lat)*1e3:.1f}ms  "
              f"p max {max(lat)*1e3:.1f}ms  "
              f"{n/sum(lat):.1f} img/s  top1 {hits/n:.3f} "
              f"(untrained chance {1/args.classes:.3f})")
        stats = svc.stats()
        if "slo" in stats:
            s = stats["slo"]
            print(f"slo: p99 {s['p99_s']*1e3:.1f}ms vs target "
                  f"{s['target_s']*1e3:.1f}ms  burn {s['error_budget_burn']:.2f}  "
                  f"{'READY' if s['healthy'] else 'NOT READY'}")
        print("service stats:", stats)
        if server is not None:
            server.stop()


if __name__ == "__main__":
    main()
