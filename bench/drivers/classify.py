"""Classify cells: open-loop zero-shot classification through
``repro.serving.ZeroShotService.classify``, the entry the serving launcher
uses (micro-batched image tower, registry class matrix, fused
similarity→top-k).

Set-up builds the service on the seed's weights (made on the device in
one jitted call), renders a seeded image pool, builds the class matrix
with the first call and warms every image-tower bucket and every request
size the schedule holds. The window then sends each request at its due
time from a pool of caller threads, whether or not earlier ones have
finished; a request's latency runs from its due time to its answer. The
run waits for every request sent, up to the service's request timeout.

``correct``: once the window has closed and the service is gone, a
sample of the answered requests drawn from the seed (the largest among
them) is classified by the reference, and every served (class, value)
pair of the top-k is held against the reference's logits.
"""
from __future__ import annotations

import concurrent.futures as cf
import json
import math
import os
import shutil
import threading
import time

import jax
import numpy as np

from bench import flops
from bench import harness as H
from bench import trace_reduce as TR
from bench import traffic as T
from bench.program import program_config


def run(ctx) -> H.Record:
    from repro.data import load_tokenizer
    from repro.models import dual_encoder as de
    from repro.serving import ZeroShotService
    cfg, tr = ctx.config, ctx.workload["traffic"]
    pc = program_config(cfg)
    it = cfg["image_tower"]
    sched = T.schedule(tr, ctx.seconds, ctx.seed)
    pool = T.image_pool(tr["image_pool"], it["image_size"], it["channels"],
                        ctx.seed)
    names = T.class_names(tr["classes"])
    k = int(tr["k"])
    params = jax.jit(lambda key: de.init_params(pc, key))(
        jax.random.key(ctx.seed))
    tok = load_tokenizer("v1")
    trace_dir = None
    results = [None] * len(sched.sizes)

    with ZeroShotService(pc, params, tok,
                         precision=cfg["precision"]["serve"]) as svc:
        del params
        svc.classify(pool[:1], names, k=k)              # class matrix
        for b in svc.batcher.buckets:
            svc.embed_images(pool[:b])
        for n in sorted(set(sched.sizes.tolist())):
            svc.classify(pool[:n], names, k=k)
        ex = cf.ThreadPoolExecutor(max_workers=int(tr["callers"]))
        warm_callers(ex, int(tr["callers"]),
                     lambda: svc.classify(pool[:1], names, k=k))
        before = dict(svc.batcher.stats)

        if ctx.trace:
            trace_dir = os.path.join(ctx.run_dir, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir, profiler_options=TR.options())
        with ex:
            (t0, wall0), late_submit = open_loop(
                svc, sched, pool, names, k, ex, results, ctx.trace)
        setup_s = wall0 - ctx.t_start
        if ctx.trace:
            jax.profiler.stop_trace()
        after = dict(svc.batcher.stats)
        templates, text_len = svc.templates, svc.text_len
        served_classes = np.asarray(svc.registry.get(
            names, templates, svc.checkpoint_tag,
            embed_dim=pc.embed_dim).matrix, np.float64)
    del svc             # the service and its weights go before the reference
    device = H.device_info(ctx.devices)

    lat, late, ok = [], [], []
    t_last = t0
    for i, r in enumerate(results):
        if r is None or isinstance(r[3], Exception):
            lat.append(math.inf)
            continue
        due, t_send, t_done, res = r
        late.append(t_send - due)
        lat.append(t_done - due)
        t_last = max(t_last, t_done)
        ok.append(i)
    n = len(lat)
    failed = n - len(ok)
    lat_sorted = np.sort(np.asarray(lat))
    p95 = float(lat_sorted[max(0, math.ceil(0.95 * n) - 1)]) * 1e3
    req_per_s = len(ok) / max(t_last - t0, 1e-9)
    notes = [f"window: {n} requests, {failed} failed, "
             f"{int(sched.sizes.sum())} images, p50 "
             f"{float(np.median(lat_sorted)) * 1e3:.1f} ms, p95 {p95:.1f} ms, "
             f"{req_per_s:.2f} req/s completed; generator late p50 "
             f"{np.median(late_submit) * 1e3:.3f} ms, max "
             f"{max(late_submit) * 1e3:.3f} ms; caller start late max "
             f"{max(late, default=0.0) * 1e3:.3f} ms"]
    ref_t = time.time()
    checks, more, readings = compare(
        ctx, cfg, tr, sched, pool, results, ok, failed,
        prompts(tok, names, templates, text_len), len(templates),
        served_classes)
    notes += more + [f"reference {time.time() - ref_t:.1f} s"]

    enc, pad = (after[c] - before[c] for c in ("encoded_examples",
                                               "padded_examples"))
    data = {"device": device, "chips": len(ctx.devices),
            "window_s": t_last - t0, "requests": n, "completed": len(ok),
            "encoded_examples": enc, "padded_examples": pad,
            "image_flops": flops.image_forward_flops(cfg),
            "embed_dim": cfg["embed_dim"], "classes": len(names), "k": k,
            "sizes": [int(sched.sizes[i]) for i in ok],
            "readings": readings}
    return H.Record(attempted=n, failed=failed,
                    e2e={"serve_p95_ms": p95, "serve_req_per_s": req_per_s,
                         "setup_s": setup_s},
                    checks=checks, data=data, trace_dir=trace_dir,
                    notes=notes)


def warm_callers(ex, n: int, fn):
    """Run ``fn`` once on each of the executor's ``n`` threads, so that
    no caller thread meets the service for the first time in the
    window."""
    gate = threading.Barrier(n)

    def one():
        gate.wait(timeout=120)
        fn()
    for f in [ex.submit(one) for _ in range(n)]:
        f.result()


def open_loop(svc, sched, pool, names, k, ex, results, trace=False):
    """Send every request of ``sched`` at its due time to the caller
    threads of ``ex``; fill ``results[i]`` with (due, start, end, answer
    or exception). Returns ((window start on the perf clock, on the wall
    clock), how late each request was handed to a caller)."""
    def call(i, due):
        t_send = time.perf_counter()
        lo = sched.first_image[i]
        imgs = pool[lo:lo + sched.sizes[i]]
        try:
            if trace:
                with jax.profiler.TraceAnnotation("bench/request"):
                    res = svc.classify(imgs, names, k=k)
            else:
                res = svc.classify(imgs, names, k=k)
            results[i] = (due, t_send, time.perf_counter(), res)
        except Exception as e:  # noqa: BLE001 — a failed request
            results[i] = (due, t_send, time.perf_counter(), e)

    t0, wall0 = time.perf_counter(), time.time()
    if trace:
        with jax.profiler.TraceAnnotation("bench/window"):
            pass
    futs, late = [], []
    for i, off in enumerate(sched.due_s):
        due = t0 + off
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late.append(time.perf_counter() - due)
        futs.append(ex.submit(call, i, due))
    cf.wait(futs, timeout=svc.batcher.request_timeout + 5.0)
    return (t0, wall0), late


def prompts(tok, names, templates, text_len):
    """Token ids and masks of every class × template prompt, in the
    order the prompt-ensembled class matrix averages them."""
    ids = []
    for name in names:
        parts = name.split(" ", 1)
        ids.extend(tok.encode(t.format(*parts), max_len=text_len)
                   for t in templates)
    return tok.pad_batch(ids, max_len=text_len)


def compare(ctx, cfg, tr, sched, pool, results, ok, failed, prompt_ids,
            n_templates, served_classes):
    from bench.reference import model as M
    from bench.reference import serve as RS
    rng = np.random.default_rng(ctx.seed ^ 0x5EED)
    order = sorted(ok, key=lambda i: -int(sched.sizes[i]))
    pick, total = [], 0
    if order:
        pick.append(order[0])
        total = int(sched.sizes[order[0]])
        for i in rng.permutation(order[1:]):
            if total >= tr["check_images"]:
                break
            pick.append(int(i))
            total += int(sched.sizes[i])
    imgs = np.concatenate([pool[sched.first_image[i]:sched.first_image[i]
                                + sched.sizes[i]] for i in pick]) \
        if pick else pool[:0]
    vals = np.concatenate([results[i][3].values for i in pick]) \
        if pick else np.zeros((0, tr["k"]))
    idx = np.concatenate([results[i][3].indices for i in pick]) \
        if pick else np.zeros((0, tr["k"]), np.int32)
    tokens, mask = prompt_ids
    got = {}
    for num, suffix in ((M.REFERENCE, ""), (M.STATED, "_st")):
        x, c, inv_tau = RS.embeddings(
            cfg, ctx.seed, imgs, tokens, mask, n_templates, num=num,
            block_rows=cfg["reference"]["block_rows"])
        got.update({k + suffix: v for k, v in RS.readings(
            x, c, inv_tau, vals, idx, served_classes, num).items()})
    checks = [H.Check(name, got[name], limit)
              for name, limit in ctx.workload["limits"].items()]
    checks.append(H.Check("missing", float(failed), 0.0))
    return checks, [f"checked {len(pick)} requests, {len(imgs)} images "
                    f"(largest {int(sched.sizes[pick[0]]) if pick else 0})",
                    "readings " + json.dumps(got)], got
