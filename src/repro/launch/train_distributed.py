"""Distributed trainer: the pjit production loop at any mesh size.

The same code path drives a 1-device dev box and the 16×16 pod: params are
initialized DIRECTLY into their shardings (no host-side full copy), the step
is jitted with donated buffers, data comes from the shard-aware prefetching
pipeline, and checkpoints round-trip with resume.

Two objectives share the loop (``--objective`` defaults to ``auto``: picked
by arch family):

  lm           — next-token loss on a single transformer (LM archs)
  contrastive  — the paper's dual-encoder objective: Algorithm-1 GradAccum
                 (``--num-micro``) over the GLOBAL batch, with the
                 cross-shard global-batch loss (``--loss allgather`` or
                 ``--loss chunked``, core/distributed_loss.py) so the
                 contrastive batch does NOT shrink with the data-parallel
                 degree; per-tower remat via ``--remat-image`` /
                 ``--remat-text`` (DESIGN.md §7). Images are RAW pixels
                 through the patchify frontend (DESIGN.md §8).

Both objectives take ``--precision {f32,bf16,bf16_pure}`` (models.precision
policy; fp32 norms/projections/logits stay on under bf16) and ``--attn
{naive,chunked,pallas,auto}`` (models.attention backend registry; 'pallas'
runs the kernels/flash_attention fwd+bwd kernels).

The contrastive input side runs on the multi-host sharded data subsystem
(DESIGN.md §9): versioned tokenizer artifact (``--tokenizer v1``),
per-data-shard block layout assembled with
``jax.make_array_from_process_local_data``, optional ``--augment on``, and
loader state checkpointed alongside params so resume replays the exact
batch sequence.

  python -m repro.launch.train_distributed --arch llama3.2-1b --smoke \\
      --steps 50 --batch 8 --seq 128 --model-parallel 1 --ckpt-dir /tmp/ck

  python -m repro.launch.train_distributed --arch basic-s --smoke \\
      --steps 20 --batch 32 --num-micro 2 --loss chunked

``--memstats`` prints the compiled per-step memory/FLOPs report
(launch/memstats.py) before training starts.

Fault tolerance (DESIGN.md §10): checkpoints are written ASYNCHRONOUSLY
(``checkpoint.AsyncCheckpointManager`` — the step only pays for the host
snapshot; ``--ckpt-sync`` restores the blocking path), carry per-leaf
sha256 integrity records, and are retained per ``--ckpt-keep`` /
``--ckpt-keep-every``. ``--resume auto`` restores params/opt-state/loader
input state from the newest checkpoint that VERIFIES — torn or corrupt
step dirs are skipped, stale ``.tmp_ckpt_*`` dirs GC'd. SIGTERM (the
cluster preemption signal) triggers a final sync checkpoint after the
in-flight step, and persistent async-write failures degrade the run to
sync checkpointing after capped-backoff retries.

Telemetry (DESIGN.md §11): with ``--run-dir`` (default: ``--ckpt-dir``)
the loop streams one schema-versioned JSONL record per step to
``<run-dir>/runlog.jsonl`` — loss, grad-norm, examples/sec, and the
data-wait / device-step / ckpt-stall breakdown — plus checkpoint /
degrade / resume marker records, and exports a Chrome ``trace_event``
JSON (``trace.json``, Perfetto-viewable) on exit. Each step also runs
under ``jax.profiler.StepTraceAnnotation("train")`` with its phases as
``repro/train/*`` spans (data_wait, dispatch, wait, ckpt_stall, log) and
the prefetch thread's ``repro/data/render`` / ``repro/data/put`` /
``repro/data/queue_wait``, so any profiler capture lines them up with the
device's ops.
``--log-every N`` paces the human stdout line, ``--quiet`` silences it;
summarize a run with ``python -m repro.obs.report <run-dir>/runlog.jsonl``.

Health (DESIGN.md §14): ``--health`` arms the anomaly detector suite
(non-finite loss/grad, grad/loss spikes via windowed MAD z-score, loss
plateau, data-wait stall, per-host straggler skew) — anomalies land in
the runlog, as trace instants, and as flight-recorder dumps under
``<run-dir>/flight/`` — and switches the jitted step to non-finite-grad
skipping (the poisoned update is dropped ON DEVICE; finite steps are
bit-exact with the unguarded path). ``--metrics-port P`` serves live
Prometheus ``/metrics``, ``/healthz`` and ``/snapshot.json`` on
127.0.0.1:P for the whole run (0 picks an ephemeral port, written to
``<run-dir>/metrics_port``).
"""
from __future__ import annotations

import argparse
import math
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro import obs
from repro.obs import health as obs_health
from repro.obs import trace as obs_trace
from repro.configs import get_arch, smoke_variant
from repro.core import sharding as shd
from repro.core.remat import get_policy, list_policies
from repro.data.pipeline import Prefetcher, host_rng
from repro.launch import compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import frontends, transformer as tf
from repro.optim import AdaFactorW, apply_updates, warmup_cosine


def build_state(init_fn, mesh, mode, opt, seed):
    """Init params/opt-state directly into their shardings.

    init_fn(key) -> params pytree (LM or dual-encoder). Returns
    (params, opt_state, param shardings, opt-state shardings)."""
    params_abs = jax.eval_shape(init_fn, jax.random.key(seed))
    pspecs = shd.to_named(shd.params_specs(params_abs, mesh, mode), mesh)
    params = jax.jit(init_fn, out_shardings=pspecs)(jax.random.key(seed))
    opt_abs = jax.eval_shape(opt.init, params_abs)
    ospecs = shd.to_named(shd.params_specs(opt_abs, mesh, mode), mesh)
    opt_state = jax.jit(opt.init, out_shardings=ospecs)(params)
    return params, opt_state, pspecs, ospecs


def make_step(cfg, opt, lr_fn, *, remat="basic", moe_args=None,
              precision="f32", skip_nonfinite=False):
    """LM train step: next-token loss + AdaFactorW update, jit-ready.
    ``precision``: models.precision policy name (historical default f32).

    ``skip_nonfinite=True`` arms the in-jit step guard (DESIGN.md §14.2):
    a non-finite loss or grad norm keeps the INCOMING params/opt-state
    via an elementwise ``jnp.where`` select — the poisoned update never
    lands, no host round-trip, donation-safe — and ``metrics`` gains a
    0/1 ``skipped`` flag. Finite steps take the identical update values,
    so guarded training is bit-exact with unguarded training."""
    policy = get_policy(remat)

    def train_step(params, opt_state, batch, step):
        def loss_fn(p):
            loss, metrics = tf.lm_loss(cfg, p, batch, remat_policy=policy,
                                       precision=precision,
                                       moe_args=moe_args)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                             for g in jax.tree.leaves(grads)))
        updates, new_opt = opt.update(grads, opt_state, params,
                                      lr_fn(step))
        new_params = apply_updates(params, updates)
        metrics = dict(metrics, grad_norm=gnorm)
        if skip_nonfinite:
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_opt, opt_state)
            metrics["skipped"] = (~ok).astype(jnp.int32)
        return new_params, new_opt, loss, metrics

    return train_step


def _make_manager(args, registry=None):
    """The run's AsyncCheckpointManager (None without --ckpt-dir):
    ``--ckpt-sync`` degrades to the blocking path, ``--ckpt-keep`` /
    ``--ckpt-keep-every`` set the retention policy (DESIGN.md §10.3).
    ``registry``: the run's obs.Registry, so checkpoint counters and the
    write-latency histogram land in the same snapshot as everything
    else."""
    if not args.ckpt_dir:
        return None
    return ckpt.AsyncCheckpointManager(
        args.ckpt_dir,
        sync=bool(getattr(args, "ckpt_sync", False)),
        keep_last=int(getattr(args, "ckpt_keep", 0) or 0),
        keep_every=int(getattr(args, "ckpt_keep_every", 0) or 0),
        registry=registry)


def _make_obs(args, resumed_from):
    """The run's telemetry bundle (DESIGN.md §11): a metrics Registry
    (always — subsystem counters are cheap), plus a span Tracer and a
    schema-versioned RunLogger when the run has a directory to stream
    into (``--run-dir``, defaulting to ``--ckpt-dir``). A resumed run
    APPENDS to the existing runlog with a ``resumed_from`` marker record
    instead of interleaving a second run_start header."""
    run_dir = getattr(args, "run_dir", None) or args.ckpt_dir
    registry = obs.Registry()
    tracer = runlog = None
    if run_dir:
        os.makedirs(run_dir, exist_ok=True)
        tracer = obs.Tracer()
        meta = {"arch": getattr(args, "arch", None),
                "objective": getattr(args, "objective", "auto"),
                "batch": getattr(args, "batch", None),
                "steps": getattr(args, "steps", None),
                "seed": getattr(args, "seed", None)}
        runlog = obs.RunLogger(os.path.join(run_dir, "runlog.jsonl"),
                               meta=meta,
                               resumed_from=resumed_from or None)
    return registry, tracer, runlog, run_dir


def _make_health(args, registry, tracer, runlog, run_dir):
    """The run's active-monitoring pair (DESIGN.md §14): a
    ``HealthMonitor`` when ``--health`` is set (default detector suite +
    flight recorder into the run dir) and a started ``MetricsServer``
    when ``--metrics-port`` is given (0 = ephemeral; the bound port is
    written to ``<run_dir>/metrics_port``). Either can be on without the
    other; ``/healthz`` reports the monitor's status when both are."""
    monitor = server = None
    if getattr(args, "health", False):
        monitor = obs.HealthMonitor(registry=registry, tracer=tracer,
                                    runlog=runlog, run_dir=run_dir)
    port = getattr(args, "metrics_port", None)
    if port is not None:
        server = obs.MetricsServer(
            registry, health=monitor.status if monitor else None,
            port=int(port), run_dir=run_dir).start()
        if not getattr(args, "quiet", False):
            print(f"obs: serving /metrics /healthz /snapshot.json on "
                  f"{server.url}")
    return monitor, server


def _run_loop(args, step_fn, params, opt_state, make_batch, start, *,
              step_takes_index, ckpt_meta_fn=None, registry=None,
              tracer=None, runlog=None, run_dir=None, monitor=None,
              server=None):
    """Shared prefetch/step/log/checkpoint loop; returns per-step losses.
    ``ckpt_meta_fn(next_step) -> dict``: optional user-meta (e.g. resumable
    loader input state) written into every checkpoint step dir.

    Telemetry (DESIGN.md §11): every step appends one schema-versioned
    JSONL record to ``runlog`` — loss, grad-norm, examples/sec, and the
    data-wait / device-step / ckpt-stall time breakdown — while stdout
    only gets the human line every ``--log-every`` steps (``--quiet``
    silences it entirely). Each iteration runs under
    ``StepTraceAnnotation("train", step_num=i)`` and its phases are
    profiler spans carrying ``step``: ``train/data_wait`` (``next``),
    ``train/dispatch`` (the call into the jitted step, until it
    returns), ``train/wait`` (``float(loss)``, until the device step
    ends), ``train/ckpt_stall`` and ``train/log`` (the metric reads, the
    run-log write and the health monitor). The run log's
    ``device_step_s`` is dispatch + wait. ``tracer`` keeps the same
    spans in its ring; the Chrome trace JSON is exported to
    ``<run_dir>/trace.json`` when the loop ends. All of it is host-side
    work OUTSIDE the jitted step.

    Health (DESIGN.md §14): with a ``monitor`` every step's host-side
    floats feed the anomaly detectors (anomaly runlog records, trace
    instants, ``health/*`` counters, flight-recorder dumps); a ``server``
    keeps ``/metrics`` + ``/healthz`` live for the whole run and is shut
    down on exit. The module-level step fault hook (obs/health.py) is
    applied to every batch right before the device step — the chaos seam
    the NaN-injection acceptance test drives.

    Checkpoints go through the async manager (serialize + rename off the
    step path; DESIGN.md §10). SIGTERM — the preemption signal — is caught:
    the loop finishes the step in flight, writes a final SYNC checkpoint,
    and returns early, so a preempted run resumes from its very last step.
    A persistent async-write failure (after the manager's capped-backoff
    retries) degrades the run to synchronous checkpointing rather than
    training on without durability."""
    stop = getattr(args, "stop_after", None) or args.steps
    stream = Prefetcher(make_batch, depth=2, start=start)
    t0, losses = time.time(), []
    quiet = bool(getattr(args, "quiet", False))
    manager = _make_manager(args, registry)
    preempted = threading.Event()
    prev_handler = None
    if threading.current_thread() is threading.main_thread():
        prev_handler = signal.signal(
            signal.SIGTERM, lambda signum, frame: preempted.set())
    preempt_after = getattr(args, "preempt_after", None)

    def save(step, *, final=False, event="save"):
        """Checkpoint + degrade-on-failure; returns the loop stall in
        seconds (the runlog/step record's ``ckpt_stall_s`` share)."""
        meta = ckpt_meta_fn(step) if ckpt_meta_fn else None
        tree = (params, opt_state)
        t_save = time.perf_counter()
        try:
            if final:
                manager.save_sync(step, tree, meta=meta)
            else:
                manager.save(step, tree, meta=meta)
        except ckpt.CheckpointError as e:
            # a previous async write died after retries — don't keep
            # training without durability: degrade to blocking saves and
            # re-write this step synchronously
            print(f"ckpt: async write failed ({e}); degrading to sync")
            manager.degrade_to_sync()
            if runlog:
                runlog.log("checkpoint", step=step,
                           event="degrade_to_sync", error=str(e))
            manager.save_sync(step, tree, meta=meta)
        stall = time.perf_counter() - t_save
        if runlog:
            runlog.log("checkpoint", step=step, event=event,
                       sync=bool(final or manager.sync), stall_s=stall)
        return stall

    def log_step(i, loss_f, metrics, **times):
        """The step's metric reads, run-log record, health check and
        human line."""
        gnorm_f = (float(metrics["grad_norm"])
                   if metrics.get("grad_norm") is not None else None)
        skipped = bool(float(metrics.get("skipped", 0)))
        step_rec = None
        if runlog:
            extra = {} if gnorm_f is None else {"grad_norm": gnorm_f}
            if skipped:
                extra["skipped"] = 1
            step_rec = runlog.log_step(
                i, loss=loss_f, examples_per_sec=args.batch / times["step_s"],
                **times, **extra)
        if monitor is not None:
            monitor.observe_step(obs.StepSample(
                step=i, loss=loss_f,
                grad_norm=math.nan if gnorm_f is None else gnorm_f,
                data_wait_s=times["data_wait_s"],
                device_step_s=times["device_step_s"],
                step_s=times["step_s"], skipped=skipped), record=step_rec)
        if not quiet and (i % args.log_every == 0 or i == args.steps - 1):
            gtxt = "" if gnorm_f is None else f"gnorm {gnorm_f:.2f} "
            print(f"step {i:5d} loss {loss_f:.4f} {gtxt}"
                  f"{(time.time()-t0)/max(1, i-start+1):.2f}s/step")

    final_saved = False
    try:
        for i in range(start, min(args.steps, stop)):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                t_iter = time.perf_counter()
                with obs_trace.span(tracer, "train/data_wait", step=i):
                    batch = next(stream)
                batch = obs_health.apply_step_fault_hook(i, batch)
                t_data = time.perf_counter()
                with obs_trace.span(tracer, "train/dispatch", step=i):
                    if step_takes_index:
                        params, opt_state, loss, metrics = step_fn(
                            params, opt_state, batch, jnp.asarray(i))
                    else:
                        params, opt_state, loss, metrics = step_fn(
                            params, opt_state, batch)
                with obs_trace.span(tracer, "train/wait", step=i):
                    loss_f = float(loss)   # until the device step ends
                t_device = time.perf_counter()
                losses.append(loss_f)
                ckpt_stall, breaking = 0.0, False
                if preempt_after is not None and \
                        i - start + 1 == preempt_after:
                    # simulated-preemption hook: deliver a REAL SIGTERM to
                    # ourselves so tests exercise the exact signal path
                    os.kill(os.getpid(), signal.SIGTERM)
                if preempted.is_set():
                    if args.ckpt_dir:
                        print(f"SIGTERM: preemption checkpoint at step "
                              f"{i + 1}")
                        with obs_trace.span(tracer, "train/ckpt_stall",
                                            step=i):
                            ckpt_stall += save(i + 1, final=True,
                                               event="preempt_save")
                    final_saved = breaking = True
                elif args.ckpt_dir and args.ckpt_every and \
                        (i + 1) % args.ckpt_every == 0:
                    with obs_trace.span(tracer, "train/ckpt_stall", step=i):
                        ckpt_stall += save(i + 1)
                step_s = time.perf_counter() - t_iter
                with obs_trace.span(tracer, "train/log", step=i):
                    log_step(i, loss_f, metrics, data_wait_s=t_data - t_iter,
                             device_step_s=t_device - t_data,
                             ckpt_stall_s=ckpt_stall, step_s=step_s)
            if breaking:
                break
    finally:
        stream.close()
        if prev_handler is not None:
            signal.signal(signal.SIGTERM, prev_handler)
    if args.ckpt_dir and not final_saved:
        with obs_trace.span(tracer, "train/ckpt_stall",
                            step=min(args.steps, stop)):
            save(min(args.steps, stop), final=True, event="final_save")
    if manager is not None:
        manager.close()
    trace_path = None
    if tracer is not None and run_dir:
        trace_path = tracer.export(os.path.join(run_dir, "trace.json"))
    if runlog:
        if trace_path:
            # dropped > 0 means the exported timeline is truncated at the
            # old end — report.py surfaces it as a warning
            runlog.log("event", event="trace_export", path=trace_path,
                       dropped=tracer.dropped)
        if registry is not None:
            runlog.log("metrics", **registry.snapshot())
        runlog.close()
    if trace_path and not quiet:
        print(f"obs: trace -> {trace_path} (open in Perfetto)")
    if server is not None:
        server.stop()
    return losses


def _restore(args, params, opt_state, pspecs, ospecs):
    """Resume per ``--resume``: ``auto`` (default) restores from
    ``latest_verified_step`` — torn/corrupt step dirs are skipped and
    stale ``.tmp_ckpt_*`` dirs GC'd, so a crash mid-save can never wedge
    the relaunch; ``latest`` trusts the newest step dir (the historical
    behavior); ``off`` starts fresh."""
    start = 0
    resume = getattr(args, "resume", None) or "auto"
    if args.ckpt_dir and resume != "off":
        latest = (ckpt.latest_verified_step(args.ckpt_dir)
                  if resume == "auto" else ckpt.latest_step(args.ckpt_dir))
        if latest:
            like = jax.eval_shape(lambda: (params, opt_state))
            params, opt_state = ckpt.restore(args.ckpt_dir, latest, like,
                                             shardings=(pspecs, ospecs))
            start = latest
            print(f"resumed from step {start} (--resume {resume})")
    return params, opt_state, start


def train_lm(args):
    """LM objective at any mesh size; returns the per-step loss list."""
    import dataclasses
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if getattr(args, "attn", None):
        cfg = dataclasses.replace(cfg, attn_impl=args.attn)
    mesh = make_local_mesh(model=args.model_parallel)
    opt = AdaFactorW(weight_decay=0.0025)
    lr_fn = warmup_cosine(args.lr, args.lr / 100,
                          max(1, args.steps // 10), args.steps)
    moe_args = {"dispatch": "dense"} if args.smoke else None
    precision = getattr(args, "precision", None) or "f32"

    with mesh:
        params, opt_state, pspecs, ospecs = build_state(
            lambda k: tf.init_params(cfg, k), mesh, args.sharding, opt,
            args.seed)
        params, opt_state, start = _restore(args, params, opt_state,
                                            pspecs, ospecs)
        registry, tracer, runlog, run_dir = _make_obs(args, start)
        monitor, server = _make_health(args, registry, tracer, runlog,
                                       run_dir)
        step_fn = jax.jit(make_step(cfg, opt, lr_fn, remat=args.remat,
                                    moe_args=moe_args, precision=precision,
                                    skip_nonfinite=bool(
                                        getattr(args, "health", False))),
                          donate_argnums=(0, 1))

        def make_batch(step):
            rng = host_rng(args.seed, 0, step)
            b = frontends.synthetic_inputs(cfg, args.batch, args.seq, rng)
            return jax.tree.map(jnp.asarray, b)

        return _run_loop(args, step_fn, params, opt_state, make_batch, start,
                         step_takes_index=True, registry=registry,
                         tracer=tracer, runlog=runlog, run_dir=run_dir,
                         monitor=monitor, server=server)


def train_contrastive(args):
    """Paper objective: GradAccum × data-parallel × tensor-parallel with the
    cross-shard global-batch contrastive loss, one jit. Returns the
    per-step loss list.

    Input side (DESIGN.md §9): the versioned tokenizer artifact
    (``artifacts/tokenizer_v1.json`` — NOT retrained per run, so text-tower
    checkpoints stay portable), a ``data.sharded.ShardedLoader`` laid out
    with one host block per data shard (global batches assemble to
    globally-sharded jax.Arrays via ``make_array_from_process_local_data``),
    optional ``--augment`` train-time augmentation, and resumable loader
    state persisted as checkpoint user-meta — a resumed run validates the
    tokenizer hash/layout and replays the exact batch sequence."""
    from repro.configs import smoke_dual_variant
    from repro.data import world_for_tower
    from repro.data.sharded import (HostLayout, ShardedLoader,
                                    default_augmentations, device_put_global,
                                    load_tokenizer)
    from repro.data.sharded.loader import LoaderState
    from repro.launch import steps as st
    from repro.models import dual_encoder as de

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_dual_variant(cfg)
    mesh = make_local_mesh(model=args.model_parallel)
    num_micro = getattr(args, "num_micro", 2)
    loss = getattr(args, "loss", "chunked")

    data_size = int(np.prod([mesh.shape[a] for a in shd.data_axes(mesh)
                             if a in mesh.shape]))
    if args.batch % num_micro:
        raise SystemExit(f"--batch {args.batch} must be divisible by "
                         f"--num-micro {num_micro}")
    if loss in ("allgather", "chunked"):
        if args.batch % data_size:
            raise SystemExit(
                f"--loss {loss}: --batch {args.batch} must be divisible by "
                f"the data extent {data_size} (one equal block per shard)")
        if (args.batch // data_size) % 8:
            raise SystemExit(
                f"--loss {loss}: per-shard batch {args.batch}/{data_size} "
                f"must be a multiple of 8 (fused-kernel tiling; see "
                f"kernels.contrastive_loss.ops.pick_blocks)")

    step_core, opt = st.make_contrastive_step(
        cfg, num_micro=num_micro, remat=args.remat,
        remat_image=getattr(args, "remat_image", None),
        remat_text=getattr(args, "remat_text", None),
        precision=getattr(args, "precision", None) or "bf16",
        attn=getattr(args, "attn", None),
        lr=args.lr, mesh=mesh, loss=loss,
        skip_nonfinite=bool(getattr(args, "health", False)))

    with mesh:
        params, opt_state, pspecs, ospecs = build_state(
            lambda k: de.init_params(cfg, k), mesh, args.sharding, opt,
            args.seed)
        params, opt_state, start = _restore(args, params, opt_state,
                                            pspecs, ospecs)
        # pin the state's output shardings to its input shardings: the
        # donated loop then reuses ONE executable (and the --memstats AOT
        # compile below is the same one the loop runs)
        step_fn = jax.jit(step_core, donate_argnums=(0, 1),
                          out_shardings=(pspecs, ospecs, None, None))

        world_rng = np.random.default_rng(args.seed)
        world = world_for_tower(world_rng, cfg.image_tower, n_classes=16,
                                noise=0.2)
        tok = load_tokenizer(getattr(args, "tokenizer", None) or "v1")
        augment = default_augmentations() \
            if getattr(args, "augment", "off") == "on" else ()
        if jax.process_count() > 1:
            # the loader's per-host blocks (HostLayout, local_batch_at) are
            # multi-process-ready, but this trainer still materializes the
            # FULL global batch per process — fail loudly rather than feed
            # make_array_from_process_local_data global-shaped data
            # (ROADMAP: "True multi-process input")
            raise NotImplementedError(
                "train_contrastive simulates multi-host input inside one "
                "process; wiring jax.process_index() into HostLayout is a "
                "ROADMAP item")
        registry, tracer, runlog, run_dir = _make_obs(args, start)
        monitor, server = _make_health(args, registry, tracer, runlog,
                                       run_dir)
        # one host block per data shard: block h of the global batch lands
        # on data shard h, the §5.1 "distributed equally to all cores" layout
        loader = ShardedLoader(world, tok, args.batch,
                               layout=HostLayout(n_hosts=data_size),
                               seed=args.seed, text_len=args.seq,
                               augment=augment, registry=registry,
                               tracer=tracer)
        if start and args.ckpt_dir and \
                (meta := ckpt.load_meta(args.ckpt_dir, start)) \
                and "loader" in meta:
            # validates seed/layout/tokenizer-hash/augment against the
            # checkpointed input state — a retrained tokenizer or changed
            # augmentation policy fails here instead of silently diverging
            loader.restore(LoaderState.from_json(meta["loader"]))

        def make_batch(step):
            with obs_trace.span(tracer, "data/render", step=step):
                host = loader.global_batch_at(step)
            with obs_trace.span(tracer, "data/put", step=step):
                return device_put_global(host, mesh)

        def ckpt_meta_fn(next_step):
            return {"loader": loader.state(step=next_step).to_json()}

        if getattr(args, "memstats", False):
            from repro.launch import memstats
            # AOT-compile once, report, and run the loop on the SAME
            # executable (jit's dispatch cache ignores lower().compile(),
            # so calling step_fn afterwards would compile a second time)
            compiled = step_fn.lower(params, opt_state,
                                     make_batch(start)).compile()
            print(memstats.format_rows([memstats.compiled_stats(
                compiled,
                label=f"{args.arch} B={args.batch} micro={num_micro} "
                      f"loss={loss} remat={args.remat}")]))
            step_fn = compiled

        return _run_loop(args, step_fn, params, opt_state, make_batch, start,
                         step_takes_index=False, ckpt_meta_fn=ckpt_meta_fn,
                         registry=registry, tracer=tracer, runlog=runlog,
                         run_dir=run_dir, monitor=monitor, server=server)


def train(args):
    """Dispatch on objective (``auto``: contrastive for dual-encoder archs,
    i.e. configs without a ``family`` attribute; lm otherwise)."""
    objective = getattr(args, "objective", "auto")
    if objective == "auto":
        objective = ("lm" if hasattr(get_arch(args.arch), "family")
                     else "contrastive")
    if objective == "lm":
        return train_lm(args)
    return train_contrastive(args)


def parser() -> argparse.ArgumentParser:
    """The trainer's command line (also how ``chip_smoke.py`` builds its
    arguments, so both share every default)."""
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True,
                    help="arch name from repro.configs (LM archs train the "
                         "lm objective; basic-{s,m,l} train contrastive)")
    ap.add_argument("--objective", default="auto",
                    choices=["auto", "lm", "contrastive"])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-sized variant of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch (split over the data axes)")
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length (lm) / caption length "
                         "(contrastive)")
    ap.add_argument("--lr", type=float, default=1e-3,
                    help="peak LR (lm: warmup-cosine schedule; "
                         "contrastive: constant)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sharding", default="basic_ws",
                    choices=["basic_ws", "tp", "replicated"])
    remat_names = list_policies() + ["off"]   # 'off': no checkpoint wrapping
    ap.add_argument("--remat", default="basic", choices=remat_names,
                    help="jax.checkpoint policy (core.remat registry; "
                         "'off' applies no checkpoint wrapping at all)")
    ap.add_argument("--remat-image", default=None, choices=remat_names,
                    help="override --remat for the image tower "
                         "(contrastive only)")
    ap.add_argument("--remat-text", default=None, choices=remat_names,
                    help="override --remat for the text tower "
                         "(contrastive only)")
    ap.add_argument("--precision", default=None,
                    choices=["f32", "bf16", "bf16_pure"],
                    help="mixed-precision policy (models.precision; "
                         "default: f32 for lm, bf16 for contrastive — the "
                         "historical dtypes)")
    ap.add_argument("--attn", default=None,
                    choices=["naive", "chunked", "pallas", "auto"],
                    help="attention backend override for every tower "
                         "(models.attention registry)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--num-micro", type=int, default=2,
                    help="GradAccum microbatches (contrastive only)")
    ap.add_argument("--loss", default="chunked",
                    choices=["local", "fused", "allgather", "chunked"],
                    help="contrastive loss impl (core.distributed_loss; "
                         "'local'/'fused' compute on the logical global "
                         "batch without explicit cross-shard collectives)")
    ap.add_argument("--memstats", action="store_true",
                    help="print the compiled per-step memory/FLOPs report "
                         "before training (launch/memstats.py)")
    ap.add_argument("--augment", default="off", choices=["on", "off"],
                    help="train-time image augmentation (crop jitter + "
                         "flip + channel noise; data.sharded.augment, "
                         "contrastive only)")
    ap.add_argument("--tokenizer", default="v1",
                    help="tokenizer artifact version to load "
                         "(artifacts/tokenizer_<v>.json; contrastive only)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="print a human step line every N steps (the "
                         "runlog gets EVERY step regardless)")
    ap.add_argument("--quiet", action="store_true",
                    help="no per-step stdout lines; telemetry still "
                         "streams to the runlog")
    ap.add_argument("--health", action="store_true",
                    help="active monitoring (DESIGN.md §14): anomaly "
                         "detectors on loss/grad/data-wait (anomaly "
                         "runlog records + flight-recorder dumps into "
                         "the run dir) and in-jit non-finite step "
                         "skipping — a NaN loss/grad keeps the incoming "
                         "params instead of poisoning them")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live /metrics (Prometheus), /healthz and "
                         "/snapshot.json on 127.0.0.1:PORT for the whole "
                         "run (0 = ephemeral; the bound port is written "
                         "to <run-dir>/metrics_port)")
    ap.add_argument("--run-dir", default=None,
                    help="directory for runlog.jsonl + trace.json "
                         "(default: --ckpt-dir; no files when neither "
                         "is set). DESIGN.md §11")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-sync", action="store_true",
                    help="blocking checkpoint writes (default: async — "
                         "snapshot on the step path, serialize + atomic "
                         "rename on a background thread)")
    ap.add_argument("--ckpt-keep", type=int, default=0,
                    help="retention: keep only the newest K checkpoints "
                         "(0 = keep all)")
    ap.add_argument("--ckpt-keep-every", type=int, default=0,
                    help="retention: additionally keep every Nth step "
                         "forever (0 = none)")
    ap.add_argument("--resume", default="auto",
                    choices=["auto", "latest", "off"],
                    help="auto: resume from the newest checkpoint that "
                         "passes integrity verification (torn/corrupt "
                         "steps skipped, stale tmp dirs GC'd); latest: "
                         "trust the newest step dir; off: start fresh")
    ap.add_argument("--preempt-after", type=int, default=None,
                    help="chaos hook: SIGTERM ourselves after N steps — "
                         "exercises the preemption path (final sync "
                         "checkpoint + clean exit) deterministically")
    ap.add_argument("--stop-after", type=int, default=None,
                    help="halt early but keep the --steps LR horizon")
    return ap


def main():
    args = parser().parse_args()
    compile_cache.enable()
    train(args)


if __name__ == "__main__":
    main()
