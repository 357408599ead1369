"""Train cells: the window is the program's own training loop.

``repro.launch.train_distributed.train`` runs as a user runs it: the
jitted Algorithm-1 step with its donated state, the prefetching sharded
loader, the run log and the per-step ``float(loss)``. The benchmark only
wraps the step function the loop is handed, to

- keep host copies of the batches of the three compared steps and of
  the weights before the first step,
  read the first gradient's per-leaf norms from the optimizer's state
  after one step, and the per-leaf change of the weights after the
  compared steps (all in set-up, before the window);
- mark the window's start at the end of the last warm-up step, and end
  it through the loop's own stop path: SIGTERM, after which the loop
  finishes the step in flight and returns.

``pairs_per_s`` is the pairs of every step completed in the window over
the window's wall time, from its start to the end of its last step (the
run log's time of that step). After the window the reference drives the
same steps from the same seed and batches; ``correct`` compares each
step's loss, the first gradient's per-leaf norms and the weights'
per-leaf change. ``peak_hbm_gib.train`` and the device line's memory
peak take the timed step's own compiled footprint (arguments, temporaries
and outputs not aliased to arguments, from its memory analysis), since
the allocator's ``peak_bytes_in_use`` leaves the step's temporaries out.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench import harness as H
from bench import trace_reduce as TR
from bench.program import program_config


COMPARED_STEPS = 3     # the steps the reference follows


def grad_norms_from_state(opt_state, params, beta2: float) -> dict:
    """Per-leaf norms of the first gradient, as the optimizer got it,
    from its second moment after one step: a full moment holds
    (1-β2)·g², a factored one the row means (1-β2)·mean(g², -1)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    moments = jax.tree.leaves(opt_state.v_row)
    sums = jax.device_get(jax.jit(lambda v: [jnp.sum(x) for x in v])(
        moments))
    out = {}
    for (path, p), v, s in zip(flat, moments, sums):
        cols = 1 if v.shape == p.shape else p.shape[-1]
        out[jax.tree_util.keystr(path)] = float(
            np.sqrt(max(float(s) * cols / (1.0 - beta2), 0.0)))
    return out


def host_norms(tree, base=None) -> dict:
    """{leaf path: float64 norm} of a host tree (minus ``base``)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    base_leaves = None if base is None else jax.tree.leaves(base)
    out = {}
    for i, (k, v) in enumerate(flat):
        a = np.asarray(v, np.float32)
        if base_leaves is not None:
            a = a - np.asarray(base_leaves[i], np.float32)
        out[jax.tree_util.keystr(k)] = float(np.sqrt(np.sum(
            np.square(a, dtype=np.float64))))
    return out


class StepCapture:
    """Wraps the loop's compiled step; watches the warm-up steps and
    opens the window."""

    def __init__(self, step_fn, seconds: float, beta2: float, trace_dir):
        self.step_fn, self.seconds, self.beta2 = step_fn, seconds, beta2
        self.compared, self.warm = COMPARED_STEPS, COMPARED_STEPS + 1
        self.trace_dir = trace_dir
        self.calls = 0
        self.batches, self.p0 = [], None
        self.grad0, self.change = None, None
        self.t_window = None
        self.timer = None
        self.abstract = None        # the step's argument shapes

    def __call__(self, params, opt_state, batch):
        i = self.calls
        self.calls += 1
        if i < self.compared:
            self.batches.append(jax.device_get(batch))
        if i == 0:
            self.abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                (params, opt_state, batch))
            self.p0 = jax.device_get(params)
        elif i == 1:
            self.grad0 = grad_norms_from_state(opt_state, params, self.beta2)
        elif i == self.compared:
            self.change = host_norms(jax.device_get(params), self.p0)
            self.p0 = None
        if self.trace_dir and i >= self.warm:
            with jax.profiler.TraceAnnotation("bench/dispatch", step=i):
                out = self.step_fn(params, opt_state, batch)
        else:
            out = self.step_fn(params, opt_state, batch)
        if i == self.warm - 1:
            jax.block_until_ready(out)
            self._open_window()
        return out

    def _open_window(self):
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=TR.options())
            with jax.profiler.TraceAnnotation("bench/window"):
                pass
        self.t_window = time.time()
        self.timer = threading.Timer(
            self.seconds, lambda: os.kill(os.getpid(), signal.SIGTERM))
        self.timer.daemon = True
        self.timer.start()


def run(ctx) -> H.Record:
    from repro.launch import train_distributed as td
    wl, cfg, tr = ctx.workload, ctx.config, ctx.workload["traffic"]
    program_config(cfg)
    opt = cfg["optimizer"]
    shutil.rmtree(ctx.run_dir, ignore_errors=True)
    os.makedirs(ctx.run_dir)
    trace_dir = os.path.join(ctx.run_dir, "trace") if ctx.trace else None
    argv = ["--arch", cfg["arch"], "--steps", str(10 ** 9),
            "--batch", str(tr["batch"]), "--seq", str(tr["text_len"]),
            "--num-micro", str(tr["num_micro"]), "--loss", tr["loss"],
            "--remat", tr["remat"], "--precision", cfg["precision"]["train"],
            "--lr", repr(opt["lr"]), "--seed", str(ctx.seed),
            "--run-dir", ctx.run_dir, "--quiet"]
    if cfg.get("smoke"):
        argv.append("--smoke")
    args = td.parser().parse_args(argv)

    cap = StepCapture(None, ctx.seconds, opt["beta2"], trace_dir)
    orig_loop = td._run_loop

    def run_loop(a, step_fn, params, opt_state, make_batch, start, **kw):
        cap.step_fn = step_fn
        return orig_loop(a, cap, params, opt_state, make_batch, start, **kw)

    orig_prefetcher = td.Prefetcher

    class SpannedPrefetcher(orig_prefetcher):
        """The loop's prefetcher, its waits marked in the trace."""

        def __next__(self):
            with jax.profiler.TraceAnnotation("bench/data_wait"):
                return super().__next__()

    td._run_loop = run_loop
    if trace_dir:
        td.Prefetcher = SpannedPrefetcher
    try:
        losses = td.train(args)
    finally:
        td._run_loop, td.Prefetcher = orig_loop, orig_prefetcher
        if cap.timer is not None:
            cap.timer.cancel()
            cap.timer.join()
    if trace_dir:
        jax.profiler.stop_trace()
    device = H.device_info(ctx.devices)
    footprint = step_footprint(cap.step_fn, cap.abstract)
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"],
                                      footprint)

    with open(os.path.join(ctx.run_dir, "runlog.jsonl")) as f:
        steps = [r for r in map(json.loads, f) if r.get("kind") == "step"]
    window = [r for r in steps if r["step"] >= cap.warm]
    if cap.t_window is None or not window:
        raise H.BenchError(f"no step completed in the window "
                           f"({len(steps)} steps in all)")
    window_s = window[-1]["t"] - cap.t_window
    batch = int(tr["batch"])
    pairs_per_s = len(window) * batch / window_s
    setup_s = cap.t_window - ctx.t_start
    failed = sum(not np.isfinite(r["loss"]) for r in window)

    ref_s = time.time()
    checks, notes = compare(ctx, cfg, tr, cap, losses[:cap.compared])
    notes.append(f"window {len(window)} steps in {window_s:.3f} s, "
                 f"{pairs_per_s:.2f} pairs/s, set-up {setup_s:.2f} s, "
                 f"reference {time.time() - ref_s:.1f} s")
    chips = len(ctx.devices)
    data = {"device": device, "chips": chips, "batch": batch,
            "steps": len(window), "window_s": window_s,
            "pairs_per_s": pairs_per_s,
            "data_wait_s": [r["data_wait_s"] for r in window],
            "flops_per_pair": flops.train_flops_per_pair(cfg,
                                                         tr["text_len"]),
            "embed_dim": cfg["embed_dim"], "step_footprint_bytes": footprint,
            "loss_rows_local": batch // chips, "loss_rows_global": batch}
    return H.Record(attempted=len(window), failed=failed,
                    e2e={"pairs_per_s": pairs_per_s, "setup_s": setup_s},
                    checks=checks, data=data, trace_dir=trace_dir,
                    notes=notes)


def step_footprint(step_fn, abstract) -> int:
    """Bytes on each chip while the compiled step runs: its arguments,
    temporaries and the outputs that do not reuse a donated argument
    (XLA's memory analysis of the executable the loop ran, found again
    in the compile cache)."""
    mem = step_fn.lower(*abstract).compile().memory_analysis()
    return int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def gaps(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf of |‖prog‖ − ‖ref‖| over the larger of the reference
    leaf's norm and the median leaf's norm."""
    if set(prog) != set(ref):
        raise H.BenchError(f"leaves differ: program only "
                           f"{sorted(set(prog) - set(ref))[:4]}, reference "
                           f"only {sorted(set(ref) - set(prog))[:4]}")
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for k in ref:
        if keep is not None and k not in keep:
            continue
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not g <= worst:      # NaN wins
            worst, where = g, k
    return worst, where


def compare(ctx, cfg, tr, cap, prog_losses):
    """The reference over the same steps; the three checks."""
    from bench.reference import train as RT
    mesh = None
    if len(ctx.devices) > 1:
        mesh = jax.sharding.Mesh(np.array(ctx.devices), ("rows",))
    ref = RT.run_steps(cfg, ctx.seed, cap.batches,
                       block_rows=cfg["reference"]["block_rows"], mesh=mesh)
    limits = ctx.workload["limits"]
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(prog_losses, ref["loss"]))
    grad_gap, g_leaf = gaps(cap.grad0, ref["grad0"])
    med_g = float(np.median(list(ref["grad0"].values())))
    moving = {k for k, v in ref["grad0"].items() if v >= 1e-3 * med_g}
    change_gap, c_leaf = gaps(cap.change, ref["change"], keep=moving)
    notes = [f"losses program {prog_losses} reference {ref['loss']}",
             f"worst gradient leaf {g_leaf}, worst change leaf {c_leaf}, "
             f"{len(ref['grad0']) - len(moving)} leaves left out of the "
             f"change (reference gradient under 1e-3 of the median leaf)"]
    checks = [H.Check("loss_gap", loss_gap, limits["loss_gap"]),
              H.Check("grad_gap", grad_gap, limits["grad_gap"]),
              H.Check("change_gap", change_gap, limits["change_gap"])]
    return checks, notes
