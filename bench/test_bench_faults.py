"""A whole run on the CPU at the smoke size, past the look for a chip:
sound, it is correct; with the timed path broken underneath, ``correct``
comes out false; and the control (the reference in the program's place,
one step of precision lower) fails the cell's limits."""
import os

import jax
import jax.numpy as jnp
import pytest

from bench import control as CTL
from bench import harness as H
from bench import run as R

TESTDATA = os.path.join(H.BENCH, "testdata")
SEED = 2 ** 31 + 11
MANIFEST = {
    "end_to_end": [
        {"name": "pairs_per_s", "unit": "pairs/s", "workloads": ["tiny.train"]},
        {"name": "serve_p95_ms", "unit": "ms", "workloads": ["tiny.classify"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}


def run_tiny(cell, seconds=1.0):
    args = R.parse(["--workload", cell, "--seed", str(SEED), "--seconds",
                    str(seconds), "--trace", "0"])
    return R.run(args, require_tpu=False, workload_dirs=[TESTDATA],
                 config_dirs=[TESTDATA], manifest=MANIFEST)


def wrap_step(monkeypatch, wrapper):
    """Replace the trainer's step factory by one whose step is
    ``wrapper(step)``."""
    from repro.launch import steps
    orig = steps.make_contrastive_step

    def make(*a, **k):
        step, opt = orig(*a, **k)
        return wrapper(step), opt
    monkeypatch.setattr(steps, "make_contrastive_step", make)


def test_sound_train_run_is_correct():
    line = run_tiny("tiny.train")
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert list(line)[-1] == "checks"


def test_state_left_unchanged_is_caught(monkeypatch):
    def wrapper(step):
        def s(params, opt_state, batch):
            _, _, loss, metrics = step(params, opt_state, batch)
            return params, opt_state, loss, metrics
        return s
    wrap_step(monkeypatch, wrapper)
    line = run_tiny("tiny.train")
    assert not line["correct"]
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch):
    def wrapper(step):
        def s(params, opt_state, batch):
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
            return step(params, opt_state, half)
        return s
    wrap_step(monkeypatch, wrapper)
    line = run_tiny("tiny.train")
    assert not line["correct"]
    assert line["checks"]["loss_gap"]["value"] > \
        line["checks"]["loss_gap"]["limit"]


def test_exchange_between_chips_left_out_is_caught(monkeypatch):
    from repro.core import distributed_loss as dist
    from repro.core.contrastive import contrastive_loss

    def local_only(mesh, method="chunked", **kw):
        def loss_fn(x, y, tau):
            r = 4      # each of four chips' rows alone
            xs = x.reshape(r, -1, x.shape[-1])
            ys = y.reshape(r, -1, y.shape[-1])
            losses = jax.vmap(lambda a, b: contrastive_loss(a, b, tau)[0])(
                xs, ys)
            zero = jnp.zeros((), jnp.float32)
            return jnp.mean(losses), {"row_loss": zero, "col_loss": zero,
                                      "i2t_top1": zero}
        return loss_fn
    monkeypatch.setattr(dist, "make_global_loss_fn", local_only)
    line = run_tiny("tiny.train")
    assert not line["correct"]


def test_sound_classify_run_is_correct():
    line = run_tiny("tiny.classify", seconds=2.0)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p95_ms", "setup_s"}


def test_altered_answer_is_caught(monkeypatch):
    from repro.kernels.similarity_topk import ops
    orig = ops.similarity_topk

    def altered(q, c, k, **kw):
        v, i = orig(q, c, k, **kw)
        return v, i.at[:, 0].set((i[:, 0] + 1) % c.shape[0])
    monkeypatch.setattr(ops, "similarity_topk", altered)
    line = run_tiny("tiny.classify", seconds=2.0)
    assert not line["correct"]


def test_controls_fail_the_limits():
    devs = jax.devices()[:1]
    tr = H.load_workload("tiny.train", dirs=[TESTDATA])
    cfg = H.load_config("tiny", dirs=[TESTDATA])
    got = {r["run"]: r for r in CTL.train_readings(cfg, tr, SEED, devs)}
    lim = tr["limits"]
    for run in ("fp8", "half_batch"):
        assert any(got[run][k] > lim[k] for k in lim), got[run]
    cl = H.load_workload("tiny.classify", dirs=[TESTDATA])
    (low,) = CTL.classify_readings(cfg, cl, SEED, devs, 2.0)
    assert any(low[k] > v for k, v in cl["limits"].items()), low
