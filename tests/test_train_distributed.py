"""Distributed trainer: loss decreases; checkpoint resume continues exactly;
every step streams a schema-valid runlog record with the full time
breakdown, and the trace export is Perfetto-shaped (DESIGN.md §11).
With --health armed, an injected NaN batch is skipped in-jit, flight-
recorded, and served live over /metrics and /healthz (§14)."""
import json
import os
import sys
import types
import urllib.request

import jax.numpy as jnp
import numpy as np

from repro.launch.train_distributed import train
from repro.obs import health as obs_health
from repro.obs import runlog as rl
from repro.obs import trace as obs_trace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

import check_runlog  # noqa: E402


def _args(**kw):
    base = dict(arch="llama3.2-1b", smoke=True, steps=12, batch=4, seq=32,
                lr=3e-3, seed=0, sharding="basic_ws", remat="basic",
                model_parallel=1, log_every=100, ckpt_dir=None, ckpt_every=0,
                stop_after=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_trainer_reduces_loss():
    # uniform-random tokens have an entropy floor of ln(vocab) ~ 6.24; from
    # a ~6.6 init the trainer must close most of the gap to the floor. The
    # AdaFactorW+warmup-cosine run transits a loss BUMP (up to ~7.0 around
    # steps 10-30, second-moment estimates settling) before descending, so
    # the horizon must extend past it: at 40 steps last-5 mean still sits
    # above first-5, at 80 the descent is unambiguous (~6.58 -> ~6.40).
    losses = train(_args(steps=80, lr=5e-3))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05, \
        (np.mean(losses[:5]), np.mean(losses[-5:]))
    assert all(np.isfinite(losses))


def test_checkpoint_resume_is_exact(tmp_path):
    """train 12 straight == train 6, checkpoint, resume 6 more (bitwise-close
    — the data stream is keyed by absolute step, so resume sees the same
    batches)."""
    full = train(_args(steps=12))
    d = str(tmp_path / "ck")
    # stop_after keeps the LR-schedule horizon (steps=12) identical
    train(_args(steps=12, stop_after=6, ckpt_dir=d))
    resumed = train(_args(steps=12, ckpt_dir=d))
    np.testing.assert_allclose(resumed, full[6:], rtol=1e-4)


def test_smoke_run_streams_runlog_and_trace(tmp_path, capsys):
    """A --run-dir smoke run emits one schema-valid step record per step
    (full data-wait/device-step/ckpt-stall breakdown), checkpoint events,
    and a Chrome-trace JSON whose spans carry the required keys."""
    rd = str(tmp_path / "run")
    train(_args(steps=6, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                run_dir=rd, quiet=True, log_every=2))
    # quiet mode: telemetry streams, stdout stays silent
    assert "step " not in capsys.readouterr().out

    path = os.path.join(rd, "runlog.jsonl")
    assert check_runlog.check_file(path) == []       # the schema gate
    records = rl.read_runlog(path)
    steps = [r for r in records if r["kind"] == "step"]
    assert [r["step"] for r in steps] == list(range(6))
    for r in steps:
        for key in rl.STEP_BREAKDOWN_KEYS + ("step_s", "loss",
                                             "examples_per_sec",
                                             "grad_norm"):
            assert isinstance(r[key], (int, float)), (key, r)
        assert r["step_s"] >= r["data_wait_s"] + r["device_step_s"]
    saves = [r for r in records if r["kind"] == "checkpoint"]
    assert {r["event"] for r in saves} >= {"save", "final_save"}
    # the final registry snapshot rode along
    final = [r for r in records if r["kind"] == "metrics"]
    assert final and final[-1]["counters"]["ckpt/saves"] >= 2

    doc = json.load(open(os.path.join(rd, "trace.json")))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {"train/data_wait", "train/dispatch", "train/wait",
            "train/ckpt_stall", "train/log"} <= {e["name"] for e in spans}
    assert "train/device_step" not in {e["name"] for e in spans}
    assert all("step" in e["args"] for e in spans)
    for ev in doc["traceEvents"]:
        for key in obs_trace.REQUIRED_EVENT_KEYS:
            assert key in ev, (key, ev)


def test_profiled_run_has_loop_and_loader_spans(tmp_path):
    """Under a ``jax.profiler`` capture, with no run directory (no
    Tracer), a contrastive smoke run shows one ``repro/train/dispatch``
    per step on one thread, and each step's ``repro/data/render``,
    ``repro/data/put`` and ``repro/data/queue_wait`` (the same ``step``,
    each step once) on another: the prefetch thread's."""
    import glob

    import jax
    from jax.profiler import ProfileData
    args = types.SimpleNamespace(
        arch="basic-s", objective="auto", smoke=True, steps=4, batch=8,
        seq=16, lr=3e-4, seed=0, sharding="basic_ws", remat="basic",
        model_parallel=1, log_every=100, ckpt_dir=None, ckpt_every=0,
        stop_after=None, num_micro=2, loss="local", quiet=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        train(args)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = {}              # name -> [(thread row, step)]
    row = 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro/"):
                    spans.setdefault(e.name, []).append(
                        (row, dict(e.stats)["step"]))
            row += 1
    dispatch = spans["repro/train/dispatch"]
    assert sorted(s for _, s in dispatch) == list(range(4))
    main = {t for t, _ in dispatch}
    assert len(main) == 1
    for name in ("repro/train/data_wait", "repro/train/wait",
                 "repro/train/log"):
        assert sorted(spans[name]) == sorted(dispatch), name
    render, put = spans["repro/data/render"], spans["repro/data/put"]
    assert sorted(render) == sorted(put)     # each render, then its put
    assert sorted(spans["repro/data/queue_wait"]) == sorted(render)
    assert len({s for _, s in render}) == len(render)   # each step once
    rows = {t for t, _ in render}
    assert len(rows) == 1 and not rows & main
    assert set(range(4)) <= {s for _, s in render}
    blocks = spans["repro/data/host_block"]
    assert sorted(blocks) == sorted(render)  # one data shard: one block


def test_resume_appends_to_runlog_with_marker(tmp_path):
    """A --resume relaunch APPENDS to the same runlog — one run_start,
    one resume marker, monotone step records across the boundary."""
    d = str(tmp_path / "ck")
    train(_args(steps=12, stop_after=6, ckpt_dir=d, quiet=True))
    train(_args(steps=12, ckpt_dir=d, quiet=True))   # run_dir defaults here
    path = os.path.join(d, "runlog.jsonl")
    assert check_runlog.check_file(path) == []
    records = rl.read_runlog(path)
    kinds = [r["kind"] for r in records]
    assert kinds.count("run_start") == 1 and kinds.count("resume") == 1
    assert next(r for r in records
                if r["kind"] == "resume")["resumed_from"] == 6
    assert [r["step"] for r in records
            if r["kind"] == "step"] == list(range(12))


def test_health_run_survives_injected_nan(tmp_path):
    """The §14 acceptance path end to end: a --health --metrics-port run
    with a NaN batch injected at step 2 must (a) skip the poisoned update
    in-jit so every later loss is finite, (b) write a schema-valid
    ``anomaly`` runlog record and mark the step ``skipped``, (c) dump the
    flight recorder, and (d) serve live /metrics and /healthz mid-run —
    staying healthy, because one contained incident is not an outage."""
    rd = str(tmp_path / "run")
    args = types.SimpleNamespace(
        arch="basic-s", objective="auto", smoke=True, steps=8, batch=8,
        seq=16, lr=3e-4, seed=0, sharding="basic_ws", remat="basic",
        model_parallel=1, log_every=100, ckpt_dir=None, ckpt_every=0,
        stop_after=None, num_micro=2, loss="local", quiet=True,
        run_dir=rd, health=True, metrics_port=0)
    probes = {}

    def hook(step, batch):
        if step == 2:                 # poison the whole image batch
            imgs = dict(batch["images"])
            imgs["image"] = batch["images"]["image"] * jnp.nan
            batch = dict(batch, images=imgs)
        if step == 4:                 # scrape the live endpoint mid-run
            port = int(open(os.path.join(rd, "metrics_port")).read())
            for ep in ("metrics", "healthz"):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/{ep}", timeout=5) as r:
                    probes[ep] = (r.status, r.read().decode())
        return batch

    obs_health.set_step_fault_hook(hook)
    try:
        losses = train(args)
    finally:
        obs_health.set_step_fault_hook(None)

    # (a) the poisoned step reports NaN but never lands: params stay
    # finite, so every subsequent loss is too
    assert not np.isfinite(losses[2])
    assert all(np.isfinite(v) for i, v in enumerate(losses) if i != 2)

    # (b) schema-valid runlog with the anomaly + skipped step record
    path = os.path.join(rd, "runlog.jsonl")
    assert check_runlog.check_file(path) == []
    records = rl.read_runlog(path)
    anoms = [r for r in records if r["kind"] == "anomaly"]
    assert anoms and all(r["detector"] == "nonfinite" and r["step"] == 2
                         and r["severity"] == "critical" for r in anoms)
    steps = {r["step"]: r for r in records if r["kind"] == "step"}
    assert steps[2].get("skipped") == 1
    assert all("skipped" not in steps[i] for i in steps if i != 2)
    event = next(r for r in records if r["kind"] == "event"
                 and r["event"] == "trace_export")
    assert isinstance(event["dropped"], int)
    final = [r for r in records if r["kind"] == "metrics"][-1]
    assert final["counters"]["health/steps_skipped"] == 1

    # (c) the flight recorder dumped the incident
    dumps = os.listdir(os.path.join(rd, "flight"))
    assert dumps == ["step000002_nonfinite"]
    anomaly = json.load(open(os.path.join(
        rd, "flight", dumps[0], "anomaly.json")))
    assert anomaly["detector"] == "nonfinite" and anomaly["step"] == 2

    # (d) the mid-run scrape saw Prometheus text + a healthy /healthz
    code, body = probes["metrics"]
    assert code == 200 and "# TYPE health_checks counter" in body
    assert 'health_anomalies{detector="nonfinite",severity="critical"} 2' \
        in body
    code, body = probes["healthz"]
    assert code == 200 and json.loads(body)["healthy"] is True
