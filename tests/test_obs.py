"""obs/ telemetry subsystem: histogram math vs numpy, thread safety,
trace-event schema, runlog round-trip + schema gating, stats back-compat,
and the committed runlog sample artifact."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.obs import metrics, report, runlog, trace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

import check_runlog  # noqa: E402


# -- metrics ---------------------------------------------------------------
def test_histogram_percentiles_vs_numpy_oracle():
    """Interpolated percentile error is bounded by one bucket width."""
    buckets = metrics.exponential_buckets(1e-3, 2.0, 16)
    h = metrics.Histogram("lat", buckets=buckets)
    rng = np.random.default_rng(0)
    vals = rng.uniform(1e-3, 1.0, 4000)
    for v in vals:
        h.observe(v)
    bounds = (0.0,) + buckets + (float("inf"),)
    for q in (1, 25, 50, 75, 90, 99):
        oracle = float(np.percentile(vals, q))
        est = h.percentile(q)
        # the bucket containing the oracle bounds the allowed error
        i = np.searchsorted(buckets, oracle)
        width = bounds[i + 1] - bounds[i]
        assert abs(est - oracle) <= width, (q, est, oracle, width)
    assert h.count == len(vals)
    np.testing.assert_allclose(h.sum, vals.sum(), rtol=1e-9)


def test_histogram_summary_and_edges():
    h = metrics.Histogram("h", buckets=(1.0, 2.0, 4.0))
    assert np.isnan(h.percentile(50))
    assert h.summary()["count"] == 0 and h.summary()["p50"] is None
    for v in (0.5, 1.5, 3.0, 100.0):   # incl. overflow bucket
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 100.0
    assert s["p50"] <= s["p90"] <= s["p99"] <= 100.0
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        metrics.Histogram("bad", buckets=(2.0, 1.0))


def test_concurrent_counter_increments():
    """8 threads x 5000 incs race one counter; nothing is lost."""
    reg = metrics.Registry()
    c = reg.counter("hits")
    h = reg.histogram("obs", buckets=(0.5, 1.0))

    def work():
        for _ in range(5000):
            c.inc()
            h.observe(0.25)
    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8 * 5000
    assert h.count == 8 * 5000


def test_registry_labeled_children_and_snapshot():
    reg = metrics.Registry()
    a = reg.counter("req", tower="image")
    b = reg.counter("req", tower="text")
    assert a is not b
    assert reg.counter("req", tower="image") is a   # same child back
    a.inc(3)
    b.inc()
    reg.gauge("depth").set(7)
    reg.histogram("lat").observe(0.01)
    snap = reg.snapshot()
    assert snap["counters"]["req{tower=image}"] == 3
    assert snap["counters"]["req{tower=text}"] == 1
    assert snap["gauges"]["depth"] == 7.0
    assert snap["histograms"]["lat"]["count"] == 1
    json.loads(reg.to_json())                        # serializable
    with pytest.raises(TypeError):
        reg.gauge("req", tower="image")              # kind mismatch
    with pytest.raises(ValueError):
        a.inc(-1)                                    # counters only go up


# -- trace -----------------------------------------------------------------
def test_trace_event_schema_and_ring_buffer():
    tr = trace.Tracer(capacity=3)
    for i in range(5):
        with trace.span(tr, "work", step=i, host=i % 2):
            time.sleep(0.001)
    tr.instant("marker", step=5)
    events = tr.events()
    assert len(events) == 3 and tr.dropped == 3      # ring: newest 3 win
    doc = tr.to_chrome_trace()
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        for key in trace.REQUIRED_EVENT_KEYS:
            assert key in ev, (key, ev)
        assert ev["pid"] == os.getpid()
    # span durations are real wall time, on the wall clock (µs since epoch)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans and all(e["dur"] >= 900 for e in spans)   # ≥0.9ms in µs
    assert all(abs(e["ts"] / 1e6 - time.time()) < 60 for e in spans)
    # the host rides as an argument of the span, beside its step
    assert [(e["args"]["step"], e["args"]["host"]) for e in spans] == \
        [(3, 1), (4, 0)]


def test_trace_export_and_none_tracer(tmp_path):
    tr = trace.Tracer()
    for h in range(2):
        with trace.span(tr, "data/host_block", step=7, host=h):
            pass
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert not [e for e in doc["traceEvents"] if e["ph"] == "M"]  # no lanes
    assert {e["args"]["host"] for e in doc["traceEvents"]
            if e["name"] == "data/host_block"} == {0, 1}
    with trace.span(None, "noop") as got:            # disabled path
        assert got is None


def test_spans_reach_a_profiler_capture(tmp_path):
    """A span kept in a Tracer and one with no Tracer both appear in a
    ``jax.profiler`` capture as ``repro/<name>``, with their ``step``."""
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = trace.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span(tr, "train/dispatch", step=3):
            time.sleep(0.002)
        with trace.span(None, "data/render", step=4):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    got = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(trace.PREFIX):
                    got[e.name] = (dict(e.stats).get("step"), e.duration_ns)
    assert set(got) == {"repro/train/dispatch", "repro/data/render"}
    assert got["repro/train/dispatch"][0] == 3
    assert got["repro/data/render"][0] == 4
    assert all(d >= 1.5e6 for _, d in got.values())
    assert [e["name"] for e in tr.events()] == ["train/dispatch"]


def test_trace_thread_lanes():
    tr = trace.Tracer()
    def work():
        with trace.span(tr, "bg"):
            pass
    t = threading.Thread(target=work)
    t.start()
    t.join()
    with trace.span(tr, "fg"):
        pass
    tids = {e["name"]: e["tid"] for e in tr.events()}
    assert tids["bg"] != tids["fg"]


# -- runlog ----------------------------------------------------------------
def _write_steps(path, n, **meta):
    with runlog.RunLogger(str(path), meta=meta) as log:
        for i in range(n):
            log.log_step(i, loss=5.0 - i * 0.1, data_wait_s=0.001,
                         device_step_s=0.01, ckpt_stall_s=0.0,
                         step_s=0.011, examples_per_sec=700.0,
                         grad_norm=2.0)


def test_runlog_roundtrip_and_resume_marker(tmp_path):
    p = tmp_path / "runlog.jsonl"
    _write_steps(p, 3, arch="basic-s")
    # resumed segment appends to the SAME file: marker, no second header
    with runlog.RunLogger(str(p), resumed_from=3) as log:
        log.log_step(3, loss=4.6, data_wait_s=0.001, device_step_s=0.01,
                     ckpt_stall_s=0.002, step_s=0.013,
                     examples_per_sec=600.0)
        log.log("checkpoint", step=4, event="final_save")
    recs = runlog.read_runlog(str(p))
    kinds = [r["kind"] for r in recs]
    assert kinds.count("run_start") == 1 and kinds[0] == "run_start"
    assert kinds.count("resume") == 1
    resume = next(r for r in recs if r["kind"] == "resume")
    assert resume["resumed_from"] == 3
    steps = [r for r in recs if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [0, 1, 2, 3]
    for r in steps:
        for key in runlog.STEP_BREAKDOWN_KEYS:
            assert isinstance(r[key], float)


def test_runlog_schema_version_rejection(tmp_path):
    p = tmp_path / "runlog.jsonl"
    _write_steps(p, 2)
    with open(p, "a") as f:
        f.write(json.dumps({"schema": 99, "kind": "step", "t": 0.0}) + "\n")
        f.write("")
    with pytest.raises(runlog.RunlogError, match="schema"):
        runlog.read_runlog(str(p))
    assert len(runlog.read_runlog(str(p), strict=False)) == 3  # skipped


def test_runlog_torn_final_line_tolerated(tmp_path):
    p = tmp_path / "runlog.jsonl"
    _write_steps(p, 2)
    with open(p, "a") as f:
        f.write('{"schema": 1, "kind": "st')      # crash mid-write
    recs = runlog.read_runlog(str(p))             # strict, still fine
    assert sum(r["kind"] == "step" for r in recs) == 2


def test_runlog_refuses_invalid_writes(tmp_path):
    with runlog.RunLogger(str(tmp_path / "r.jsonl")) as log:
        with pytest.raises(runlog.RunlogError):
            log.log("no_such_kind")
        with pytest.raises(runlog.RunlogError):
            log.log("resume")                     # missing resumed_from


def test_report_cli_and_summary(tmp_path, capsys):
    p = tmp_path / "runlog.jsonl"
    _write_steps(p, 10, arch="basic-s")
    assert report.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "10 step records" in out and "p99" in out
    summary = report.summarize(runlog.read_runlog(str(p)))
    assert summary["loss"]["first"] == pytest.approx(5.0)
    assert summary["phases"]["device_step_s"]["p50"] == pytest.approx(0.01)
    # exact percentile helper matches numpy's linear convention
    vals = [1.0, 2.0, 10.0, 11.0]
    assert report._percentile(vals, 50) == pytest.approx(
        float(np.percentile(vals, 50)))
    # bad file -> non-zero
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"schema": 9, "kind": "step", "t": 0}\n' * 2)
    assert report.main([str(bad)]) == 1


def test_committed_runlog_sample_validates():
    """The committed artifacts/runlog_sample.jsonl (a real smoke-run
    output) stays valid under the schema gate — drift in the runlog
    format shows up here, not in a consumer's dashboard."""
    sample = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                          "runlog_sample.jsonl")
    assert check_runlog.check_file(sample) == []
    recs = runlog.read_runlog(sample)
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps, "sample must contain step records"
    for r in steps:
        for key in runlog.STEP_BREAKDOWN_KEYS:
            assert key in r
    assert check_runlog.main([sample]) == 0


# -- back-compat: one stats mechanism repo-wide ----------------------------
def test_batcher_stats_backcompat_registry_backed():
    from repro.serving.embed.batcher import MicroBatcher
    mb = MicroBatcher({"t": lambda b: np.asarray(b["x"], np.float32)},
                      buckets=(2, 4), autostart=False)
    mb.submit_many("t", {"x": np.ones((3, 2), np.float32)})
    mb.flush_now()
    # legacy dict shape intact...
    assert mb.stats["requests"] == 3
    assert mb.stats["manual_flushes"] == 1
    assert mb.stats["encoded_examples"] == 3
    assert mb.stats["padded_examples"] == 1        # 3 -> bucket 4
    # ...and the SAME numbers come from the registry
    snap = mb.metrics.snapshot()
    assert snap["counters"]["serve/requests"] == 3
    assert snap["histograms"]["serve/batch_occupancy"]["count"] == 1
    assert snap["histograms"]["serve/request_latency_s"]["count"] == 1
    assert snap["gauges"]["serve/queue_depth"] == 0.0
    mb.stop()


def test_manager_stats_backcompat_registry_backed(tmp_path):
    from repro.checkpoint.manager import AsyncCheckpointManager
    with AsyncCheckpointManager(str(tmp_path), sync=True) as m:
        m.save(1, {"w": np.ones(4, np.float32)})
        assert m.stats["saves"] == 1 and m.stats["sync_saves"] == 1
        snap = m.metrics.snapshot()
        assert snap["counters"]["ckpt/saves"] == 1
        assert snap["histograms"]["ckpt/write_latency_s"]["count"] == 1
        assert snap["gauges"]["ckpt/last_stall_s"] > 0
        m.degrade_to_sync()                        # already sync: no-op
        assert m.stats["degraded"] == 0
        m.sync = False
        m.degrade_to_sync()
        assert m.sync and m.stats["degraded"] == 1


def test_shared_registry_across_subsystems(tmp_path):
    """One run registry can host batcher + manager series side by side."""
    from repro.checkpoint.manager import AsyncCheckpointManager
    from repro.serving.embed.batcher import MicroBatcher
    reg = metrics.Registry()
    mb = MicroBatcher({"t": lambda b: np.asarray(b["x"], np.float32)},
                      buckets=(2,), autostart=False, registry=reg)
    mb.submit_many("t", {"x": np.ones((2, 2), np.float32)})
    mb.flush_now()
    with AsyncCheckpointManager(str(tmp_path), sync=True,
                                registry=reg) as m:
        m.save(1, {"w": np.ones(2, np.float32)})
    counters = reg.snapshot()["counters"]
    assert counters["serve/requests"] == 2 and counters["ckpt/saves"] == 1
    mb.stop()


def test_report_serving_snapshot_rendering(tmp_path, capsys):
    """--serving renders serve/retrieval_* series: per-stage latency,
    prune ratio, shard skew, and serve/ counters; unwraps a full
    ZeroShotService.stats() dict via its "metrics" key."""
    reg = metrics.Registry()
    for stage, v in (("coarse", 0.002), ("rerank", 0.05), ("total", 0.06)):
        reg.histogram("serve/retrieval_latency_s", stage=stage).observe(v)
    pr = reg.histogram("serve/retrieval_prune_ratio",
                       buckets=metrics.RATIO_BUCKETS)
    pr.observe(0.06)
    pr.observe(0.10)
    reg.histogram("serve/retrieval_shard_share",
                  buckets=metrics.RATIO_BUCKETS).observe(0.25)
    reg.counter("serve/gallery_uploads").inc()

    stats = {"retrieval_mode": "twostage", "metrics": reg.snapshot()}
    text = report.format_serving(stats)
    assert "stage=rerank" in text
    assert "prune ratio" in text and "mean 0.080" in text
    assert "shard skew" in text and "0.250" in text
    assert "serve/gallery_uploads=1" in text

    p = tmp_path / "stats.json"
    p.write_text(json.dumps(stats))
    assert report.main(["--serving", str(p)]) == 0
    assert "prune ratio" in capsys.readouterr().out

    assert report.format_serving({"histograms": {}, "counters": {}}) == (
        "no serve/retrieval_* series in snapshot")
