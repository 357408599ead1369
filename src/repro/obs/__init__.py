"""Unified telemetry + active monitoring subsystem (DESIGN.md §11, §14).

One stats mechanism repo-wide. The passive layers (§11):

  metrics  — process-wide registry of counters / gauges / fixed-bucket
             histograms (p50/p90/p99 summaries), thread-safe, labeled
             children, ``snapshot()``/``to_json()``.
  trace    — ``span(...)``: ``jax.profiler`` annotations named
             ``repro/<name>`` (the program's spans on the profiler's
             clock, beside the device's ops), kept also in a ring buffer
             exportable as Chrome ``trace_event`` JSON (Perfetto /
             chrome://tracing).
  runlog   — one schema-versioned JSONL record per train step (loss,
             grad-norm, examples/sec, data-wait / device-step /
             ckpt-stall breakdown, checkpoint + retention + anomaly
             events), plus the ``python -m repro.obs.report`` trajectory
             summarizer.

And the active tier built on them (§14):

  windows  — fixed-memory sliding-window aggregators: exact windowed
             percentiles, trailing event rates, robust MAD z-scores.
  health   — ``HealthMonitor`` + pluggable anomaly detectors (non-finite
             loss/grad, spikes, plateau, input stall, host straggler),
             flight recorder, serving ``SLOTracker``.
  export   — Prometheus text exposition of any registry snapshot and the
             stdlib-HTTP ``/metrics`` / ``/healthz`` / ``/snapshot.json``
             endpoint (localhost-only by default).

Everything is off-hot-path cheap: instruments mutate a couple of Python
ints under a lock, a span with no profiler attached costs about a
microsecond, and snapshotting and JSONL writes happen outside the jitted
step.
"""
from repro.obs.export import MetricsServer, render_prometheus
from repro.obs.health import (Anomaly, Detector, FlightRecorder,
                              HealthMonitor, NonFiniteDetector,
                              PlateauDetector, SLOTracker, SpikeDetector,
                              StallDetector, StepSample,
                              StragglerDetector, default_detectors,
                              set_step_fault_hook)
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               exponential_buckets, get_registry)
from repro.obs.runlog import (RunLogger, RunlogError, SCHEMA_VERSION,
                              STEP_BREAKDOWN_KEYS, read_runlog,
                              validate_record)
from repro.obs.trace import Tracer, span
from repro.obs.windows import SlidingWindow, WindowedRate, percentile

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "exponential_buckets",
    "get_registry", "RunLogger", "RunlogError", "SCHEMA_VERSION",
    "STEP_BREAKDOWN_KEYS", "read_runlog", "validate_record", "Tracer",
    "span",
    "SlidingWindow", "WindowedRate", "percentile",
    "Anomaly", "Detector", "FlightRecorder", "HealthMonitor",
    "NonFiniteDetector", "PlateauDetector", "SLOTracker", "SpikeDetector",
    "StallDetector", "StepSample", "StragglerDetector",
    "default_detectors", "set_step_fault_hook",
    "MetricsServer", "render_prometheus",
]
