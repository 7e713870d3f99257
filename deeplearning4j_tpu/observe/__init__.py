"""Device-resident telemetry: in-step metrics, tracing, Prometheus export.

The reference's observability stack (BaseStatsListener + UI, SURVEY
§2.12/§5.5) polls the JVM from the host; porting that shape verbatim
makes every score/statistic its own device→host sync, stalling the TPU
pipeline. This package inverts it:

- ``telemetry``: a metric spec (loss, global grad-norm, per-layer
  update:param ratio, non-finite counts) compiled INTO the jitted train
  step, accumulated in a fixed-size on-device ring buffer and flushed to
  host every N steps in ONE device fetch — steady-state training
  performs zero extra syncs.
- ``tracer``: host-side span tracer (ETL, host→device transfer,
  dispatch, the loop's own waits for the device, flush, eval,
  checkpoint) exporting Chrome/Perfetto trace JSON; the export names
  the ``perf_counter`` reading its ``ts`` count from, which lays it
  beside a jax.profiler capture.
- ``recompile``: watchdog recording each new (shape, dtype) signature a
  compiled step sees — silent retrace storms become a counter.
- ``registry``: process-wide metrics registry rendered as Prometheus
  text exposition at ``/metrics`` on the UI server.
- ``flight_recorder``: always-on black-box crash forensics — on a
  terminal event (non-finite at flush, OOM, uncaught exception in fit)
  the last-N telemetry rows, in-step histograms, memory reports, span
  and recompile tails are written as one post-mortem dump directory.
- ``health``: degradation verdict over the registry's series backing
  the UI server's ``/healthz`` (503 on nonfinite / recompile storm /
  replica divergence).
"""

from deeplearning4j_tpu.observe.flight_recorder import (
    FlightRecorder,
    crash_dumps_enabled,
    default_flight_recorder,
)
from deeplearning4j_tpu.observe.health import health_status
from deeplearning4j_tpu.observe.latency import LatencyRing
from deeplearning4j_tpu.observe.registry import (
    MetricsRegistry,
    default_registry,
)
from deeplearning4j_tpu.observe.recompile import RecompileWatchdog
from deeplearning4j_tpu.observe.telemetry import (
    HistRing,
    ReplicaRing,
    TelemetryBuffer,
    TelemetryCollector,
    TelemetrySpec,
)
from deeplearning4j_tpu.observe.tracer import NULL_TRACER, SpanTracer

__all__ = [
    "MetricsRegistry",
    "default_registry",
    "FlightRecorder",
    "default_flight_recorder",
    "crash_dumps_enabled",
    "health_status",
    "LatencyRing",
    "RecompileWatchdog",
    "HistRing",
    "ReplicaRing",
    "TelemetryBuffer",
    "TelemetryCollector",
    "TelemetrySpec",
    "SpanTracer",
    "NULL_TRACER",
]
