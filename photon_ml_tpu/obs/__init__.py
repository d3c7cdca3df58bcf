"""Observability layer: span tracing, labeled metrics, run manifests.

One subsystem in place of three disjoint fragments (wall-clock splits
kept in module-level dicts, the single process-global fetch counter,
the log-only event bus):

- ``obs.trace`` — thread-safe nestable span tracer
  (``trace.span("cd.update", coordinate=cid)``), exported as Chrome
  trace-event JSON (Perfetto-loadable) and structured JSONL. Disabled by
  default; zero jax, zero device syncs.
- ``obs.metrics`` — counters/gauges/histograms with labels
  (``REGISTRY``); ``utils/sync_telemetry`` is now a thin shim over the
  ``host_fetches`` counter, so per-site fetch attribution is free while
  the legacy ``host_fetch_count()`` total keeps its contract.
- ``obs.bridge`` — event-bus listener mirroring fault/recovery/
  quarantine events into counters.
- ``obs.heartbeat`` — stall-detecting progress records for long runs.
- ``obs.export`` — the live telemetry plane: a bounded non-blocking
  sink streaming span/heartbeat/run-end records as line-delimited JSON
  to a local socket (or file-tail) consumer while the run trains.
- ``obs.run`` — the drivers' ``--trace-dir`` integration: run manifest,
  live heartbeat stream, final trace/metrics flush, and the
  ``--telemetry-endpoint`` / ``--device-telemetry`` wiring.
- ``obs.compile`` — the device plane's compile/retrace attribution:
  site-labeled AOT compiles (``xla.compile`` spans with
  ``cost_analysis()`` flops/bytes) and retrace-cause records naming
  the argument whose shape/dtype/static value changed.
- ``obs.devicemem`` — HBM accounting: heartbeat-cadence
  ``hbm_bytes{device, kind}`` gauges, per-coordinate watermarks at the
  CD sweep drain, run-wide ``peak_hbm_bytes`` on the run_end record.
- ``obs.otlp`` — the standard-protocol exit: NDJSON telemetry →
  OTLP/HTTP JSON traces + metrics (``tools/otlp_bridge.py`` is the
  CLI), versioned against ``telemetry_proto``.
"""

from photon_ml_tpu.obs import compile  # noqa: F401,A004
from photon_ml_tpu.obs import devicemem, trace  # noqa: F401
from photon_ml_tpu.obs.bridge import MetricsEventListener  # noqa: F401
from photon_ml_tpu.obs.export import (  # noqa: F401
    TELEMETRY_PROTO,
    TelemetrySink,
)
from photon_ml_tpu.obs.heartbeat import Heartbeat  # noqa: F401
from photon_ml_tpu.obs.metrics import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from photon_ml_tpu.obs.run import (  # noqa: F401
    ObservedRun,
    run_manifest,
    start_observed_run,
    start_observed_run_from_flags,
)
