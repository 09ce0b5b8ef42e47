"""paddle_tpu.observability — unified metrics + tracing layer.

The telemetry the serving north star ("heavy traffic ... as fast as the
hardware allows") requires as a *layer*, not per-module counters:

  * :mod:`.metrics` — a thread-safe registry of counters / gauges /
    fixed-bucket histograms with percentile readout, exported as a JSON
    snapshot (bench artifacts, tests) or Prometheus text exposition (a
    serving host's scrape endpoint).  ServingEngine (TTFT / TPOT /
    queue-wait / occupancy), BlockManager (pool occupancy, prefix hits,
    evictions, COW) and the ops dispatchers (kernel-path selections)
    all report here — ``observability.snapshot()`` after a serving
    trace is the whole story in one dict;
  * :mod:`.tracing` — a host-side span tracer with Chrome-trace /
    Perfetto JSON export; every span is also a
    ``jax.profiler.TraceAnnotation``, so the same labelled regions
    appear in an XLA device trace (``profiler.RecordEvent`` is a thin
    wrapper over it);
  * :mod:`.clock` — the one ``perf_counter`` origin spans and request
    events are stamped against, and the conversions to it;
  * :mod:`.request_log` — per-request lifecycle timelines (submitted →
    admitted → prefill → first token → retired) keyed by a uid minted
    at ``submit()`` and threaded router → replica → engine → slot, with
    Perfetto export (one named track per request) and
    ``slo_report()`` goodput-under-deadline readout;
  * :mod:`.watchdog` — ``track_retraces``: per-call-site jit trace
    counting with a budget, generalising the engine's
    ``step_traces == 1`` contract into a reusable, CI-armed guarantee;
  * :mod:`.federation` — the fleet tier: merges worker registry
    snapshots into one federated view (``worker=`` labels, pooled
    percentiles from merged buckets, post-merge cardinality cap),
    recovers per-worker clock offsets from RPC timestamps (NTP-style
    min-RTT estimator) and exports ONE merged Perfetto timeline for
    plane + workers + requests.

Conventions: metric names are dotted lowercase (``serving.ttft_ms``);
millisecond histograms carry the ``_ms`` suffix; per-instance series are
distinguished by labels (``engine="0"``, ``pool="1"``), never by name.
"""

from . import clock
from .costmodel import (CostModel, HardwareProfile, PROFILES,
                        TickAttribution, kv_bytes_per_token, perf_signature,
                        resolve_profile)
from .costmodel import reset as _reset_costmodel
from .federation import (ClockOffsetEstimator, FederatedRegistry,
                         TransportStitch, fleet_obs_signature,
                         merge_perfetto, percentile_from_buckets,
                         scope_snapshot)
from .http_exposition import ExpositionServer, maybe_serve
from . import metrics as _metrics_mod
from .metrics import (Counter, Gauge, Histogram, LATENCY_BUCKETS_MS,
                      MetricsRegistry, SNAPSHOT_SCHEMA_VERSION,
                      prometheus_text, snapshot)
from .metrics import reset as _reset_metrics
from .regression import EwmaDetector, HISTORY_TOLERANCES, check_history
from .regression import reset as _reset_regression
from .request_log import RequestLog, get_request_log
from .tracing import (SpanTracer, export_chrome_trace, get_tracer, instant,
                      span)
from .watchdog import (RetraceError, RetraceWarning, TrackedFunction,
                       track_retraces)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "LATENCY_BUCKETS_MS", "SNAPSHOT_SCHEMA_VERSION", "default_registry",
    "snapshot", "prometheus_text", "reset",
    "SpanTracer", "get_tracer", "span", "instant", "export_chrome_trace",
    "clock",
    "RequestLog", "get_request_log",
    "RetraceError", "RetraceWarning", "TrackedFunction", "track_retraces",
    "HardwareProfile", "PROFILES", "resolve_profile", "CostModel",
    "TickAttribution", "kv_bytes_per_token", "perf_signature",
    "EwmaDetector", "HISTORY_TOLERANCES", "check_history",
    "ExpositionServer", "maybe_serve",
    "ClockOffsetEstimator", "FederatedRegistry", "TransportStitch",
    "scope_snapshot", "percentile_from_buckets", "merge_perfetto",
    "fleet_obs_signature",
]


def default_registry() -> MetricsRegistry:
    """The process-wide registry every subsystem reports into.

    Delegates through the :mod:`.metrics` module attribute (rather than
    binding the function at import) so a test that monkeypatches
    ``metrics.default_registry`` — e.g. the BlockManager model checker
    handing thousands of short-lived pools throwaway registries —
    redirects every ``observability.default_registry()`` call site too."""
    return _metrics_mod.default_registry()


def reset() -> None:
    """Clear the default registry AND the default tracer's buffer AND
    the default request log AND every live cost-model/anomaly-detector
    state (test isolation; live metric handles keep working but stop
    being exported until re-registered)."""
    _reset_metrics()
    get_tracer().clear()
    get_request_log().clear()
    _reset_costmodel()
    _reset_regression()
