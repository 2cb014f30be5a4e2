"""Unified telemetry: metrics registry, trace spans, flight recorder.

Jax-free by design — serving reader processes and spawn farm workers
import from here. Three layers:

  * `repro.obs.metrics` — counters/gauges/histograms with one fixed
    log-spaced bucket grid (merge-exact), named-scope instruments, text +
    JSON exposition, picklable snapshot/merge; `metrics.current()` is the
    process (or active campaign) registry.
  * `repro.obs.trace` — `span("tune.round", device=..., task=...)`
    context managers emitting host events into a recording JAX profiler
    trace (on the device ops' clock, attrs as event stats) and
    Chrome-trace/Perfetto events into an active `Tracer`, with
    `(trace_id, span_id)` contexts small enough to ride farm pipe
    messages and serving RPC frames; `validate_events` pins span-tree
    wellformedness.
  * `repro.obs.recorder` — `FlightRecorder` ties both to per-campaign
    artifacts: append-only `events.jsonl` + `campaign.trace.json`.

On top of those, the always-on monitoring layer for long-running serving:

  * `repro.obs.timeseries` — `TimeSeriesSampler` snapshots a registry at
    a fixed interval into a bounded ring; windowed rate/percentile
    queries are reset-safe deltas between ring entries.
  * `repro.obs.slo` — declarative `SLOSpec`s evaluated with fast/slow
    multi-window burn rates and hysteresis (`SLOEvaluator`), emitting
    de-flapped alert transitions into the logger (and thus any active
    recorder).

Turned inward on the learned components (search introspection):

  * `repro.obs.calibration` — `CalibrationTracker` streams
    predicted-vs-measured residuals, rolling pairwise rank accuracy,
    top-k regret, and draft-acceptance per (device, task) into the same
    registry, as the cost model and speculative draft are used.

Plus `get_logger` (obs.logging): the structured `[name] msg key=value`
status logger that replaced the stack's ad-hoc prints
(`REPRO_LOG_LEVEL`-controlled, quiet under pytest; `REPRO_LOG_JSON=1`
switches stderr to one-JSON-object-per-line with identical fields).
"""
from repro.obs.calibration import CalibrationTracker
from repro.obs.logging import get_logger
from repro.obs.metrics import (Counter, Gauge, Histogram, LatencyWindow,
                               MetricsRegistry)
from repro.obs.recorder import FlightRecorder, summarize_trace
from repro.obs.slo import (SLOEvaluator, SLOSpec, SLOStatus,
                           default_serving_slos)
from repro.obs.timeseries import (TimeSeriesSampler, WindowDelta,
                                  reset_safe_delta)
from repro.obs.trace import (SpanContext, Tracer, current_context,
                             remote_event, span, to_chrome_trace,
                             validate_events)
from repro.obs import metrics, trace

__all__ = [
    "CalibrationTracker",
    "Counter", "Gauge", "Histogram", "LatencyWindow", "MetricsRegistry",
    "FlightRecorder", "summarize_trace", "SpanContext", "Tracer",
    "current_context", "remote_event", "span", "to_chrome_trace",
    "validate_events", "get_logger", "metrics", "trace",
    "TimeSeriesSampler", "WindowDelta", "reset_safe_delta",
    "SLOEvaluator", "SLOSpec", "SLOStatus", "default_serving_slos",
]
