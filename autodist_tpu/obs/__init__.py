"""Unified observability (L1.5): spans, step profiling, metrics export.

The production triad's third leg (after ``serve/`` and ``ft/``): the layer
that tells you *where the time and bytes went* across a multi-host fleet.
Supersedes the earlier islands — ``utils/tracing.py``'s StepTimer (now a
compat shim over :mod:`~autodist_tpu.obs.profiler`), the ad-hoc prometheus
text in serve, and the unexported roofline/metrics plumbing:

- :mod:`~autodist_tpu.obs.spans` — cross-process span tracer: context
  manager/decorator spans into a thread-safe ring, one trace id propagated
  through the launcher's ``AUTODIST_*`` env so launcher → coordinator →
  worker spans stitch into a single chrome-trace/Perfetto JSON.
- :mod:`~autodist_tpu.obs.profiler` — :class:`StepProfiler`: dispatch-gap
  vs device-compute split per run window (one end barrier, bench.py
  discipline), live MFU from the compiled program's own cost analysis,
  roofline position, compile counts, HBM high-water.
- :mod:`~autodist_tpu.obs.exporter` — ONE OpenMetrics renderer for every
  export surface (serve ``GET /metrics`` and the headless
  :class:`FileExporter` are byte-identical), plus the matching parser.
- :mod:`~autodist_tpu.obs.aggregate` — per-host step-time quantiles over
  the ft coordination transports; straggler scores feed the
  HealthMonitor's suspect escalation.
- :mod:`~autodist_tpu.obs.recorder` — the always-on **flight recorder**:
  one compact JSONL record per train/serve step plus sparse events, in a
  crash-safe fsync'd segment ring under ``<ft base>/flight`` — the black
  box every death leaves behind.
- :mod:`~autodist_tpu.obs.sentry` — online anomaly sentry over that
  stream: NaN/Inf, loss spikes, step-time regressions, HBM creep,
  stragglers — stable ``SNT###`` verdict codes, escalated into the ft
  HealthMonitor.
- :mod:`~autodist_tpu.obs.doctor` — the postmortem: stitch flight
  records, heartbeats, snapshot manifests, hang bundles and span parts
  into one timeline and classify the death (``DOC###`` verdicts).
- :mod:`~autodist_tpu.obs.attrib` — measured-wire attribution: the ONE
  xplane reader parses a ``jax.profiler`` capture of a windowed step and
  joins every device op back to the plan's promised wire (per-bucket
  measured overlap, measured-vs-promised payloads, ``SLT###`` conformance
  findings, trace-fed calibration records) — the measured leg of the
  planned → priced → measured loop.

Entry points: ``AutoDist(observability=ObsConfig(...))`` → ``autodist.obs``
(:class:`ObsRuntime`), ``python -m autodist_tpu.obs doctor <ft-dir>``, and
``python -m autodist_tpu.obs --selftest`` — the zero-hardware CPU proof.
See docs/observability.md.
"""
from __future__ import annotations

from autodist_tpu.obs.aggregate import HostAggregator
from autodist_tpu.obs.attrib import MeasuredWire, attribute
from autodist_tpu.obs.config import ObsConfig, ObsRuntime
from autodist_tpu.obs.doctor import Diagnosis, diagnose
from autodist_tpu.obs.exporter import (
    FileExporter,
    parse_openmetrics,
    render_openmetrics,
)
from autodist_tpu.obs.profiler import (
    StepProfiler, StepTimer, detect_peak_flops, peak_flops_for_kind)
from autodist_tpu.obs.recorder import FlightRecorder, read_records
from autodist_tpu.obs.sentry import Finding, Sentry, SentryConfig
from autodist_tpu.obs.slo import SLOSpec, SLOTracker, replay_flight_records
from autodist_tpu.obs.spans import (
    Span,
    SpanTracer,
    add_span,
    current_trace_id,
    enable_trace_out,
    events_for_request,
    get_tracer,
    span,
    stitch,
    traced,
)

__all__ = [
    "Diagnosis",
    "FileExporter",
    "Finding",
    "FlightRecorder",
    "HostAggregator",
    "MeasuredWire",
    "ObsConfig",
    "ObsRuntime",
    "SLOSpec",
    "SLOTracker",
    "Sentry",
    "SentryConfig",
    "Span",
    "SpanTracer",
    "StepProfiler",
    "StepTimer",
    "add_span",
    "attribute",
    "current_trace_id",
    "detect_peak_flops",
    "diagnose",
    "enable_trace_out",
    "events_for_request",
    "get_tracer",
    "parse_openmetrics",
    "peak_flops_for_kind",
    "read_records",
    "render_openmetrics",
    "replay_flight_records",
    "span",
    "stitch",
    "traced",
]
