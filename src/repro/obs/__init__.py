"""Default-off observability for the serving stack (DESIGN.md §10).

`tracer.Span` puts each engine phase on the JAX profiler's clock (always)
and `tracer.Tracer` records spans/events/counters into a bounded ring
buffer and exports JSONL + Chrome ``trace.json``; `schema` is the phase/
lifecycle vocabulary and validator; `report` aggregates traces into the
phase-breakdown / waterfall views; `summary` is the shared
percentile-with-empty-guard math every metrics consumer reuses;
`quality` holds the quantization-quality counters; `metrics` is the
always-on registry (counters/gauges/histograms, Prometheus + JSONL
snapshot export, DESIGN.md §11) and `provenance` the shared artifact
header. `flight` is the always-on bounded per-step flight recorder and
incident-bundle writer, `detect` the anomaly-detector catalog that
triggers bundles, and `atomic` the shared tmp+fsync+rename protocol
every exporter writes through (DESIGN.md §14).
"""
from repro.obs.atomic import atomic_dir, atomic_write_text
from repro.obs.detect import DETECTORS, AnomalyDetector, Firing
from repro.obs.flight import (FlightRecorder, load_incident_bundle,
                              tail_lines, write_incident_bundle)
from repro.obs.metrics import (DEPTH_BUCKETS, LATENCY_BUCKETS_S, Counter,
                               Gauge, Histogram, MetricsRegistry,
                               RegistryQuantProbe, SnapshotWriter,
                               default_registry, load_snapshots)
from repro.obs.provenance import provenance
from repro.obs.quality import ActQuantProbe, code_stats, span_stats
from repro.obs.report import (lifecycle_summary, phase_breakdown,
                              request_waterfalls)
from repro.obs.schema import LIFECYCLE, PHASES, RETIRE_REASONS, \
    validate_events
from repro.obs.summary import mean, pct, summarize, token_agreement
from repro.obs.tracer import ANNOTATION_PREFIX, SCHEMA_VERSION, Span, \
    Tracer, chrome_trace, load_jsonl

__all__ = [
    "Tracer", "Span", "ANNOTATION_PREFIX", "SCHEMA_VERSION",
    "chrome_trace", "load_jsonl",
    "PHASES", "LIFECYCLE", "RETIRE_REASONS", "validate_events",
    "phase_breakdown", "request_waterfalls", "lifecycle_summary",
    "pct", "mean", "summarize", "token_agreement",
    "ActQuantProbe", "code_stats", "span_stats",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "SnapshotWriter", "RegistryQuantProbe", "default_registry",
    "load_snapshots", "LATENCY_BUCKETS_S", "DEPTH_BUCKETS",
    "provenance",
    "atomic_write_text", "atomic_dir",
    "FlightRecorder", "write_incident_bundle", "load_incident_bundle",
    "tail_lines", "AnomalyDetector", "Firing", "DETECTORS",
]
