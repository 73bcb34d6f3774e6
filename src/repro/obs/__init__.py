"""Unified observability plane: record, attribute, triage, aggregate.

Dependency-free (numpy + stdlib) metrics subsystem:

  * :mod:`repro.obs.registry` -- named Counters/Gauges/Histograms with
    ``(phase, shard, modality)``-style labels and a Greenwald-Khanna
    streaming quantile sketch behind every histogram.
  * :mod:`repro.obs.ledger` -- the canonical MFU / goodput / straggler /
    imbalance formulas and the per-step :class:`StepLedger`.
  * :mod:`repro.obs.decompose` -- the per-step MFU-gap waterfall:
    additive, closure-checked attribution of ``1 - goodput`` into
    per-(phase, modality) residual imbalance, exposed dispatcher
    latency, kernel dead tiles, MoE drops, preemption recompute and
    checkpoint stalls.
  * :mod:`repro.obs.anomaly` -- online robust detectors (EWMA + MAD
    bands; spike vs level-shift vs trend) over every recorded series.
  * :mod:`repro.obs.triage` -- flight-record correlator: waterfall
    history + anomalies + alerts -> a ranked root-cause report
    (``python -m repro.obs.triage <metrics-dir>``).
  * :mod:`repro.obs.aggregate` -- mergeable registries across DP
    shards / engine replicas (GK sketch merge with a tested post-merge
    rank-error bound), a strict OpenMetrics parser, and the live
    ``/metrics`` + ``/triage`` HTTP exporter.
  * :mod:`repro.obs.export` -- atomic OpenMetrics textfile, crash-safe
    JSONL flight recorder, and the alert bridge.
  * :mod:`repro.obs.spans` -- host spans and device step phases on the
    profiler's clock (``jax.profiler.TraceAnnotation`` and
    ``jax.named_scope``), and the reader that attributes compiled
    operations to phases.
"""
from repro.obs.aggregate import (MetricsServer, aggregate_registries,
                                 merge_sketches, parse_openmetrics,
                                 registry_from_state_dict,
                                 registry_state_dict, validate_openmetrics)
from repro.obs.anomaly import Anomaly, AnomalyMonitor, SeriesDetector
from repro.obs.decompose import GapWaterfall, WaterfallStep
from repro.obs.export import (AlertBridge, FlightRecorder, read_flight_record,
                              render_openmetrics, write_openmetrics)
from repro.obs.ledger import (StepLedger, goodput_fraction, hw_mfu,
                              phase_imbalance, projected_mfu, simulated_mfu,
                              straggler_overhead, useful_flops_ratio)
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                QuantileSketch, get_registry, set_registry)
from repro.obs.triage import render_text, triage, triage_flight

__all__ = [
    "AlertBridge",
    "Anomaly",
    "AnomalyMonitor",
    "Counter",
    "FlightRecorder",
    "GapWaterfall",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "MetricsServer",
    "QuantileSketch",
    "SeriesDetector",
    "StepLedger",
    "WaterfallStep",
    "aggregate_registries",
    "get_registry",
    "goodput_fraction",
    "hw_mfu",
    "merge_sketches",
    "parse_openmetrics",
    "phase_imbalance",
    "projected_mfu",
    "read_flight_record",
    "registry_from_state_dict",
    "registry_state_dict",
    "render_openmetrics",
    "render_text",
    "set_registry",
    "simulated_mfu",
    "straggler_overhead",
    "triage",
    "triage_flight",
    "useful_flops_ratio",
    "validate_openmetrics",
    "write_openmetrics",
]
