"""Telemetry v1 — one observability layer for train, serve, and kernels
(DESIGN.md §"Telemetry v1").

Everything emits into a single schema-versioned JSONL stream format
(:mod:`repro.telemetry.schema` — a superset of the MetricsHook format):

* **optimizer-health probes** (:mod:`~repro.telemetry.probes`) — folded
  into the jitted step program, riding the runner's one bundled per-step
  ``device_get`` (zero extra recompiles, zero extra host syncs);
* **serve gauges** (:mod:`~repro.telemetry.serve`) — pool / scheduler /
  time-split sampling at the engine's chunk boundaries;
* **kernel roofline counters** (:mod:`~repro.telemetry.kernels`);
* one merging CLI: ``python -m repro.telemetry.report``.

Timelines are the profiler's own: the program's host spans
(:func:`~repro.telemetry.spans.span`, names under ``repro.``) and the
named scopes of its device programs land in the trace that
``--profile-dir`` writes, on one clock with the device's ops.
"""
from repro.telemetry.kernels import (KernelCounters, adalomo_update_counters,
                                     counters_for,
                                     paged_decode_attention_counters,
                                     zoo_cases)
from repro.telemetry.probes import ObservabilitySpec, instrument_step
from repro.telemetry.schema import (SCHEMA_VERSION, SchemaError,
                                    TelemetryStream, classify, header_record,
                                    iter_data_records, jsonify,
                                    parse_records, read_stream,
                                    validate_bench, validate_bench_dir,
                                    validate_record)
from repro.telemetry.serve import ServeTelemetry
from repro.telemetry.spans import span, step_span
from repro.telemetry.writer import TelemetryWriter

__all__ = [
    "SCHEMA_VERSION", "SchemaError", "TelemetryStream", "classify",
    "header_record", "iter_data_records", "jsonify", "parse_records",
    "read_stream", "validate_record", "validate_bench",
    "validate_bench_dir",
    "ObservabilitySpec", "instrument_step",
    "ServeTelemetry", "TelemetryWriter",
    "KernelCounters", "counters_for", "adalomo_update_counters",
    "paged_decode_attention_counters", "zoo_cases",
    "span", "step_span",
]
