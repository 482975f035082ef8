"""Device-resident telemetry: in-scan metrics pytrees, host-side JSONL
export, and profiler trace hooks.

Three pieces, one discipline (nothing leaves the device mid-scan):

  metrics.py — a :class:`MetricsSpec` registry of metric *groups*
               (``selection`` / ``training`` / ``fairness`` /
               ``async``); each enabled group contributes fields to a
               flat ``Telemetry`` dict pytree emitted as an extra
               ``lax.scan`` output by all three drivers (sync scanned
               loop, async tick scan, vmapped sweep).  Disabled groups
               materialize zero-width arrays — same pytree structure,
               no second code path, no re-jits.
  export.py — flattens stacked telemetry to JSONL + a summary dict,
               and stamps environment metadata (jax version, backend,
               git SHA) into benchmark artifacts so the bench gate can
               refuse cross-machine comparisons.
  trace.py  — ``jax.profiler`` host spans behind the
               ``REPRO_TRACE=1`` env switch (the drivers' phases:
               ``fed/keys``, ``fed/scan_segment[n]``, ``fed/history``,
               ``fed/eval``), and the scanned loop's optimized HLO
               text (``program_text``), whose ``jax.named_scope``
               metadata maps a device trace's operations to the
               round's phases.

See docs/observability.md for the full tour.
"""
from repro.telemetry.export import (env_stamp, read_jsonl, records_from_telemetry,
                                    summarize, telemetry_from_records,
                                    write_jsonl, write_run, write_sweep)
from repro.telemetry.metrics import (GROUPS, Metrics, MetricsSpec,
                                     TelemetryCtx, client_true_entropy,
                                     make_metrics)
from repro.telemetry.trace import (program_text, register_program,
                                   trace_enabled, trace_span)

__all__ = [
    "GROUPS", "Metrics", "MetricsSpec", "TelemetryCtx",
    "client_true_entropy", "make_metrics",
    "env_stamp", "read_jsonl", "records_from_telemetry", "summarize",
    "telemetry_from_records", "write_jsonl", "write_run", "write_sweep",
    "program_text", "register_program", "trace_enabled", "trace_span",
]
