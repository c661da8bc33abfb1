"""First-class observability subsystem (ISSUE 2).

The reference ships zero observability (SURVEY.md §5); the rebuild's
BASELINE claims — pipeline overlap, byte-identical convergence, the
compaction speedup — are invisible without instrumentation.  This package
is the full layer on top of the span/counter registry PR 1 seeded:

* :mod:`.record`  — the process-wide registry: spans with bounded
  log-scale latency histograms (p50/p95/p99), counters, gauges, and a
  bounded per-occurrence event ring buffer with thread identity.
  ``crdt_enc_tpu.utils.trace`` is a compat shim onto this module.
* :mod:`.timeline` — Chrome-trace/Perfetto JSON export of the event log
  (per-thread lanes, chunk-index args, counter tracks) plus the chunk
  overlap analysis the streaming-pipeline acceptance tests assert on.
* :mod:`.runtime`  — the runtime's signals: XLA recompile counting via
  ``jax.monitoring``, H2D transfer accounting, device memory gauges
  sampled at fold boundaries.
* :mod:`.sink`     — run-scoped JSONL metrics sink (``CRDT_OBS_SINK``,
  schema-stamped, size-rotated) and Prometheus text exposition with
  registry-derived ``# HELP``/``# TYPE``.
* :mod:`.replication` — per-device replication/convergence status
  (ISSUE 6): causal stability watermark, per-actor op backlog,
  divergence and checkpoint-staleness gauges, sampled by the core on
  every open/read_remote/compact.
* :mod:`.fleet`    — cross-device aggregation of sink files: fleet
  stable watermark, convergence-lag distribution, backlog quantiles,
  and the BENCH_LOCAL perf-trend table with regression flagging.
* :mod:`.live`     — the live telemetry plane (ISSUE 11): an embedded
  HTTP endpoint serving ``/metrics`` (Prometheus exposition from the
  LIVE registry), ``/healthz`` (per-remote watermark/backlog/cycle
  health) and ``/snapshot``; opt-in via ``CRDT_OBS_HTTP`` or
  ``FoldService(live_port=...)``, never on the hot path.
* :mod:`.attribution` — cycle attribution: stage marginals
  (decrypt/decode/h2d/fold/scatter/seal), overlap efficiency,
  critical-path stage, and the e2e-vs-fold-marginal **gap report**
  (``obs_report gap``).
* :mod:`.slo`      — freshness SLOs: staleness-lag-vs-watermark and
  per-tenant seal-latency targets, live ``repl_slo_*`` gauges, and
  window-based burn accounting over sink records (``obs_report slo``).

CLI: ``python -m crdt_enc_tpu.tools.obs_report`` renders phase tables,
exports timelines, diffs runs, aggregates fleets (``fleet``/``trend``),
attributes cycles (``gap``) and accounts SLO burn (``slo``).
Span/metric names are registered in ``docs/observability.md`` and
linted by ``tools/check_span_names.py``.
"""

from . import (
    attribution,
    fleet,
    live,
    record,
    replication,
    runtime,
    sink,
    slo,
    timeline,
)

__all__ = [
    "attribution", "fleet", "live", "record", "replication", "runtime",
    "sink", "slo", "timeline",
]
