"""Unified observability: cross-rank tracing + metrics (L2.5, ISSUE 2).

SURVEY §5.5 flags metrics/logging/observability as the reference's
biggest operational gap, and the resilience layer (PR 1) made it acute:
retry, dedup, and supervisor counters exist but are scattered across
ad-hoc ``get_status`` dicts with no history, no cross-rank view, and no
export.  This package is the one coherent place where traces and
metrics from the coordinator and every rank land:

- :mod:`~nbdistributed_tpu.observability.spans` — lightweight span
  tracing.  A process-local :class:`Tracer` (off by default, one
  attribute check when disabled) records named spans with
  ``trace_id``/``span_id``/``parent_id``; the ids propagate across the
  control plane in an optional codec header field (mirroring the
  resilience layer's ``attempt``), so a worker's handler span is a
  *child* of the coordinator's send span in one merged timeline.
- :mod:`~nbdistributed_tpu.observability.clock` — NTP-style per-rank
  clock-offset estimation from request/response RTTs, so merged
  timelines align even though every process stamps its own wall clock.
- :mod:`~nbdistributed_tpu.observability.metrics` — a process-local
  registry of counters / gauges / fixed-bucket histograms (wire
  messages and bytes, retries, dedup hits, cell and collective
  durations, fault injections, supervisor transitions) with JSON and
  Prometheus-text export.
- :mod:`~nbdistributed_tpu.observability.export` — merge coordinator +
  all-rank span dumps into one Chrome-trace-event JSON
  (Perfetto-loadable, ``pid`` = rank) with :class:`FaultPlan` decisions
  folded in as instant events, so chaos runs are visually debuggable.
- :mod:`~nbdistributed_tpu.observability.flightrec` — the ISSUE 3
  layer the above lack: an **always-on, crash-surviving flight
  recorder**.  Every process appends self-delimiting event records to
  an mmap-backed ring file under the shared run directory
  (``NBD_RUN_DIR``); a reader recovers the ring — including a torn
  final record — from the file of a SIGKILLed process.
- :mod:`~nbdistributed_tpu.observability.telemetry` — per-worker
  device telemetry (HBM in-use/peak, live buffers, compile activity
  and what it was made of: tracing, lowering, backend compiles, cache
  loads) sampled off the hot path and piggybacked on heartbeat pings,
  so the coordinator holds a push-based live view that works mid-cell.
- :mod:`~nbdistributed_tpu.observability.bringup` — set-up on one
  timeline (ISSUE 37): a worker's contiguous bring-up stages
  (``interpreter`` … ``connect``) as flight records and as a list on
  the first heartbeat's telemetry, merged with the spawner's ``Popen``
  and attach stamps into ``comm.bringup()``; the lines ``%dist_status``
  and ``%dist_pool status`` print.
- :mod:`~nbdistributed_tpu.observability.postmortem` — assembles the
  flight rings, last telemetry, coordinator spans, and fault events
  into a postmortem bundle (merged Chrome trace + human report) when a
  worker dies.
- :mod:`~nbdistributed_tpu.observability.latency` — the latency
  observatory (ISSUE 13): per-cell eight-stage attribution
  (vet/queue/wire/dispatch/compile/execute/reply/deliver) from
  coordinator + worker stage stamps riding the optional ``lt`` reply
  header, clock-corrected, feeding log-scale histograms, the
  ``%dist_lat`` table/waterfall, and the scrape endpoint.
- :mod:`~nbdistributed_tpu.observability.httpd` — the live scrape
  endpoint: a stdlib ``ThreadingHTTPServer`` serving ``GET /metrics``
  (Prometheus text), ``/healthz``, and ``/latency.json``
  (``NBD_METRICS_PORT``; token-gated on gateway pools).

Surfaced via ``%dist_trace start|stop|save``, ``%dist_metrics``,
``%dist_top``, and ``%dist_postmortem``.  Everything here is
stdlib-only at import time (no JAX import — telemetry touches devices
lazily) so the coordinator side stays light and the modules are
unit-testable without a backend.
"""

from .clock import ClockEstimator
from .flightrec import FlightRecorder, read_ring
from .latency import LatencyObservatory
from .metrics import MetricsRegistry, registry
from .spans import Tracer, maybe_span, tracer
from .telemetry import TelemetrySampler

__all__ = ["ClockEstimator", "FlightRecorder", "LatencyObservatory",
           "MetricsRegistry", "TelemetrySampler", "Tracer",
           "maybe_span", "read_ring", "registry", "tracer"]
