"""The latency observatory: per-cell stage attribution (ISSUE 13).

The speed arc's claims — "the ~2 ms/cell dispatch overhead amortizes
to <0.1 ms/step", "serving meets its SLO under load" — are only
claims until wall-clock can be decomposed.  This module carries the
one record that makes them measurable: for every completed ``execute``
request, WHERE its end-to-end latency went, as eight contiguous
stages::

    vet      │ pre-submit analysis (cell vetting / effects classify)
    queue    │ scheduler wait (submit → mesh-slot grant)
    wire     │ grant → worker dequeue (encode + send + loop wait)
    dispatch │ worker dequeue → handler entry (replay cache, spans,
             │ busy bookkeeping)
    compile  │ XLA backend-compile seconds inside the handler (from
             │ the existing jax.monitoring listener, telemetry.py)
    execute  │ handler wall time minus compile
    reply    │ handler exit → coordinator reply arrival (wire back)
    deliver  │ last reply arrival → result handed to the caller

The coordinator stamps submit / grant / deliver on its own clock; the
worker stamps dequeue / handler-entry / handler-exit / reply-build on
ITS clock and the stamps ride home in the reply's optional ``lt``
header (:mod:`..messaging.codec` ``WIRE_EXTENSIONS``).  Worker stamps
are corrected onto the coordinator timebase with the per-rank offset
the NTP-style estimator already maintains (:mod:`.clock`) — the same
correction the Chrome-trace merge applies — so the stage chain is
monotone even across skewed host clocks.  Every stage is clamped at
zero: residual correction error may only shrink a stage, never
produce a negative duration.

Costs nothing when off: the coordinator pays one flag check per
request, the worker pays one flag check per message, and **no wire
header is emitted unless the observatory is enabled**
(``NBD_LAT=0`` — the same absent-when-off contract as ``tr``/``at``/
``ep``).

Completed records feed per-stage log-scale histograms
(``nbd_stage_seconds{stage=…}``, :data:`~.metrics.LATENCY_BUCKETS`)
plus a bounded ring of raw records (``NBD_LAT_RING``) that backs
``%dist_lat`` (per-stage p50/p95/p99 table, ``--last N`` waterfall)
and ``GET /latency.json`` on the scrape endpoint (:mod:`.httpd`).
While a ``%dist_trace`` session is active, each record is also
mirrored into the trace as ``stage/<name>`` child spans of the
request's send span, so the Perfetto view shows the same
decomposition inline.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..utils import knobs
from . import metrics as obs_metrics

# Stage names, in waterfall order.  The eight stages are CONTIGUOUS by
# construction (each starts where the previous ended), so their sum
# equals the end-to-end latency up to clock-correction clamping — the
# property the integration test pins at 10%.
STAGES = ("vet", "queue", "wire", "dispatch", "compile", "execute",
          "reply", "deliver")

DEFAULT_RING = 256


def percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile of an already-sorted list (shared by
    the observatory summary and the serving SLO block)."""
    if not sorted_vals:
        return 0.0
    i = int(round(q * (len(sorted_vals) - 1)))
    return sorted_vals[min(i, len(sorted_vals) - 1)]


def _ms(v: float) -> float:
    return round(v * 1e3, 3)


class _PendingLat:
    __slots__ = ("msg_id", "msg_type", "tenant", "t_vet", "t_submit",
                 "t_grant")

    def __init__(self, msg_id: str, msg_type: str, tenant: str | None,
                 now: float, vet_s: float | None):
        self.msg_id = msg_id
        self.msg_type = msg_type
        self.tenant = tenant
        self.t_submit = now
        # The vet stage is what the CALLER did before submit (cell
        # vetting, effects classification) — reported as a pre-duration
        # because the vetting layers don't know the msg_id yet.
        self.t_vet = now - max(0.0, vet_s or 0.0)
        self.t_grant = now  # overwritten by note_grant


class LatencyObservatory:
    """Coordinator-side stage-attribution recorder.

    One per :class:`~..messaging.coordinator.CommunicationManager`.
    Thread-safe: ``begin``/``note_grant`` run on submitter threads,
    ``complete`` on whichever thread finishes the dispatch, readers
    (``%dist_lat``, the scrape endpoint) on theirs.
    """

    def __init__(self, *, enabled: bool | None = None,
                 ring: int | None = None, registry=None,
                 now=time.time):
        self.enabled = (knobs.get_bool("NBD_LAT", True)
                        if enabled is None else bool(enabled))
        self._now = now
        self._reg = registry or obs_metrics.registry()
        self._lock = threading.Lock()
        self._pending: dict[str, _PendingLat] = {}
        n = ring if ring is not None else knobs.get_int("NBD_LAT_RING",
                                                        DEFAULT_RING)
        self._ring: deque = deque(maxlen=max(8, n))
        self.completed = 0
        self.dropped = 0  # begun but never completed (timeout, shed,
        # rejected, worker death, stamp-less replies)

    # ------------------------------------------------------------------
    # submit-side stamps (coordinator clock)

    def begin(self, msg_id: str, msg_type: str,
              tenant: str | None = None,
              vet_s: float | None = None) -> None:
        if not self.enabled:
            return
        p = _PendingLat(msg_id, msg_type, tenant, self._now(), vet_s)
        with self._lock:
            self._pending[msg_id] = p

    def note_grant(self, msg_id: str) -> None:
        """The scheduler granted the mesh slot (immediately on an idle
        mesh; after the queued wait otherwise) — the queue stage's end."""
        with self._lock:
            p = self._pending.get(msg_id)
        if p is not None:
            p.t_grant = self._now()

    def note_worker_free(self, msg_id: str,
                         t: float | None = None) -> None:
        """Overlap-aware attribution for pipelined cells (ISSUE 14):
        an async-windowed cell is transmitted while its predecessor
        still runs, so the serial worker loop only *reaches* it when
        the predecessor's reply lands.  The executor calls this at
        each predecessor completion for every still-in-flight
        successor, advancing the grant stamp to "the worker became
        free now" — the predecessor wait books as ``queue`` (what it
        is) instead of inflating ``wire``, and pipelined cells never
        double-count the overlapped time.  Monotone: the stamp only
        moves forward, and never past a completion."""
        if not self.enabled:
            return
        t = self._now() if t is None else t
        with self._lock:
            p = self._pending.get(msg_id)
        if p is not None and t > p.t_grant:
            p.t_grant = t

    def drop(self, msg_id: str) -> None:
        """Forget a request that will never complete normally
        (rejected / shed / timed out / worker died).  No-op after
        :meth:`complete` — callers put this in their ``finally``."""
        with self._lock:
            if self._pending.pop(msg_id, None) is not None:
                self.dropped += 1

    # ------------------------------------------------------------------
    # completion

    def complete(self, msg_id: str, replies: dict, offset,
                 t_deliver: float | None = None,
                 tracer=None, parent: dict | None = None) -> dict | None:
        """Close the record for a completed request.

        ``replies`` maps rank → reply Message; per-rank worker stamps
        are read from each reply's ``latency`` header and its
        coordinator-side arrival time from the ``recv_ts`` attribute
        the IO thread stamped.  ``offset(rank)`` is the estimated
        ``worker_clock − coordinator_clock`` (``ClockEstimator.offset``)
        applied as a subtraction.  Returns the record dict (also pushed
        onto the ring and into the histograms), or None when the
        request was never begun or no reply carried stamps.
        """
        with self._lock:
            p = self._pending.pop(msg_id, None)
        if p is None:
            return None
        t_deliver = self._now() if t_deliver is None else t_deliver

        per_rank: dict[int, dict] = {}
        recv_max = None
        crit_rank = None
        for r, msg in replies.items():
            st = getattr(msg, "latency", None)
            recv = getattr(msg, "recv_ts", None)
            if not isinstance(st, dict) or recv is None:
                continue
            try:
                off = float(offset(r))
                dq = float(st["dq"]) - off
                xs = float(st["xs"]) - off
                xe = float(st["xe"]) - off
                rs = float(st.get("rs") or st["xe"]) - off
                cs = max(0.0, float(st.get("cs") or 0.0))
            except (KeyError, TypeError, ValueError):
                continue
            # Worker-side durations are SAME-CLOCK differences — exact
            # regardless of the offset estimate: dispatch (dq→xs),
            # the handler (xs→xe), and reply BUILD (xe→rs: stamping,
            # epoch, replay-cache insert).  Likewise the total wire
            # budget (grant → recv minus the worker's residency) is a
            # coordinator-clock difference.  Only the SPLIT of that
            # budget into outbound wire vs reply wire needs the
            # offset-corrected anchors, so estimation error can skew
            # the split but never the sum — for sub-millisecond cells
            # a few hundred µs of offset error would otherwise clamp
            # one side to zero and inflate the other past e2e.
            handler = max(0.0, xe - xs)
            dispatch = max(0.0, xs - dq)
            build = max(0.0, rs - xe)
            both_wires = max(0.0, recv - p.t_grant
                             - (handler + dispatch + build))
            wire_raw = max(0.0, dq - p.t_grant)
            reply_raw = max(0.0, recv - rs)
            denom = wire_raw + reply_raw
            wire = (both_wires * wire_raw / denom if denom > 0
                    else both_wires / 2.0)
            per_rank[r] = {
                "wire": wire,
                "dispatch": dispatch,
                "compile": min(cs, handler),
                "execute": max(0.0, handler - cs),
                # The reply stage is handler exit → reply arrival:
                # worker-side build plus the wire back.
                "reply": build + (both_wires - wire),
            }
            if recv_max is None or recv > recv_max:
                recv_max = recv
                crit_rank = r
        if not per_rank:
            with self._lock:
                self.dropped += 1
            return None

        stages = {
            "vet": max(0.0, p.t_submit - p.t_vet),
            "queue": max(0.0, p.t_grant - p.t_submit),
            "deliver": max(0.0, t_deliver - recv_max),
        }
        # Worker-side stages summarize as the CRITICAL-PATH rank's
        # chain — the rank whose reply arrived last, i.e. the one the
        # caller actually waited on.  Mixing per-stage maxima across
        # ranks would over-count (rank A's slow execute plus rank B's
        # slow wire never happened in sequence) and break the
        # stages-sum-to-e2e contract.  Per-rank detail stays in the
        # record for the waterfall.
        stages.update(per_rank[crit_rank])
        e2e = max(0.0, t_deliver - p.t_vet)

        rec = {
            "msg_id": msg_id,
            "type": p.msg_type,
            "tenant": p.tenant,
            "ts": t_deliver,
            "e2e": e2e,
            "stages": stages,
            "ranks": {str(r): {k: round(v, 6) for k, v in d.items()}
                      for r, d in sorted(per_rank.items())},
        }

        reg = self._reg
        for s in STAGES:
            reg.histogram(
                "nbd_stage_seconds",
                "per-cell latency by attribution stage (vet/queue/"
                "wire/dispatch/compile/execute/reply/deliver)",
                {"stage": s},
                buckets=obs_metrics.LATENCY_BUCKETS).observe(stages[s])
        labels = ({"tenant": p.tenant} if p.tenant is not None else None)
        reg.histogram("nbd_cell_e2e_seconds",
                      "end-to-end cell latency (vet start → result "
                      "delivered)", labels,
                      buckets=obs_metrics.LATENCY_BUCKETS).observe(e2e)

        with self._lock:
            self._ring.append(rec)
            self.completed += 1

        if tracer is not None and getattr(tracer, "enabled", False):
            self._mirror_spans(tracer, parent, p, stages, recv_max,
                               t_deliver)
        return rec

    def _mirror_spans(self, tracer, parent, p: _PendingLat,
                      stages: dict, recv_max: float,
                      t_deliver: float) -> None:
        """Stage child spans under the request's send span: the
        Perfetto view of the same waterfall %dist_lat prints."""
        ctx = parent or {}
        t = p.t_vet
        bounds = []
        for s in ("vet", "queue", "wire", "dispatch", "compile",
                  "execute", "reply"):
            bounds.append((s, t, stages[s]))
            t += stages[s]
        bounds.append(("deliver", recv_max, t_deliver - recv_max))
        attrs = {"msg_id": p.msg_id}
        if p.tenant is not None:
            attrs["tenant"] = p.tenant
        for s, t0, dur in bounds:
            if dur <= 0:
                continue
            tracer.add_span(f"stage/{s}", "latency", t0, dur,
                            trace_id=ctx.get("tid"),
                            parent_id=ctx.get("sid"),
                            attrs=attrs)

    # ------------------------------------------------------------------
    # readers

    def records(self, last: int | None = None) -> list[dict]:
        with self._lock:
            recs = list(self._ring)
        return recs[-last:] if last else recs

    def summary(self) -> dict:
        """Percentile table over the ring, in milliseconds:
        ``{"count", "dropped", "e2e_ms": {p50,p95,p99,mean},
        "stages": {stage: {p50,p95,p99,mean,share}}}`` — ``share`` is
        the stage's mean as a fraction of the mean end-to-end."""
        recs = self.records()
        out: dict = {"count": len(recs), "dropped": self.dropped}
        if not recs:
            return out

        def _stats(vals: list[float]) -> dict:
            sv = sorted(vals)
            return {"p50": _ms(percentile(sv, 0.50)),
                    "p95": _ms(percentile(sv, 0.95)),
                    "p99": _ms(percentile(sv, 0.99)),
                    "mean": _ms(sum(sv) / len(sv))}

        e2e = [r["e2e"] for r in recs]
        e2e_mean = sum(e2e) / len(e2e)
        out["e2e_ms"] = _stats(e2e)
        out["stages"] = {}
        for s in STAGES:
            vals = [r["stages"].get(s, 0.0) for r in recs]
            st = _stats(vals)
            st["share"] = (round((sum(vals) / len(vals)) / e2e_mean, 4)
                           if e2e_mean > 0 else 0.0)
            out["stages"][s] = st
        return out

    def status_block(self, *, records: int = 32) -> dict:
        """The pool-status / latency.json payload: summary + the last
        few raw records (JSON-safe)."""
        return {"summary": self.summary(),
                "records": self.records(records)}


# ----------------------------------------------------------------------
# rendering (%dist_lat, shared by single-kernel and tenant mode)


def format_stage_table(summary: dict) -> str:
    """The ``%dist_lat`` per-stage percentile table."""
    n = summary.get("count", 0)
    if not n:
        return ("(no completed cells recorded yet — run a cell, or "
                "check NBD_LAT)")
    lines = [f"⏱ latency observatory · {n} cell(s) recorded"
             + (f" · {summary.get('dropped', 0)} dropped"
                if summary.get("dropped") else "")]
    hdr = (f"{'stage':<10}{'p50 ms':>9}{'p95 ms':>9}{'p99 ms':>9}"
           f"{'mean ms':>9}{'share':>8}")
    lines.append(hdr)
    lines.append("─" * len(hdr))
    for s in STAGES:
        st = (summary.get("stages") or {}).get(s) or {}
        lines.append(f"{s:<10}{st.get('p50', 0):>9}{st.get('p95', 0):>9}"
                     f"{st.get('p99', 0):>9}{st.get('mean', 0):>9}"
                     f"{st.get('share', 0) * 100:>7.1f}%")
    e = summary.get("e2e_ms") or {}
    lines.append(f"{'e2e':<10}{e.get('p50', 0):>9}{e.get('p95', 0):>9}"
                 f"{e.get('p99', 0):>9}{e.get('mean', 0):>9}")
    return "\n".join(lines)


def format_waterfall(records: list[dict], width: int = 44) -> str:
    """ASCII waterfall, one block per record: each stage as an offset
    bar on a shared scale, so WHERE the cell's wall-clock went is
    visible without Perfetto."""
    if not records:
        return "(no records)"
    blocks = []
    for rec in records:
        e2e = rec.get("e2e") or 0.0
        scale = width / e2e if e2e > 0 else 0.0
        who = f" · tenant {rec['tenant']}" if rec.get("tenant") else ""
        blocks.append(f"▼ {rec.get('msg_id', '?')[:12]} "
                      f"{rec.get('type')}{who} · "
                      f"e2e {_ms(e2e)} ms")
        t = 0.0
        stages = rec.get("stages") or {}
        for s in STAGES:
            v = stages.get(s, 0.0)
            pad = int(t * scale)
            bar = max(1, int(v * scale)) if v > 0 else 0
            blocks.append(f"  {s:<10}{_ms(v):>9} ms  "
                          f"{' ' * pad}{'█' * bar}")
            t += v
    return "\n".join(blocks)


# ----------------------------------------------------------------------
# clock-skew surfacing (satellite: the estimator's offsets as gauges +
# the %dist_status warning)


def export_clock_metrics(clock, registry=None) -> None:
    """Mirror the clock estimator's per-rank offset / min-RTT into
    gauges (``nbd_clock_offset_seconds{rank=}`` /
    ``nbd_clock_min_rtt_seconds{rank=}``) — skew silently degrades
    merged traces and stage attribution; this makes it scrapeable."""
    reg = registry or obs_metrics.registry()
    for r, st in clock.stats().items():
        reg.gauge("nbd_clock_offset_seconds",
                  "estimated worker−coordinator clock offset",
                  {"rank": str(r)}).set(st.get("offset_s") or 0.0)
        rtt = st.get("min_rtt_s")
        if rtt is not None:
            reg.gauge("nbd_clock_min_rtt_seconds",
                      "lowest observed request RTT (clock-sample "
                      "quality)", {"rank": str(r)}).set(rtt)


def skew_warnings(clock_stats: dict,
                  threshold_ms: float | None = None) -> list[str]:
    """Human warnings for ranks whose |offset| exceeds the
    ``NBD_LAT_SKEW_WARN_MS`` threshold — rendered by ``%dist_status``."""
    if threshold_ms is None:
        threshold_ms = knobs.get_float("NBD_LAT_SKEW_WARN_MS", 50.0)
    if threshold_ms <= 0:
        return []
    out = []
    for r, st in sorted(clock_stats.items()):
        off_ms = (st.get("offset_s") or 0.0) * 1e3
        if abs(off_ms) > threshold_ms:
            out.append(
                f"⚠ rank {r} clock offset {off_ms:+.1f} ms exceeds "
                f"{threshold_ms:.0f} ms (NBD_LAT_SKEW_WARN_MS) — "
                f"merged traces and stage attribution degrade with "
                f"skew; check host NTP")
    return out
