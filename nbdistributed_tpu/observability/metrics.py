"""Process-local metrics registry: counters, gauges, histograms.

One :func:`registry` per process (coordinator and each worker) holding
the numbers that used to live scattered across ad-hoc ``get_status``
dicts: wire messages and bytes, retries, dedup hits, cell and
collective durations, fault injections, supervisor transitions.
Exported two ways:

- :meth:`MetricsRegistry.to_json` — the payload of the worker
  ``metrics`` handler and ``%dist_metrics``;
- :meth:`MetricsRegistry.prometheus_text` — standard Prometheus
  exposition text, so a deployment can be scraped with nothing but a
  file/HTTP shim.

Metrics are keyed by ``(name, labels)``; histogram buckets are FIXED
at creation (cumulative ``le`` semantics, ``+Inf`` implicit) so
``observe`` is O(#buckets) with no allocation.  Everything is
stdlib-only and thread-safe.
"""

from __future__ import annotations

import re
import threading
from typing import Mapping

# Prometheus' classic latency ladder, widened to cover XLA compiles.
DURATION_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

# Log-scale ladder for the latency observatory (ISSUE 13): stage and
# SLO distributions span ~100 µs (worker dispatch, per-token decode)
# to tens of seconds (cold compiles), so the classic ladder's 1 ms
# floor would fold every sub-millisecond stage into one bucket.
LATENCY_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                   0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 30.0)


def _fmt(v: float) -> str:
    """Prometheus sample formatting: integers without the trailing .0,
    floats via repr (full precision)."""
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(v)


def _labels_suffix(labels: tuple[tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return "{" + inner + "}"


class Counter:
    """Monotonic counter."""

    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError("counters only go up (use a gauge)")
        with self._lock:
            self.value += n


class Gauge:
    """Set-anywhere value (mirrored snapshots, staleness, sizes)."""

    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n


class Histogram:
    """Fixed-bucket histogram with cumulative Prometheus semantics."""

    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, buckets=DURATION_BUCKETS):
        bs = tuple(sorted(float(b) for b in buckets))
        if not bs:
            raise ValueError("histogram needs at least one bucket bound")
        self._lock = threading.Lock()
        self.buckets = bs
        self.counts = [0] * (len(bs) + 1)  # last = +Inf overflow
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        i = 0
        for b in self.buckets:
            if v <= b:
                break
            i += 1
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def cumulative(self) -> list[tuple[str, int]]:
        """[(le, cumulative_count)] including +Inf."""
        out = []
        acc = 0
        with self._lock:
            counts = list(self.counts)
            for b, c in zip(self.buckets, counts):
                acc += c
                out.append((_fmt(b), acc))
            out.append(("+Inf", acc + counts[-1]))
        return out


class MetricsRegistry:
    """get-or-create metric store keyed by (name, sorted label items)."""

    _KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}

    def __init__(self):
        self._lock = threading.Lock()
        # name -> (kind, help, {label_items: metric})
        self._metrics: dict[str, tuple[str, str, dict]] = {}

    # ------------------------------------------------------------------
    # registration

    def _get(self, kind: str, name: str, help: str,
             labels: Mapping[str, str] | None, **kw):
        key = tuple(sorted((k, str(v)) for k, v in (labels or {}).items()))
        with self._lock:
            entry = self._metrics.get(name)
            if entry is None:
                entry = (kind, help, {})
                self._metrics[name] = entry
            elif entry[0] != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {entry[0]}, "
                    f"not {kind}")
            series = entry[2]
            m = series.get(key)
            if m is None:
                m = self._KINDS[kind](**kw)
                series[key] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Mapping[str, str] | None = None) -> Counter:
        return self._get("counter", name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Mapping[str, str] | None = None) -> Gauge:
        return self._get("gauge", name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Mapping[str, str] | None = None,
                  buckets=DURATION_BUCKETS) -> Histogram:
        return self._get("histogram", name, help, labels, buckets=buckets)

    # NOTE deliberately no clear(): instrumentation sites (the
    # collectives' decoration-time histograms, the wire hook's counter
    # cache) hold direct references to their metric objects — dropping
    # the registry's entries would orphan those handles, which would
    # keep incrementing invisibly forever.  Tests wanting isolation
    # build a fresh MetricsRegistry.

    def remove_label_series(self, label: str, value: str) -> int:
        """Drop every series whose label set includes
        ``label="value"``; returns how many series were removed.

        The gateway calls this with ``("tenant", name)`` when a tenant
        is EVICTED: per-tenant series otherwise accumulate one entry
        per tenant name for the daemon's lifetime (the PR 8 stated
        limit this closes).  Only safe for series resolved through the
        registry at each use site (the per-tenant counters are); a
        removed series whose handle something cached would keep
        incrementing invisibly — exactly why there is no blanket
        ``clear()``.  Metric names whose last series is removed keep
        their (name, kind, help) registration so a later re-create
        cannot flip kinds."""
        removed = 0
        with self._lock:
            for _name, (_kind, _help, series) in self._metrics.items():
                doomed = [key for key in series
                          if (label, str(value)) in key]
                for key in doomed:
                    del series[key]
                removed += len(doomed)
        return removed

    # ------------------------------------------------------------------
    # export

    def to_json(self) -> dict:
        """``{"counters": {...}, "gauges": {...}, "histograms": {...}}``
        with label-qualified series names (``name{k="v"}``)."""
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        with self._lock:
            items = [(n, k, dict(s)) for n, (k, _h, s)
                     in self._metrics.items()]
        for name, kind, series in sorted(items):
            for key, m in sorted(series.items()):
                qname = name + _labels_suffix(key)
                if kind == "counter":
                    out["counters"][qname] = m.value
                elif kind == "gauge":
                    out["gauges"][qname] = m.value
                else:
                    out["histograms"][qname] = {
                        "buckets": dict(m.cumulative()),
                        "sum": m.sum, "count": m.count}
        return out

    def prometheus_text(self) -> str:
        """Prometheus exposition format (text/plain version 0.0.4)."""
        lines: list[str] = []
        with self._lock:
            items = [(n, k, h, dict(s)) for n, (k, h, s)
                     in self._metrics.items()]
        for name, kind, help, series in sorted(items):
            if help:
                lines.append(f"# HELP {name} {help}")
            lines.append(f"# TYPE {name} {kind}")
            for key, m in sorted(series.items()):
                if kind == "histogram":
                    for le, c in m.cumulative():
                        lines.append(
                            f"{name}_bucket"
                            f"{_labels_suffix(key + (('le', le),))} {c}")
                    lines.append(f"{name}_sum{_labels_suffix(key)} "
                                 f"{_fmt(m.sum)}")
                    lines.append(f"{name}_count{_labels_suffix(key)} "
                                 f"{m.count}")
                else:
                    lines.append(f"{name}{_labels_suffix(key)} "
                                 f"{_fmt(m.value)}")
        return "\n".join(lines) + ("\n" if lines else "")


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-global registry."""
    return _REGISTRY


# ----------------------------------------------------------------------
# exposition-format validation (the CI scrape check and the golden
# tests share one rule set, so "parses" means the same thing in both)

_SAMPLE_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                      # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"\n]*"'            # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"\n]*")*\})?'       # more labels
    r" [-+]?(?:[0-9.eE+-]+|Inf|NaN)$")                # value


def validate_prometheus_text(text: str) -> list[str]:
    """Structural check of Prometheus exposition text (version 0.0.4
    as :meth:`MetricsRegistry.prometheus_text` emits it).  Returns a
    list of human-readable problems — empty means parseable.  Checks
    line syntax, that every sample's family was TYPE-declared, and
    that histogram families expose ``_bucket``/``_sum``/``_count``."""
    errors: list[str] = []
    typed: dict[str, str] = {}
    sampled: set[str] = set()
    for i, line in enumerate(text.splitlines(), 1):
        if not line:
            errors.append(f"line {i}: blank line inside exposition")
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary",
                    "untyped"):
                errors.append(f"line {i}: malformed TYPE line")
            else:
                typed[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue  # HELP / comments: free text
        if not _SAMPLE_LINE.match(line):
            errors.append(f"line {i}: unparseable sample {line!r}")
            continue
        name = line.split("{", 1)[0].split(" ", 1)[0]
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in typed:
                base = name[:-len(suffix)]
                break
        sampled.add(base)
        if base not in typed:
            errors.append(f"line {i}: sample {name!r} has no TYPE "
                          f"declaration")
    for name, kind in typed.items():
        if kind != "histogram" or name not in sampled:
            continue
        for suffix in ("_bucket", "_sum", "_count"):
            if f"# TYPE {name} histogram" in text \
                    and f"{name}{suffix}" not in text:
                errors.append(f"histogram {name} is missing its "
                              f"{suffix} series")
    return errors


# ----------------------------------------------------------------------
# wire accounting

_hook_installed = False


def install_wire_hook() -> None:
    """Route the codec's per-frame accounting into the registry:
    ``nbd_wire_messages_total{dir,type}`` and
    ``nbd_wire_bytes_total{dir}``.  Idempotent; called by both ends of
    the control plane at startup.  The hook pre-resolves its counters
    through a tiny cache so the per-frame cost is two dict hits and
    two increments."""
    global _hook_installed
    if _hook_installed:
        return
    from ..messaging import codec

    reg = _REGISTRY
    series: dict[tuple[str, str], Counter] = {}
    bytes_c = {
        "tx": reg.counter("nbd_wire_bytes_total",
                          "control-plane bytes by direction",
                          {"dir": "tx"}),
        "rx": reg.counter("nbd_wire_bytes_total",
                          "control-plane bytes by direction",
                          {"dir": "rx"}),
    }

    def hook(direction: str, msg_type: str, nbytes: int) -> None:
        c = series.get((direction, msg_type))
        if c is None:
            c = reg.counter("nbd_wire_messages_total",
                            "control-plane frames by direction and type",
                            {"dir": direction, "type": msg_type})
            series[(direction, msg_type)] = c
        c.inc()
        bytes_c[direction].inc(nbytes)

    codec.set_wire_hook(hook)
    _hook_installed = True
