"""Lightweight span tracing with cross-process id propagation.

One :class:`Tracer` per process (module singleton, :func:`tracer`),
**disabled by default**: every instrumentation site first checks
``tracer.enabled`` — a single attribute read — so the framework pays
near-zero overhead until ``%dist_trace start`` flips it on.

A span is ``(name, kind, trace_id, span_id, parent_id, t0, dur, tid,
attrs)``.  ``trace_id`` names the tracing *session* (minted by
``Tracer.start`` on the coordinator and adopted by workers from the
wire context), ``span_id`` is unique per span, and ``parent_id`` links
children — either to the thread-local *current* span in this process,
or, for worker handler spans, to the coordinator's send span whose ids
rode the request envelope (the ``tr`` codec header;
see :mod:`nbdistributed_tpu.messaging.codec`).

Timestamps are ``time.time()`` wall clock — deliberately, so the
coordinator can merge per-process dumps onto one timeline after
correcting each rank by its estimated clock offset
(:mod:`~nbdistributed_tpu.observability.clock`).  ``tid`` is a small
per-process thread ordinal so overlapping spans from different threads
(e.g. the magic's send helper vs the cell wrapper) render on separate
tracks instead of producing an invalid stack.
"""

from __future__ import annotations

import sys
import threading
import time
import uuid
from typing import Any

# Bound on retained spans: a runaway traced loop must not grow the
# coordinator without limit.  At ~200 bytes/span this is ~10 MB.
MAX_SPANS = 50_000


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    __slots__ = ("name", "kind", "trace_id", "span_id", "parent_id",
                 "t0", "dur", "tid", "attrs")

    def __init__(self, name: str, kind: str, trace_id: str,
                 parent_id: str | None, tid: int,
                 attrs: dict[str, Any] | None = None):
        self.name = name
        self.kind = kind
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.t0 = time.time()
        self.dur = 0.0
        self.tid = tid
        self.attrs = attrs or {}

    def as_dict(self) -> dict:
        d = {"name": self.name, "kind": self.kind, "tid": self.tid,
             "trace_id": self.trace_id, "span_id": self.span_id,
             "t0": self.t0, "dur": self.dur}
        if self.parent_id:
            d["parent_id"] = self.parent_id
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class _NullCtx:
    """Shared no-op context manager: the disabled-tracing fast path of
    :func:`maybe_span` must not allocate."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class _SpanCtx:
    """Context manager returned by ``Tracer.span``: activates the span
    for the duration (children parent to it) and ends it on exit."""

    __slots__ = ("_tracer", "_span", "_prev")

    def __init__(self, tr: "Tracer", span: "Span"):
        self._tracer = tr
        self._span = span

    def __enter__(self):
        tls = self._tracer._tls
        self._prev = getattr(tls, "current", None)
        tls.current = self._span
        return self._span

    def __exit__(self, exc_type, exc, tb):
        self._tracer._tls.current = self._prev
        if exc_type is not None:
            self._span.attrs.setdefault("error", exc_type.__name__)
        self._tracer.end(self._span)
        return False


class _ActivateCtx:
    """Make an already-open span the thread-local current WITHOUT
    ending it on exit — how a span opened on one thread (the cell
    wrapper) becomes the parent for work on another (the send helper
    thread; thread-locals don't cross threads by themselves)."""

    __slots__ = ("_tracer", "_span", "_prev")

    def __init__(self, tr: "Tracer", span: "Span | None"):
        self._tracer = tr
        self._span = span

    def __enter__(self):
        tls = self._tracer._tls
        self._prev = getattr(tls, "current", None)
        if self._span is not None:
            tls.current = self._span
        return self._span

    def __exit__(self, *exc):
        self._tracer._tls.current = self._prev
        return False


class Tracer:
    """Process-local span recorder.  Thread-safe; all record paths are
    no-ops while ``enabled`` is False."""

    def __init__(self):
        self.enabled = False
        self.trace_id: str | None = None
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._instants: list[dict] = []
        self._dropped = 0
        self._tls = threading.local()
        self._thread_ids: dict[int, int] = {}

    # ------------------------------------------------------------------
    # lifecycle

    def start(self, trace_id: str | None = None) -> str:
        """Begin a tracing session: clears prior spans, mints (or
        adopts) the session trace id, enables recording."""
        with self._lock:
            self.trace_id = trace_id or _new_id()
            self._spans = []
            self._instants = []
            self._dropped = 0
            self._thread_ids = {}
            self.enabled = True
            return self.trace_id

    def stop(self) -> int:
        """Disable recording; spans stay buffered for ``dump``."""
        self.enabled = False
        with self._lock:
            return len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans = []
            self._instants = []
            self._dropped = 0

    # ------------------------------------------------------------------
    # recording

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._thread_ids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._thread_ids.setdefault(ident,
                                                  len(self._thread_ids))
        return tid

    def begin(self, name: str, kind: str = "", *,
              trace_id: str | None = None, parent_id: str | None = None,
              attrs: dict | None = None) -> Span | None:
        """Open a span (None when disabled).  With no explicit
        ``parent_id`` the thread-local current span is the parent; an
        explicit one (from a wire context) wins and its ``trace_id``
        should come with it."""
        if not self.enabled:
            return None
        if parent_id is None:
            cur = getattr(self._tls, "current", None)
            if cur is not None:
                parent_id = cur.span_id
                trace_id = trace_id or cur.trace_id
        return Span(name, kind, trace_id or self.trace_id or _new_id(),
                    parent_id, self._tid(), attrs)

    def end(self, span: Span | None) -> None:
        if span is None:
            return
        span.dur = time.time() - span.t0
        with self._lock:
            if len(self._spans) >= MAX_SPANS:
                self._dropped += 1
                return
            self._spans.append(span)

    def span(self, name: str, kind: str = "", *,
             trace_id: str | None = None, parent_id: str | None = None,
             attrs: dict | None = None):
        """``with tracer.span("x") as s:`` — begin + activate + end.
        Returns a no-op context when disabled."""
        sp = self.begin(name, kind, trace_id=trace_id,
                        parent_id=parent_id, attrs=attrs)
        if sp is None:
            return _NULL_CTX
        return _SpanCtx(self, sp)

    def activate(self, span: Span | None):
        """Adopt ``span`` as this thread's current (no end on exit)."""
        if span is None:
            return _NULL_CTX
        return _ActivateCtx(self, span)

    def add_span(self, name: str, kind: str, t0: float, dur: float, *,
                 trace_id: str | None = None,
                 parent_id: str | None = None,
                 attrs: dict | None = None) -> None:
        """Append an already-timed span (explicit ``t0``/``dur``).
        The latency observatory computes its stage decomposition only
        AFTER a request completes, so its ``stage/*`` child spans
        cannot be opened live — they are reconstructed here under the
        request's send span."""
        if not self.enabled:
            return
        sp = Span(name, kind, trace_id or self.trace_id or _new_id(),
                  parent_id, self._tid(), attrs)
        sp.t0 = t0
        sp.dur = max(0.0, dur)
        with self._lock:
            if len(self._spans) >= MAX_SPANS:
                self._dropped += 1
                return
            self._spans.append(sp)

    def instant(self, name: str, kind: str = "",
                attrs: dict | None = None) -> None:
        """Record a zero-duration event (Chrome ``ph: "i"``)."""
        if not self.enabled:
            return
        ev = {"name": name, "kind": kind, "t0": time.time(),
              "tid": self._tid()}
        if attrs:
            ev["attrs"] = attrs
        with self._lock:
            if len(self._instants) < MAX_SPANS:
                self._instants.append(ev)

    # ------------------------------------------------------------------
    # propagation / export

    def context(self) -> dict | None:
        """Wire context for the current span — the value of the codec's
        ``tr`` header — or None when disabled (no header emitted, the
        acceptance bar for zero-overhead-off)."""
        if not self.enabled:
            return None
        cur = getattr(self._tls, "current", None)
        if cur is not None:
            return {"tid": cur.trace_id, "sid": cur.span_id}
        return {"tid": self.trace_id or _new_id()}

    def context_for(self, span: Span | None) -> dict | None:
        if span is None:
            return None
        return {"tid": span.trace_id, "sid": span.span_id}

    def dump(self) -> dict:
        """JSON-able session dump: spans + instants (+ drop count)."""
        with self._lock:
            return {"trace_id": self.trace_id,
                    "spans": [s.as_dict() for s in self._spans],
                    "instants": list(self._instants),
                    "dropped": self._dropped}

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-global tracer (coordinator and each worker process
    own exactly one)."""
    return _TRACER


def maybe_span(name: str, kind: str = "", attrs: dict | None = None):
    """Module-level ``with maybe_span("collective/all_reduce"):`` for
    instrumentation sites — one flag check, zero allocation when
    tracing is off."""
    t = _TRACER
    if not t.enabled:
        return _NULL_CTX
    return t.span(name, kind, attrs=attrs)


# ----------------------------------------------------------------------
# named phases: one set of names for the tracer and the device profiler

_ANNOTATION = None   # jax.profiler.TraceAnnotation, once jax is imported


def _annotation():
    """``jax.profiler.TraceAnnotation`` on a process that has imported
    jax (the workers), else None: this module imports no jax, so the
    coordinator and the gateway stay off it."""
    global _ANNOTATION
    if _ANNOTATION is None and sys.modules.get("jax") is not None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _PhaseCtx:
    """A tracer span and a profiler annotation entered as one."""

    __slots__ = ("_span", "_ann")

    def __init__(self, span, ann):
        self._span = span
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


def phase(name: str, tick: int | None = None, wall: float | None = None):
    """``with phase("serve/step/sync", tick=seq):`` — a named phase of
    the serving tick, under the one name in every place it can be
    read: a span in this process's tracer while ``%dist_trace`` is on
    (child of the thread's current span), and, where jax is imported,
    a ``jax.profiler.TraceAnnotation`` — a flag check in C++ until a
    profile runs, then an event on the host plane of the ``.xplane.pb``
    beside the device's own lines.  ``tick`` (the gateway's sequence
    number) joins the two processes' spans and the device trace;
    ``wall`` (``time.time()``, on a tick's first annotation) lays the
    gateway's wall-clock spans beside the profile.  Tracer off and no
    jax: the shared null context."""
    ann = _ANNOTATION or _annotation()
    t = _TRACER
    if not t.enabled:
        # The hot path (every phase of every serving tick): no dict.
        if ann is None:
            return _NULL_CTX
        if wall is not None:
            return ann(name, tick=tick, wall=wall)
        return ann(name, tick=tick) if tick is not None else ann(name)
    attrs = {}
    if tick is not None:
        attrs["tick"] = tick
    if wall is not None:
        attrs["wall"] = wall
    span = t.span(name, "serve", attrs=attrs)
    return span if ann is None else _PhaseCtx(span, ann(name, **attrs))
