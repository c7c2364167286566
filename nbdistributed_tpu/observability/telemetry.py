"""Per-worker live device telemetry (ISSUE 3 tentpole, part 2).

The status probe (``get_status``) answers through the worker's SERIAL
request loop, so it stalls exactly when the operator most wants it —
mid-cell, mid-compile, mid-OOM-death-spiral.  This module is the
*push*-based alternative: a :class:`TelemetrySampler` snapshots device
state off the hot path and the worker's heartbeat thread piggybacks the
compact snapshot on its ping ``data``, giving the coordinator a live
per-rank view (HBM in use / peak, live buffer count, compile activity,
resilience counters) that works while the main thread is busy.

Snapshot shape (compact on purpose — it rides every Nth 2-second
heartbeat)::

    {"ts": unix_s,
     "hbm": [{"id", "in_use", "peak", "limit"}, ...],   # bytes | None
     "bufs": live jax.Array count,
     "compiles": backend_compile count, "compile_s": cumulative seconds,
     "cw": compile_split(),             # trace / lower / backend / cache
     ...extra_fn() fields (dedup hits, msgs seen, the bring-up stages)}

The module imports no JAX at import time (the observability package
stays coordinator-safe); all device access is lazy and fail-soft.
Device memory numbers come from ``Device.memory_stats()`` — the same
source ``runtime/introspect.py:device_status`` reports, refactored here
so the pull path and the push path cannot drift.
"""

from __future__ import annotations

import threading
import time

from . import metrics as obs_metrics

DEFAULT_INTERVAL_S = 4.0


def device_memory(device) -> dict | None:
    """``{"in_use", "peak", "limit"}`` in raw bytes from
    ``Device.memory_stats()``, or None when the backend exposes no
    stats (CPU devices return None).  Shared by the ``get_status``
    pull path (:func:`~nbdistributed_tpu.runtime.introspect
    .device_status`) and the heartbeat push path."""
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    def _get(key):
        v = stats.get(key)
        return int(v) if v is not None else None
    return {"in_use": _get("bytes_in_use"),
            "peak": _get("peak_bytes_in_use"),
            "limit": _get("bytes_limit")}


class _CompileWatch:
    """The one ``jax.monitoring`` listener: counts XLA backend compiles
    from duration events — the only compile signal that fires *inside*
    the blocking compile path, which is exactly when the serial loop
    can't answer a status probe — and splits what a compile is made of
    (ISSUE 37): tracing, lowering, the backend's own compile, and the
    persistent cache's retrieval, with the cache's hits and misses and
    the eight longest programs by name.  Cumulative since the listener
    was installed (where the worker imports jax).  Process-global
    (listeners cannot be unregistered); instances read deltas off the
    shared counters.

    ``jaxpr_trace_duration`` nests (an outer jit's trace holds its
    inner jits' traces, and a lowering may trace again), so each
    thread keeps the depth from the ``record_scalar`` that opens every
    such event and only an outermost one adds its seconds: the four
    sums never hold the same instant twice."""

    _lock = threading.Lock()
    _installed = False
    _local = threading.local()   # .depth, .how: this thread's compile
    count = 0
    seconds = 0.0
    trace_s = 0.0
    lower_s = 0.0
    backend_s = 0.0
    cache_load_s = 0.0
    hits = 0
    misses = 0
    slowest: list = []           # [program, seconds, hit|miss|uncached]

    _PARTS = {"jaxpr_trace_duration": "trace_s",
              "jaxpr_to_mlir_module_duration": "lower_s",
              "backend_compile_duration": "backend_s"}

    @classmethod
    def install(cls) -> bool:
        with cls._lock:
            if cls._installed:
                return True
            try:
                import jax.monitoring as jmon

                jmon.register_scalar_listener(cls._on_enter)
                jmon.register_event_duration_secs_listener(
                    cls._on_duration)
                jmon.register_event_listener(cls._on_event)
            except Exception:
                return False
            cls._installed = True
            return True

    @classmethod
    def _on_enter(cls, name: str, _value, **kw) -> None:
        if name.rpartition("/")[2] in cls._PARTS:
            loc = cls._local
            loc.depth = getattr(loc, "depth", 0) + 1

    @classmethod
    def _on_event(cls, name: str, **kw) -> None:
        # jax 0.9: a hit is recorded where the executable was read back,
        # a miss where a compiled one was written (a compile under the
        # cache's thresholds is neither).  Both fire inside the
        # backend_compile_duration block of the same thread.
        if name.endswith("/compilation_cache/cache_hits"):
            cls._local.how = "hit"
            with cls._lock:
                cls.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            cls._local.how = "miss"
            with cls._lock:
                cls.misses += 1

    @classmethod
    def _on_duration(cls, name: str, secs: float, **kw) -> None:
        tail = name.rpartition("/")[2]
        if tail == "cache_retrieval_time_sec":
            with cls._lock:
                cls.cache_load_s += secs
            return
        part = cls._PARTS.get(tail)
        if part is None:
            return
        loc = cls._local
        loc.depth = max(0, getattr(loc, "depth", 1) - 1)
        outermost = loc.depth == 0
        if part != "backend_s":
            if outermost:
                with cls._lock:
                    setattr(cls, part, getattr(cls, part) + secs)
            return
        how = getattr(loc, "how", None) or "uncached"
        loc.how = None
        with cls._lock:
            cls.count += 1
            cls.seconds += secs
            if how != "hit" and outermost:
                cls.backend_s += secs
            cls.slowest = sorted(
                cls.slowest + [[str(kw.get("fun_name") or "?"),
                                round(secs, 3), how]],
                key=lambda e: -e[1])[:8]

    @classmethod
    def snapshot(cls) -> tuple[int, float]:
        with cls._lock:
            return cls.count, round(cls.seconds, 3)

    @classmethod
    def split(cls) -> dict:
        with cls._lock:
            return {"trace_s": round(cls.trace_s, 4),
                    "lower_s": round(cls.lower_s, 4),
                    "backend_s": round(cls.backend_s, 4),
                    "cache_load_s": round(cls.cache_load_s, 4),
                    "hits": cls.hits, "misses": cls.misses,
                    "slowest": [list(e) for e in cls.slowest]}


def install_compile_watch() -> bool:
    """Install the listener now: the worker calls this where it imports
    jax, so the namespace's own compiles are on the record."""
    return _CompileWatch.install()


def compile_snapshot() -> tuple[int, float]:
    """``(count, seconds)`` of ``backend_compile_duration`` events so
    far in this process (installs the listener on first use).  One
    event is one program made ready, whichever way: compiled by the
    backend, or read back from the persistent cache;
    :func:`compile_split` tells the two apart.  The serve_step handler
    takes the delta across a tick, so a tick that compiled, or loaded
    a program from the cache, says so."""
    _CompileWatch.install()
    return _CompileWatch.snapshot()


def compile_split() -> dict:
    """What the compiles so far were made of: ``trace_s``, ``lower_s``,
    ``backend_s`` (backend compiles that were no cache hit),
    ``cache_load_s`` (the persistent cache's retrieval), the cache's
    ``hits`` and ``misses``, and ``slowest``: the eight longest
    programs as ``[name, seconds, "hit" | "miss" | "uncached"]``."""
    return _CompileWatch.split()


def compile_seconds() -> float:
    """Cumulative XLA backend-compile seconds observed in this process
    (0.0 until the listener is installed).  The latency observatory's
    worker-side stamps take a delta of this around each handler, so a
    cell's first-run compile shows up as its own stage instead of
    inflating ``execute``."""
    with _CompileWatch._lock:
        return _CompileWatch.seconds


class TelemetrySampler:
    """Samples device state for one worker rank.

    ``sample()`` forces a snapshot; ``maybe_sample()`` respects the
    minimum interval (heartbeats fire every 2 s — resampling device
    stats and walking live arrays on every ping would make the
    liveness signal itself a load source) and returns None between
    samples so unchanged pings stay small.  Every snapshot also feeds
    the process metrics registry so ``%dist_metrics`` exports carry
    the device numbers.
    """

    def __init__(self, rank: int, *,
                 min_interval_s: float = DEFAULT_INTERVAL_S,
                 extra_fn=None):
        self.rank = rank
        self.min_interval_s = min_interval_s
        self._extra_fn = extra_fn
        self._last_ts = 0.0
        self.last: dict | None = None
        self._compile_watch = _CompileWatch.install()

    # ------------------------------------------------------------------

    def maybe_sample(self, now: float | None = None) -> dict | None:
        now = time.time() if now is None else now
        if now - self._last_ts < self.min_interval_s:
            return None
        return self.sample(now)

    def sample(self, now: float | None = None) -> dict:
        now = time.time() if now is None else now
        self._last_ts = now
        snap: dict = {"ts": round(now, 3)}
        reg = obs_metrics.registry()
        try:
            import jax

            hbm = []
            for d in jax.local_devices():
                mem = device_memory(d)
                if mem is not None:
                    hbm.append({"id": d.id, **mem})
                    for k in ("in_use", "peak"):
                        if mem[k] is not None:
                            reg.gauge(f"nbd_hbm_{k}_bytes",
                                      f"device HBM {k} bytes",
                                      {"device": str(d.id)}).set(mem[k])
            if hbm:
                snap["hbm"] = hbm
            try:
                n_live = len(jax.live_arrays())
                snap["bufs"] = n_live
                reg.gauge("nbd_live_buffers",
                          "live jax.Array count").set(n_live)
            except Exception:
                pass
        except Exception:
            pass
        if self._compile_watch:
            n, secs = _CompileWatch.snapshot()
            snap["compiles"] = n
            snap["compile_s"] = secs
            snap["cw"] = _CompileWatch.split()
            reg.gauge("nbd_backend_compiles",
                      "XLA backend compiles observed").set(n)
        if self._extra_fn is not None:
            try:
                snap.update(self._extra_fn() or {})
            except Exception:
                pass
        self.last = snap
        return snap


def hbm_totals(snapshot: dict | None) -> dict | None:
    """Sum a snapshot's per-device HBM numbers into one
    ``{"in_use", "peak", "limit", "devices"}`` (bytes) — the per-rank
    figure ``%dist_top`` and the postmortem report show.  A worker may
    own several chips (one process per host on pods); showing only
    device 0 would hide an OOM on any other device.  None when the
    snapshot carries no memory stats (CPU backends)."""
    hbm = (snapshot or {}).get("hbm") or []
    if not hbm:
        return None
    out = {"devices": len(hbm)}
    for key in ("in_use", "peak", "limit"):
        vals = [d.get(key) for d in hbm if d.get(key) is not None]
        out[key] = sum(vals) if vals else None
    return out


def peak_hbm(snapshots) -> dict:
    """Summarize a sequence of snapshots into per-device peak HBM
    bytes."""
    peaks: dict[str, int] = {}
    for snap in snapshots:
        for dev in (snap or {}).get("hbm", ()):
            for key in ("peak", "in_use"):
                v = dev.get(key)
                if v is not None:
                    did = str(dev.get("id"))
                    peaks[did] = max(peaks.get(did, 0), v)
                    break
    return peaks
