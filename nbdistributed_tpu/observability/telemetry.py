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
     ...extra_fn() fields (dedup hits, msgs seen, ...)}

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
    """Counts XLA backend compiles via ``jax.monitoring`` duration
    events — the only compile signal that fires *inside* the blocking
    compile path, which is exactly when the serial loop can't answer a
    status probe.  Process-global (listeners cannot be unregistered);
    instances read deltas off the shared counters."""

    _lock = threading.Lock()
    _installed = False
    count = 0
    seconds = 0.0

    @classmethod
    def install(cls) -> bool:
        with cls._lock:
            if cls._installed:
                return True
            try:
                import jax.monitoring as jmon

                def _on_duration(name: str, secs: float, **kw) -> None:
                    if name.endswith("backend_compile_duration"):
                        with cls._lock:
                            cls.count += 1
                            cls.seconds += secs

                jmon.register_event_duration_secs_listener(_on_duration)
            except Exception:
                return False
            cls._installed = True
            return True

    @classmethod
    def snapshot(cls) -> tuple[int, float]:
        with cls._lock:
            return cls.count, round(cls.seconds, 3)


def compile_snapshot() -> tuple[int, float]:
    """``(backend compiles, their seconds)`` so far in this process
    (installs the listener on first use): the serve_step handler
    takes the delta across a tick, so a tick that compiled, or loaded
    a program from the persistent cache, says so."""
    _CompileWatch.install()
    return _CompileWatch.snapshot()


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
    """Summarize a sequence of snapshots into per-device peak HBM bytes
    (the ``bench.py`` trajectory summary)."""
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
