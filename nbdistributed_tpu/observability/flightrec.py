"""Always-on, crash-surviving flight recorder (ISSUE 3 tentpole).

The span tracer (:mod:`~nbdistributed_tpu.observability.spans`) and the
metrics registry are *pull*-based and in-process: a worker that is
SIGKILLed mid-cell — exactly the scenario the chaos harness and the
supervisor exist for — takes its spans, counters, and last-known state
to the grave, and the operator gets a ``WorkerDied`` and nothing else.
This module is the black box that survives the crash: every process
(coordinator and each worker) appends small self-delimiting structured
event records to an **mmap-backed ring file** under a shared per-run
directory, so a *reader in another process* can recover the dead
process's last moments from the file alone.

Why this survives SIGKILL: writes go to a shared ``mmap`` of a regular
file, so the dirty pages live in the kernel page cache — the kernel
writes them back regardless of how the owning process died.  Only a
machine crash loses data, and that failure mode takes the coordinator
(and the need for a live postmortem) with it.

Ring format (all integers little-endian)::

    file header (64 bytes):
        magic     8s   b"NBDFRING"
        version   u16
        ringsize  u32  bytes in the ring region (follows the header)
        pid       u32  writer pid (diagnostic only)
        writeoff  u64  next write offset (hint; reader never trusts it)
        seq       u64  next record sequence   (hint, ditto)
    record (anywhere in the ring region):
        magic     4s   REC_MAGIC (binary, cannot appear in JSON text)
        len       u16  payload length
        crc       u32  crc32 over (seq || payload)
        seq       u64  monotonic per-writer sequence, from 0
        payload   len  UTF-8 JSON: {"t": type, "ts": unix_s, ...fields}

Recovery does not trust the header hints (a torn header is exactly as
likely as a torn record): the reader scans the whole ring region for
``REC_MAGIC``, accepts records whose CRC verifies, orders them by
``seq``, and flags a **torn tail** — a candidate whose header names the
next expected sequence but whose payload fails the CRC or runs off the
end of the file (a write cut mid-record by a kill or truncation).

The append path is the hot path (it runs on every control-plane
dispatch): one compact-JSON encode, one CRC, one ``memoryview`` splice
into the mmap under a lock — low single-digit microseconds against a
control-plane echo of hundreds (< 5 % is the acceptance bar; the
socket round-trip is ~100× slower).  Recording is
**on by default** (``NBD_FLIGHT=0`` is the escape hatch) and every
failure mode degrades to a silent no-op: a black box must never crash
the plane.

Env knobs:

- ``NBD_RUN_DIR`` — the shared per-run directory.  The first process to
  need it (normally the coordinator) creates one under the system temp
  dir and exports it, so spawned workers inherit the same directory.
- ``NBD_FLIGHT_RING_BYTES`` — ring region size (default 1 MiB).
- ``NBD_FLIGHT=0`` — disable recording (files are still not written).
"""

from __future__ import annotations

import json
import mmap
import os
import re
import struct
import tempfile
import threading
import time
import zlib

FILE_MAGIC = b"NBDFRING"
VERSION = 1
_FHDR = struct.Struct("<8sHxxIIxxxxQQ")       # 40 bytes used...
_FILE_HEADER_SIZE = 64
REC_MAGIC = b"\xf1\x1e\xc0\xde"               # binary: never valid UTF-8 JSON
_RHDR = struct.Struct("<4sHIQ")               # magic, len, crc, seq
REC_HEADER_SIZE = _RHDR.size                  # 18 bytes

DEFAULT_RING_BYTES = 1 << 20
MAX_PAYLOAD = 4096

# Hot-path JSON: json.dumps costs several microseconds per call even
# for tiny dicts; the flight payloads are flat dicts of short scalars,
# which a hand-rolled encoder emits ~7× faster.  Values that would need
# escaping (or aren't plain scalars) fall back to json.dumps — the
# output must stay valid JSON for the recovery-side json.loads.
_NEEDS_ESCAPE = re.compile(r'[\x00-\x1f"\\]').search


def _encode_payload(etype: str, ts: float, fields: dict) -> bytes:
    parts = [f'"t":"{etype}","ts":{ts!r}']
    for k, v in fields.items():
        tv = type(v)
        if tv is str and _NEEDS_ESCAPE(v) is None:
            parts.append(f'"{k}":"{v}"')
        elif tv is int or tv is float:
            parts.append(f'"{k}":{v!r}')
        elif tv is bool:
            parts.append(f'"{k}":{"true" if v else "false"}')
        elif v is None:
            parts.append(f'"{k}":null')
        else:
            parts.append(f'"{k}":'
                         + json.dumps(v, separators=(",", ":"),
                                      default=str))
    return ("{" + ",".join(parts) + "}").encode("utf-8")


def _enabled_by_env() -> bool:
    from ..utils import knobs
    return knobs.get_bool("NBD_FLIGHT", True)


def run_dir(create: bool = True) -> str:
    """The shared per-run directory.  Honors ``NBD_RUN_DIR``; otherwise
    mints one and EXPORTS it into this process's environment, so worker
    processes spawned later (their env is a copy of ours,
    ``manager/topology.py``) land their rings next to the
    coordinator's."""
    from ..utils import knobs
    d = knobs.get_str("NBD_RUN_DIR")
    if not d:
        d = os.path.join(tempfile.gettempdir(), "nbd_runs",
                         f"run-{int(time.time())}-{os.getpid()}")
        os.environ["NBD_RUN_DIR"] = d
    if create:
        os.makedirs(d, exist_ok=True)
    return d


class _NullRecorder:
    """Degraded-mode recorder: same surface, records nothing.  Used
    when recording is disabled or the ring file cannot be created."""

    path = None
    enabled = False

    def record(self, etype: str, **fields) -> None:
        pass

    def health(self) -> dict:
        return {"utilization": 0.0, "wraps": 0, "records": 0,
                "overwritten": 0, "truncated": 0, "dropped": 0}

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    def __len__(self) -> int:
        return 0


class FlightRecorder:
    """One mmap-backed ring writer.  Thread-safe; never raises from
    ``record`` (a failing black box must not take down the process)."""

    def __init__(self, path: str, ring_bytes: int = DEFAULT_RING_BYTES):
        self.path = path
        self.enabled = True
        self._lock = threading.Lock()
        ring_bytes = max(4 * (REC_HEADER_SIZE + MAX_PAYLOAD),
                         int(ring_bytes))
        self._ring_size = ring_bytes
        total = _FILE_HEADER_SIZE + ring_bytes
        fd = os.open(path, os.O_RDWR | os.O_CREAT, 0o644)
        try:
            os.ftruncate(fd, total)
            self._mm = mmap.mmap(fd, total)
        finally:
            os.close(fd)
        # A fresh file every open: one ring belongs to one process
        # lifetime (file names carry the pid, so a respawned rank never
        # clobbers its predecessor's ring).  The whole ring region is
        # zeroed, not just the header — reopening an existing path
        # (pid recycling under a long-lived run dir, or re-init in one
        # process) must not leave the previous generation's CRC-valid
        # records where recovery would merge them into this one's.
        self._pid = os.getpid() & 0xFFFFFFFF  # cached: getpid is a
        # real syscall on every call and shows up on the append path
        self._mm[:total] = b"\0" * total
        _FHDR.pack_into(self._mm, 0, FILE_MAGIC, VERSION, ring_bytes,
                        self._pid, 0, 0)
        self._off = 0
        self._seq = 0
        self.dropped = 0      # records whose encode/write failed
        # Ring-health counters (ISSUE 13 satellite): wraps, records
        # aged out by a wrap (the previous lap is progressively
        # overwritten once a new one starts — counted at the wrap, the
        # moment evidence loss begins), and oversize payloads whose
        # capped body recovery will skip as torn.
        self.wraps = 0
        self.overwritten = 0
        self.truncated = 0
        self._lap_start_seq = 0

    def __len__(self) -> int:
        return self._seq

    # ------------------------------------------------------------------

    def record(self, etype: str, **fields) -> None:
        """Append one event.  ``fields`` must be JSON-able (they come
        from our own instrumentation sites); anything else is dropped,
        never raised."""
        if not self.enabled:
            return
        try:
            payload = _encode_payload(etype, time.time(), fields)
        except Exception:
            self.dropped += 1
            return
        if len(payload) > MAX_PAYLOAD:
            payload = payload[:MAX_PAYLOAD]  # capped: recovery skips it
            self.truncated += 1
        try:
            with self._lock:
                self._append(payload)
        except Exception:
            self.dropped += 1

    def _append(self, payload: bytes) -> None:
        # Lock held.  Records never wrap across the ring seam: if the
        # tail can't hold this record whole, zero the remnant (so a
        # stale record header there can't masquerade as fresh) and
        # start over at offset 0.
        need = REC_HEADER_SIZE + len(payload)
        base = _FILE_HEADER_SIZE
        if self._off + need > self._ring_size:
            self._mm[base + self._off: base + self._ring_size] = \
                b"\0" * (self._ring_size - self._off)
            self._off = 0
            self.wraps += 1
            # The new lap will overwrite every record of the previous
            # one — count them lost NOW, so the health gauge trips
            # before a postmortem discovers the hole.
            self.overwritten += self._seq - self._lap_start_seq
            self._lap_start_seq = self._seq
        seq = self._seq
        crc = zlib.crc32(struct.pack("<Q", seq) + payload)
        pos = base + self._off
        self._mm[pos: pos + need] = \
            _RHDR.pack(REC_MAGIC, len(payload), crc, seq) + payload
        self._off += need
        self._seq = seq + 1
        # Invalidate any stale record that happens to start exactly at
        # the new head, so the reader's "next expected seq" tail check
        # stays meaningful.
        if self._off + 4 <= self._ring_size:
            head = base + self._off
            if self._mm[head: head + 4] == REC_MAGIC:
                self._mm[head: head + 4] = b"\0\0\0\0"
        # Header hints (diagnostics only — recovery rescans).
        _FHDR.pack_into(self._mm, 0, FILE_MAGIC, VERSION,
                        self._ring_size, self._pid,
                        self._off, self._seq)

    def health(self) -> dict:
        """Ring-health snapshot for the metrics satellite:
        ``utilization`` is the fraction of the ring written this lap
        (pinned to 1.0 once it has wrapped — from then on every append
        destroys history), plus the wrap / overwritten / truncated /
        dropped counters."""
        with self._lock:
            util = (1.0 if self.wraps
                    else round(self._off / self._ring_size, 4))
            return {"utilization": util, "wraps": self.wraps,
                    "records": self._seq,
                    "overwritten": self.overwritten,
                    "truncated": self.truncated,
                    "dropped": self.dropped}

    def flush(self) -> None:
        try:
            self._mm.flush()
        except Exception:
            pass

    def close(self) -> None:
        self.enabled = False
        try:
            self._mm.flush()
            self._mm.close()
        except Exception:
            pass


# ----------------------------------------------------------------------
# recovery (runs in the postmortem process, on any ring file)


def read_ring(path: str) -> dict:
    """Recover a ring file — typically one left behind by a SIGKILLed
    process.  Returns::

        {"path", "pid", "events": [...],     # complete, seq-ordered
         "torn_tail": bool,                  # final record cut mid-write
         "recovered": n, "overwritten": n,   # ring-capacity casualties
         "corrupt": n}

    Never trusts the writer's header hints: scans the whole ring region
    for record magic and accepts only CRC-verified records.
    """
    with open(path, "rb") as f:
        blob = f.read()
    pid = None
    if len(blob) >= _FHDR.size and blob[:8] == FILE_MAGIC:
        try:
            _m, _v, _rs, pid, _off, _seq = _FHDR.unpack_from(blob, 0)
        except struct.error:
            pid = None
    region = blob[_FILE_HEADER_SIZE:]
    found: dict[int, tuple[float, dict]] = {}
    partial: list[int] = []   # seqs of candidates that failed the CRC
    corrupt = 0
    pos = region.find(REC_MAGIC)
    while pos != -1:
        ok = False
        if pos + REC_HEADER_SIZE <= len(region):
            _magic, plen, crc, seq = _RHDR.unpack_from(region, pos)
            end = pos + REC_HEADER_SIZE + plen
            if plen <= MAX_PAYLOAD:
                payload = region[pos + REC_HEADER_SIZE: end]
                if (end <= len(region) and len(payload) == plen
                        and zlib.crc32(struct.pack("<Q", seq)
                                       + payload) == crc):
                    try:
                        ev = json.loads(payload)
                    except ValueError:
                        ev = None
                    if isinstance(ev, dict):
                        found.setdefault(seq, (ev.get("ts", 0.0), ev))
                        ok = True
                        pos = region.find(REC_MAGIC, end)
                        continue
                else:
                    # Plausible header, bad body: either the torn final
                    # record of a killed writer, or an old record half
                    # overwritten by the ring — the seq disambiguates.
                    partial.append(seq)
        if not ok:
            corrupt += 1
            pos = region.find(REC_MAGIC, pos + 1)
    events = [ev for _seq, (_ts, ev) in sorted(found.items())]
    max_seq = max(found) if found else -1
    torn = any(s == max_seq + 1 for s in partial)
    min_seq = min(found) if found else 0
    return {
        "path": path,
        "pid": pid,
        "events": events,
        "torn_tail": torn,
        "recovered": len(events),
        "overwritten": min_seq,
        "corrupt": corrupt,
    }


def ring_path(directory: str, proc: str, pid: int | None = None) -> str:
    return os.path.join(directory,
                        f"flight-{proc}.{pid or os.getpid()}.ring")


def find_rings(directory: str, proc: str | None = None) -> list[str]:
    """Ring files in ``directory`` (newest first), optionally filtered
    to one process name (``rank1``, ``coordinator``)."""
    prefix = f"flight-{proc}." if proc else "flight-"
    try:
        names = [n for n in os.listdir(directory)
                 if n.startswith(prefix) and n.endswith(".ring")]
    except OSError:
        return []
    paths = [os.path.join(directory, n) for n in names]
    paths.sort(key=lambda p: (os.path.getmtime(p), p), reverse=True)
    return paths


def read_latest(directory: str, proc: str) -> dict | None:
    """Recover the newest ring for ``proc``, or None."""
    for p in find_rings(directory, proc):
        try:
            return read_ring(p)
        except OSError:
            continue
    return None


# ----------------------------------------------------------------------
# process-global recorder

_LOCK = threading.Lock()
_RECORDER: FlightRecorder | _NullRecorder | None = None
_PROC_NAME = None


def init(proc: str, *, directory: str | None = None):
    """Open (or return) this process's recorder as ``proc``
    (``coordinator`` / ``rank{N}``).  Re-initializing under a new name
    opens a new ring — a process that becomes a different actor (tests)
    gets a fresh black box."""
    global _RECORDER, _PROC_NAME
    with _LOCK:
        if _RECORDER is not None and _PROC_NAME == proc:
            return _RECORDER
        if _RECORDER is not None:
            _RECORDER.close()
        _PROC_NAME = proc
        if not _enabled_by_env():
            _RECORDER = _NullRecorder()
            return _RECORDER
        try:
            d = directory or run_dir()
            from ..utils import knobs
            size = knobs.get_int("NBD_FLIGHT_RING_BYTES",
                                 DEFAULT_RING_BYTES)
            _RECORDER = FlightRecorder(ring_path(d, proc), size)
        except Exception:
            _RECORDER = _NullRecorder()
        return _RECORDER


def recorder():
    """The process recorder; a no-op recorder until :func:`init` names
    this process (so library code can record unconditionally)."""
    r = _RECORDER
    if r is None:
        return _NULL
    return r


def record(etype: str, **fields) -> None:
    """Module-level append on the process recorder (no-op before
    :func:`init`)."""
    r = _RECORDER
    if r is not None:
        r.record(etype, **fields)


def export_health(registry=None) -> dict:
    """Mirror the process recorder's ring health into gauges
    (``nbd_flight_*``) so silent evidence loss — a wrapped ring, a
    dropped or truncated record — is scrapeable before a postmortem
    needs the evidence.  Returns the health dict it exported.  Called
    from the worker's ``metrics`` handler, ``%dist_metrics``, and the
    scrape endpoint's collector (never the hot append path)."""
    from . import metrics as obs_metrics
    reg = registry or obs_metrics.registry()
    h = recorder().health()
    reg.gauge("nbd_flight_ring_utilization",
              "flight-recorder ring fill fraction this lap (1.0 = "
              "wrapped: appends now destroy history)"
              ).set(h["utilization"])
    reg.gauge("nbd_flight_ring_wraps",
              "flight-recorder ring wraps").set(h["wraps"])
    reg.gauge("nbd_flight_records",
              "flight-recorder records appended").set(h["records"])
    reg.gauge("nbd_flight_records_overwritten",
              "flight records aged out by ring wraps (no longer "
              "recoverable)").set(h["overwritten"])
    reg.gauge("nbd_flight_records_truncated",
              "flight records whose oversize payload was capped "
              "(recovery skips them as torn)").set(h["truncated"])
    reg.gauge("nbd_flight_records_dropped",
              "flight records lost to encode/write failures"
              ).set(h["dropped"])
    return h


def reset_for_tests() -> None:
    global _RECORDER, _PROC_NAME
    with _LOCK:
        if _RECORDER is not None:
            _RECORDER.close()
        _RECORDER = None
        _PROC_NAME = None


_NULL = _NullRecorder()
