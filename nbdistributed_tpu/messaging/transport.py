"""TCP transport for the control plane.

The reference uses ZMQ ROUTER (coordinator) / DEALER (worker) sockets with
identity strings ``worker_{rank}`` (reference: communication.py:124-125,
worker.py:154-157).  This module provides the same topology on plain
sockets: a :class:`CoordinatorListener` accepts one connection per worker
and routes frames by the rank announced in an initial HELLO frame, and a
:class:`WorkerChannel` is the worker-side dial-out.

Differences from the reference, by design:

* **Explicit readiness**: the HELLO handshake makes worker attachment an
  observable event, replacing the reference's ``sleep(2)`` + ZMQ late-join
  buffering (reference: process_manager.py:136-150, SURVEY §7 "hard parts").
* **Single poller, no busy loop**: the coordinator reader thread blocks in
  ``selector.select()`` instead of polling every 100 ms
  (reference: communication.py:170), so round-trip latency is wire-bound.
* **Disconnect notifications**: worker socket death is surfaced via
  ``on_disconnect`` so pending requests can fail fast instead of hanging
  forever in no-timeout mode (reference: communication.py:263-269).

A C++ fast-path transport with the same interface can be slotted in via
:mod:`nbdistributed_tpu.messaging.native` when built (see native/).
"""

from __future__ import annotations

import selectors
import socket
import struct
import threading
from typing import Callable

from .codec import (CodecError, Message, decode, encode, frame_ready,
                    wire_hook)

# Connection preamble: worker announces its rank in a fixed header
# before any frames — the identity handshake ZMQ did with socket
# identities (reference: worker.py:154-157), kept trivially parseable
# so the native C++ listener and this Python listener speak one
# protocol.  Two variants:
#   "NBDW" + i32 rank                      (8 bytes, loopback worlds)
#   "NBDA" + i32 rank + sha256(token)      (40 bytes, authenticated:
#                                           non-loopback/multihost)
# The digest form keeps the preamble fixed-size for any token length
# and never puts the secret itself on the wire.
PREAMBLE_MAGIC = b"NBDW"
AUTH_PREAMBLE_MAGIC = b"NBDA"
PREAMBLE_SIZE = 8
AUTH_PREAMBLE_SIZE = 40


def token_digest(auth_token: str) -> bytes:
    import hashlib

    return hashlib.sha256(auth_token.encode("utf-8",
                                            "surrogatepass")).digest()


def make_preamble(rank: int, auth_token: str | None = None) -> bytes:
    if auth_token is None:
        return PREAMBLE_MAGIC + struct.pack("<i", rank)
    return (AUTH_PREAMBLE_MAGIC + struct.pack("<i", rank)
            + token_digest(auth_token))


class TransportError(Exception):
    pass


# Documented exemptions for the blocking-call-under-lock self-lint
# (analysis/concur.py).  The write locks below exist PRECISELY to
# serialize whole-frame socket writes from concurrent sender threads
# (coordinator caller threads; worker stdout-streamer + heartbeat) —
# they guard no other state, are never nested inside another lock,
# and a frame interleaved mid-write would tear the stream for good.
_LINT_BLOCKING_OK = {
    "_ConnState.send_frame:send":
        "wlock is the per-connection frame-write serializer; holding "
        "it across the (possibly partial) non-blocking send IS its "
        "one job",
    "WorkerChannel.__init__:sendall":
        "the HELLO preamble must hit the wire before any frame; the "
        "channel is not yet shared when __init__ runs",
    "WorkerChannel._send_frame:sendall":
        "_wlock is the worker-side frame-write serializer (streamer "
        "and heartbeat threads send concurrently); it guards nothing "
        "else",
}


def _set_keepalive(sock: socket.socket) -> None:
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)


class _ConnState:
    """Per-connection incremental read buffer + locked writer.

    ``auth_digest``: when set, only the "NBDA" preamble carrying this
    sha256(token) digest identifies the connection — anything else is a
    CodecError and the listener drops the peer before any frame is
    decoded (so an unauthenticated peer can never reach the codec,
    least of all its pickle path).
    """

    def __init__(self, sock: socket.socket,
                 auth_digest: bytes | None = None):
        self.sock = sock
        self.rbuf = bytearray()
        self.wlock = threading.Lock()
        self.rank: int | None = None  # set after the (validated) preamble
        self.registered = False
        self.auth_digest = auth_digest

    def send_frame(self, frame: bytes) -> None:
        """Write the whole frame even on a non-blocking socket.

        Coordinator-side sockets are non-blocking (the IO thread selects
        on them for reads), so a plain ``sendall`` of a frame larger than
        the kernel buffer would raise mid-write and tear the stream.
        Writes happen on caller threads, so blocking in ``select`` for
        writability here is safe.
        """
        import select as _select

        view = memoryview(frame)
        with self.wlock:
            while view:
                try:
                    n = self.sock.send(view)
                except (BlockingIOError, InterruptedError):
                    _select.select([], [self.sock], [], 1.0)
                    continue
                view = view[n:]

    def feed(self, data: bytes) -> list[bytes]:
        """Append received bytes; return complete frames.  Consumes the
        connection preamble first (setting ``self.rank``), enforcing
        the auth digest when this listener requires one."""
        self.rbuf.extend(data)
        if self.rank is None:
            if len(self.rbuf) < 4:
                return []
            magic = bytes(self.rbuf[:4])
            if magic == AUTH_PREAMBLE_MAGIC:
                need = AUTH_PREAMBLE_SIZE
            elif magic == PREAMBLE_MAGIC:
                need = PREAMBLE_SIZE
            else:
                raise CodecError(f"bad preamble {magic!r}")
            if len(self.rbuf) < need:
                return []
            if self.auth_digest is not None:
                import hmac
                if magic != AUTH_PREAMBLE_MAGIC or not hmac.compare_digest(
                        bytes(self.rbuf[8:AUTH_PREAMBLE_SIZE]),
                        self.auth_digest):
                    raise CodecError("auth digest mismatch")
            self.rank = struct.unpack_from("<i", self.rbuf, 4)[0]
            del self.rbuf[:need]
        frames: list[bytes] = []
        while True:
            n = frame_ready(self.rbuf)
            if not n:
                return frames
            frames.append(bytes(self.rbuf[:n]))
            del self.rbuf[:n]


class CoordinatorListener:
    """Accepts worker connections and routes frames by rank.

    ZMQ-ROUTER analog (reference: communication.py:95-135) with explicit
    connection tracking.  All callbacks run on the single reader thread;
    they must not block.
    """

    transport = "python"

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 allow_pickle: bool = True, auth_token: str | None = None):
        self._allow_pickle = allow_pickle
        # Shared-secret handshake: when set, only the "NBDA" preamble
        # carrying sha256(token) identifies a connection — enforced in
        # _ConnState.feed before any frame exists, so an
        # unauthenticated peer can never reach the codec (least of all
        # its pickle path).  Required for non-loopback binds
        # (multihost): the control plane executes code.
        self._auth_digest = (token_digest(auth_token)
                             if auth_token is not None else None)
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind((host, port))
        self._server.listen(128)
        self.host, self.port = self._server.getsockname()
        self._sel = selectors.DefaultSelector()
        self._conns: dict[int, _ConnState] = {}  # rank -> conn
        self._lock = threading.Lock()
        self._running = False
        self._thread: threading.Thread | None = None
        self.on_message: Callable[[int, Message], None] = lambda r, m: None
        self.on_connect: Callable[[int], None] = lambda r: None
        self.on_disconnect: Callable[[int], None] = lambda r: None
        # Chaos hook (resilience/faults.py): when set, every outgoing
        # frame passes through the plan, which may drop/delay/
        # duplicate/truncate it deterministically.  None in production.
        self.fault_plan = None
        # Link-shaping topology (ISSUE 6): which host each rank lives
        # on, and this process's own host label — a fault plan with
        # per-link specs uses them to decide which frames cross a
        # partitioned / slow / lossy link.  Empty map = no link ever
        # matches (single-host worlds pay nothing).
        self.host_of_rank: dict[int, str] = {}
        self.local_host: str = "local"
        # wake-up pipe so close() interrupts select()
        self._wake_r, self._wake_w = socket.socketpair()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._running = True
        self._server.setblocking(False)
        self._sel.register(self._server, selectors.EVENT_READ, ("accept", None))
        self._sel.register(self._wake_r, selectors.EVENT_READ, ("wake", None))
        self._thread = threading.Thread(target=self._loop,
                                        name="nbd-coordinator-io", daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._running = False
        try:
            self._wake_w.send(b"x")
        except OSError:
            pass
        if self._thread:
            self._thread.join(timeout=2)
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for c in conns:
            try:
                c.sock.close()
            except OSError:
                pass
        for s in (self._server, self._wake_r, self._wake_w):
            try:
                s.close()
            except OSError:
                pass

    # -- sending -----------------------------------------------------------

    def connected_ranks(self) -> list[int]:
        with self._lock:
            return sorted(self._conns)

    def _transmit(self, conn: "_ConnState", frame: bytes,
                  kind: str) -> None:
        # tx accounting wraps the ACTUAL socket write: a fan-out send
        # counts once per rank, and a chaos plan's drops (0 writes) /
        # duplicates (2 writes) / truncations (shorter frame) are all
        # counted as what really hit the wire.
        def _tx(f: bytes) -> None:
            conn.send_frame(f)
            hook = wire_hook()
            if hook is not None:
                hook("tx", kind, len(f))

        plan = self.fault_plan
        if plan is not None:
            if plan.has_links():
                # Link shaping first (partition/loss/latency/bw for the
                # host pair this frame crosses), composing with the
                # per-frame faults inside link_transmit.
                dst = (self.host_of_rank.get(conn.rank)
                       if conn.rank is not None else None)
                plan.link_transmit(self.local_host, dst, frame, _tx,
                                   kind=kind)
            else:
                plan.transmit(frame, _tx, kind=kind)
        else:
            _tx(frame)

    def send_to_rank(self, rank: int, msg: Message) -> None:
        with self._lock:
            conn = self._conns.get(rank)
        if conn is None:
            raise TransportError(f"rank {rank} is not connected")
        self._transmit(conn, encode(msg, allow_pickle=self._allow_pickle),
                       msg.msg_type)

    def send_to_ranks(self, ranks: list[int], msg: Message) -> None:
        frame = encode(msg, allow_pickle=self._allow_pickle)
        missing = []
        with self._lock:
            conns = [(r, self._conns.get(r)) for r in ranks]
        for r, conn in conns:
            if conn is None:
                missing.append(r)
            else:
                self._transmit(conn, frame, msg.msg_type)
        if missing:
            raise TransportError(f"ranks {missing} are not connected")

    # -- reader loop -------------------------------------------------------

    def _loop(self) -> None:
        unidentified: dict[socket.socket, _ConnState] = {}
        while self._running:
            try:
                events = self._sel.select(timeout=1.0)
            except OSError:
                if not self._running:
                    return
                raise
            for key, _ in events:
                tag, conn = key.data
                if tag == "wake":
                    try:
                        self._wake_r.recv(4096)
                    except OSError:
                        pass
                elif tag == "accept":
                    try:
                        sock, _addr = self._server.accept()
                    except OSError:
                        continue
                    _set_keepalive(sock)
                    sock.setblocking(False)
                    st = _ConnState(sock, auth_digest=self._auth_digest)
                    unidentified[sock] = st
                    self._sel.register(sock, selectors.EVENT_READ, ("conn", st))
                else:
                    # One misbehaving connection must never kill the
                    # selector thread (that would deafen the whole
                    # control plane): any unexpected error drops just
                    # that connection.
                    try:
                        self._service(conn, unidentified)
                    except Exception:
                        import traceback as _tb
                        _tb.print_exc()
                        self._drop(conn, unidentified)

    def _service(self, conn: _ConnState, unidentified: dict) -> None:
        try:
            data = conn.sock.recv(1 << 20)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            data = b""
        if not data:
            self._drop(conn, unidentified)
            return
        try:
            frames = conn.feed(data)  # enforces the auth preamble
        except CodecError:
            self._drop(conn, unidentified)
            return
        if conn.rank is not None and not conn.registered:
            self._register(conn, unidentified)
        if not conn.registered:
            return
        for frame in frames:
            try:
                msg = decode(frame, allow_pickle=self._allow_pickle)
            except CodecError:
                continue
            # A handler bug on ONE message must neither kill the
            # selector thread nor cost the rank its (healthy)
            # connection — log and move to the next frame.
            try:
                self.on_message(conn.rank, msg)
            except Exception:
                import traceback as _tb
                _tb.print_exc()

    def _register(self, conn: "_ConnState", unidentified: dict) -> None:
        conn.registered = True
        unidentified.pop(conn.sock, None)
        with self._lock:
            old = self._conns.get(conn.rank)
            self._conns[conn.rank] = conn
        if old is not None:
            # Replaced by a reconnect: detach the stale socket from
            # the selector too, and mark it non-current so a late
            # EOF on it does not fire on_disconnect for a live rank.
            old.rank = None
            try:
                self._sel.unregister(old.sock)
            except (KeyError, ValueError):
                pass
            try:
                old.sock.close()
            except OSError:
                pass
        self.on_connect(conn.rank)

    def _drop(self, conn: _ConnState, unidentified: dict) -> None:
        try:
            self._sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        try:
            conn.sock.close()
        except OSError:
            pass
        unidentified.pop(conn.sock, None)
        if conn.rank is not None:
            with self._lock:
                is_current = self._conns.get(conn.rank) is conn
                if is_current:
                    del self._conns[conn.rank]
            # Only report disconnect for the rank's *current* connection —
            # a late EOF on a connection already replaced by a reconnect
            # must not mark the live worker dead.
            if is_current:
                self.on_disconnect(conn.rank)


class WorkerChannel:
    """Worker-side control-plane connection (ZMQ-DEALER analog,
    reference: worker.py:154-157).

    ``recv()`` is blocking and intended for the worker's serial message
    loop (reference: worker.py:200-246); ``send()`` is thread-safe so the
    stdout streamer and heartbeat thread can push concurrently
    (reference: worker.py:43 uses a lock for the same reason).
    """

    def __init__(self, host: str, port: int, rank: int, *,
                 allow_pickle: bool = True, connect_timeout: float = 30.0,
                 auth_token: str | None = None):
        self.rank = rank
        self._allow_pickle = allow_pickle
        self._sock = socket.create_connection((host, port),
                                              timeout=connect_timeout)
        self._sock.settimeout(None)
        _set_keepalive(self._sock)
        self._wlock = threading.Lock()
        self._rbuf = bytearray()
        # Chaos hook (resilience/faults.py), mirroring the listener's:
        # outgoing frames (replies, stream output, pings) pass through
        # the plan when set.  The HELLO preamble below deliberately
        # bypasses it — an unattached worker is a bring-up problem, not
        # a chaos scenario.
        self.fault_plan = None
        # Link-shaping labels (ISSUE 6): which host this process lives
        # on and which host the coordinator lives on.  When a fault
        # plan declares the pair partitioned, send() SEVERS the
        # connection and raises — emulating the keepalive teardown a
        # real blackholed link ends in — so the worker's orphan
        # machinery engages exactly as it would on real hardware.
        self.local_host: str | None = None
        self.peer_host: str | None = None
        with self._wlock:
            # The authenticated preamble variant when the coordinator
            # requires the shared secret (non-loopback binds).
            self._sock.sendall(make_preamble(rank, auth_token))

    def _send_frame(self, frame: bytes) -> None:
        with self._wlock:
            self._sock.sendall(frame)

    def send(self, msg: Message) -> None:
        frame = encode(msg, allow_pickle=self._allow_pickle)

        def _tx(f: bytes) -> None:
            # Count actual writes (see CoordinatorListener._transmit).
            self._send_frame(f)
            hook = wire_hook()
            if hook is not None:
                hook("tx", msg.msg_type, len(f))

        plan = self.fault_plan
        if plan is not None:
            if plan.has_links() and self.local_host:
                if plan.link_blocked(self.local_host, self.peer_host):
                    # Injected partition: tear the stream the way TCP
                    # keepalive would on a real blackholed link, then
                    # surface it — the recv side sees EOF and enters
                    # the orphan machinery.
                    try:
                        self._sock.close()
                    except OSError:
                        pass
                    raise TransportError(
                        "link partitioned (injected fault)")
                plan.link_transmit(self.local_host, self.peer_host,
                                   frame, _tx, kind=msg.msg_type)
            else:
                plan.transmit(frame, _tx, kind=msg.msg_type)
        else:
            _tx(frame)

    def recv(self, timeout: float | None = None, *,
             gate=None) -> Message:
        """Block until one complete frame arrives; raise TransportError on
        EOF (coordinator gone), TimeoutError on timeout.

        The timeout is implemented with ``select`` rather than
        ``settimeout`` so the socket object's blocking mode is never
        mutated — concurrent ``send()`` from the stdout-streamer or
        heartbeat thread must not inherit a read deadline mid-write.

        ``gate`` (worker main-thread loop): an
        :class:`~nbdistributed_tpu.runtime.interrupt.InterruptGate`
        scoping SIGINT to the ``select`` wait, where no byte has been
        consumed — received bytes always reach ``_rbuf`` (partial
        frames persist across calls), so an interrupt can never desync
        the stream.  A KI between ``sock.recv`` returning and the
        buffer append would otherwise silently drop those bytes: the
        next frame parse then reads garbage, the worker tears the
        connection down, and the coordinator declares a perfectly alive
        worker dead.  Outside the gate's window the handler records the
        signal as pending (PEP 475 then restarts the interrupted
        syscall), so byte consumption is atomic with respect to
        interrupts no matter which OS thread received the signal.
        """
        import select as _select
        import time as _time

        use_gate = gate is not None and gate.main_thread()
        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            n = frame_ready(self._rbuf)
            if n:
                frame = bytes(self._rbuf[:n])
                del self._rbuf[:n]
                return decode(frame, allow_pickle=self._allow_pickle)
            if deadline is not None:
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("recv timed out")
            else:
                remaining = None
            try:
                if use_gate:
                    # KI may propagate from this block (pending
                    # delivered at window entry, or SIGINT during the
                    # wait) — nothing has been consumed yet, so the
                    # stream stays in sync.
                    with gate.window():
                        readable, _, _ = _select.select([self._sock], [],
                                                        [], remaining)
                elif deadline is not None:
                    readable, _, _ = _select.select([self._sock], [], [],
                                                    remaining)
                else:
                    readable = [self._sock]
                if not readable:
                    raise TimeoutError("recv timed out")
                data = self._sock.recv(1 << 20)
            except TimeoutError:
                raise  # a timeout is not a dead socket (OSError subclass!)
            except (OSError, ValueError) as e:
                # The socket died under us — a peer reset, or our own
                # send path severed it (injected link partition).  Both
                # mean "coordinator unreachable": surface the one error
                # the worker loop's orphan machinery handles.
                raise TransportError(
                    f"connection lost: {type(e).__name__}: {e}") from e
            if not data:
                raise TransportError("coordinator closed connection")
            self._rbuf.extend(data)

    def close(self) -> None:
        # shutdown() before close(): closing an fd does NOT wake a
        # thread blocked in an untimed recv() on it (the classic
        # close-vs-blocked-reader race — the TenantClient reader
        # would hang past its close() join without this); SHUT_RDWR
        # delivers EOF to the blocked recv immediately.
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
