"""ctypes bindings for the native (C++) control-plane listener.

Loads ``native/libnbdtransport.so`` and wraps it in
:class:`NativeCoordinatorListener`, interface-compatible with the
pure-Python :class:`~nbdistributed_tpu.messaging.transport.
CoordinatorListener`.  The ``.so`` is a build product, never
committed: it is built from ``native/nbd_transport.cpp`` by
``native/build.sh`` on first use, and again whenever the source is
newer than the library, so what runs is what the checkout holds.
Selection:

* ``NBD_NATIVE=0`` forces pure Python;
* ``NBD_NATIVE=1`` requires the native lib (raises if it cannot be
  built or loaded);
* unset: native if it builds and loads, else Python — and a build that
  was attempted and failed says so on stderr, once.

Every listener carries ``transport`` (``"native"`` / ``"python"``) so
the fleet banner and the gateway manifest can state which one is live.

The C side owns sockets, epoll, framing, and identity routing; a single
Python dispatch thread pops whole events (connect / disconnect /
complete frames) and runs the same callbacks the Python listener does —
no C→Python reentrancy, and the GIL is released for the duration of
every native call.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

from ..utils import knobs
from .codec import CodecError, decode, encode

_LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native",
    "libnbdtransport.so")

_EVENT_MESSAGE, _EVENT_CONNECT, _EVENT_DISCONNECT = 0, 1, 2

_lib = None
_build_attempted = False  # one build attempt (and one report) per process


def _stale() -> bool:
    """True when the library is absent or older than its source."""
    src = os.path.join(os.path.dirname(_LIB_PATH), "nbd_transport.cpp")
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(src)
    except OSError:
        return not os.path.exists(_LIB_PATH)


def _build_library() -> None:
    """Compile the native listener (build.sh is a one-file g++
    invocation, so building lazily keeps `pip install -e . && pytest`
    working without a separate build step).  A failed build is
    reported, not swallowed: the caller falls back to the Python
    transport knowingly."""
    script = os.path.join(os.path.dirname(_LIB_PATH), "build.sh")
    if not os.path.exists(script):
        return
    try:
        subprocess.run(["sh", script], check=True, capture_output=True,
                       timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        detail = (getattr(e, "stderr", None) or b"").decode(
            "utf-8", "replace").strip()[-400:]
        print(f"[nbd] native transport build failed ({e})"
              + (f":\n{detail}" if detail else ""), file=sys.stderr)


def load_library():
    global _lib, _build_attempted
    if _lib is not None:
        return _lib
    if _stale() and not _build_attempted:
        _build_attempted = True
        _build_library()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.nbd_listener_create.restype = ctypes.c_void_p
    lib.nbd_listener_create.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                        ctypes.POINTER(ctypes.c_int)]
    try:
        lib.nbd_listener_create_auth.restype = ctypes.c_void_p
        lib.nbd_listener_create_auth.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int)]
    except AttributeError:
        pass  # stale pre-auth .so; make_listener falls back for auth
    lib.nbd_listener_poll.restype = ctypes.c_int
    lib.nbd_listener_poll.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64)]
    lib.nbd_listener_send.restype = ctypes.c_int
    lib.nbd_listener_send.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                      ctypes.c_char_p, ctypes.c_uint64]
    lib.nbd_listener_ranks.restype = ctypes.c_int
    lib.nbd_listener_ranks.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_int32),
                                       ctypes.c_int]
    lib.nbd_listener_close.restype = None
    lib.nbd_listener_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def available() -> bool:
    if knobs.get_str("NBD_NATIVE") == "0":
        return False
    try:
        load_library()
        return True
    except OSError:
        if knobs.get_str("NBD_NATIVE") == "1":
            raise
        return False


class NativeCoordinatorListener:
    """Drop-in replacement for the Python CoordinatorListener backed by
    the C++ epoll listener."""

    transport = "native"

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 allow_pickle: bool = True, auth_token: str | None = None):
        self._allow_pickle = allow_pickle
        self._lib = load_library()
        out_port = ctypes.c_int(0)
        if auth_token is not None:
            if not hasattr(self._lib, "nbd_listener_create_auth"):
                raise OSError(
                    "native listener library predates the "
                    "authenticated preamble; rebuild with "
                    "native/build.sh")
            from .transport import token_digest
            self._handle = self._lib.nbd_listener_create_auth(
                host.encode(), port, token_digest(auth_token),
                ctypes.byref(out_port))
        else:
            self._handle = self._lib.nbd_listener_create(
                host.encode(), port, ctypes.byref(out_port))
        if not self._handle:
            raise OSError(f"native listener failed to bind {host}:{port}")
        self.host, self.port = host, out_port.value
        self._running = False
        self._thread: threading.Thread | None = None
        self.on_message = lambda r, m: None
        self.on_connect = lambda r: None
        self.on_disconnect = lambda r: None
        # Chaos hook (resilience/faults.py) — applied in this Python
        # wrapper so fault injection behaves identically over the C++
        # and pure-Python transports.  host_of_rank/local_host feed the
        # per-link shaping exactly like the Python listener's.
        self.fault_plan = None
        self.host_of_rank: dict[int, str] = {}
        self.local_host: str = "local"

    def start(self) -> None:
        self._running = True
        self._thread = threading.Thread(target=self._dispatch,
                                        name="nbd-native-dispatch",
                                        daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._running = False
        if self._thread is not None:
            self._thread.join(timeout=2)
        handle, self._handle = self._handle, None
        if handle:
            self._lib.nbd_listener_close(handle)

    def connected_ranks(self) -> list[int]:
        if not self._handle:
            return []
        buf = (ctypes.c_int32 * 4096)()
        n = self._lib.nbd_listener_ranks(self._handle, buf, 4096)
        return sorted(buf[i] for i in range(n))

    def send_to_rank(self, rank: int, msg) -> None:
        frame = encode(msg, allow_pickle=self._allow_pickle)
        self._send_frame(rank, frame, msg.msg_type)

    def send_to_ranks(self, ranks: list[int], msg) -> None:
        from .transport import TransportError
        frame = encode(msg, allow_pickle=self._allow_pickle)
        missing = [r for r in ranks
                   if self._transmit(r, frame, msg.msg_type) != 0]
        if missing:
            raise TransportError(f"ranks {missing} are not connected")

    def _send_frame(self, rank: int, frame: bytes, kind: str) -> None:
        from .transport import TransportError
        if self._transmit(rank, frame, kind) != 0:
            raise TransportError(f"rank {rank} is not connected")

    def _transmit(self, rank: int, frame: bytes, kind: str) -> int:
        plan = self.fault_plan
        if plan is None:
            return self._send_accounted(rank, frame, kind)
        rcs: list[int] = []
        if plan.has_links():
            plan.link_transmit(
                self.local_host, self.host_of_rank.get(rank), frame,
                lambda f: rcs.append(self._send_accounted(rank, f, kind)),
                kind=kind)
            return rcs[-1] if rcs else 0
        plan.transmit(
            frame,
            lambda f: rcs.append(self._send_accounted(rank, f, kind)),
            kind=kind)
        # A dropped frame never touched the socket: report success —
        # under chaos, loss is the point, and the retry layer owns
        # recovery.
        return rcs[-1] if rcs else 0

    def _send_accounted(self, rank: int, frame: bytes, kind: str) -> int:
        rc = self._try_send(rank, frame)
        if rc == 0:
            # tx accounting on the actual (successful) socket write,
            # mirroring the Python transport's per-rank counting.
            from .codec import wire_hook
            hook = wire_hook()
            if hook is not None:
                hook("tx", kind, len(frame))
        return rc

    def _try_send(self, rank: int, frame: bytes) -> int:
        if not self._handle:
            return -1
        return self._lib.nbd_listener_send(self._handle, rank, frame,
                                           len(frame))

    def _dispatch(self) -> None:
        etype = ctypes.c_int32()
        rank = ctypes.c_int32()
        data = ctypes.POINTER(ctypes.c_uint8)()
        size = ctypes.c_uint64()
        while self._running and self._handle:
            rc = self._lib.nbd_listener_poll(
                self._handle, 200, ctypes.byref(etype), ctypes.byref(rank),
                ctypes.byref(data), ctypes.byref(size))
            if rc < 0:
                return
            if rc == 0:
                continue
            try:
                if etype.value == _EVENT_CONNECT:
                    self.on_connect(rank.value)
                elif etype.value == _EVENT_DISCONNECT:
                    self.on_disconnect(rank.value)
                else:
                    frame = ctypes.string_at(data, size.value)
                    try:
                        msg = decode(frame,
                                     allow_pickle=self._allow_pickle)
                    except CodecError:
                        continue
                    self.on_message(rank.value, msg)
            except Exception:
                # Callbacks must not kill the dispatch thread, but a
                # swallowed bug here would surface only as a hang —
                # make it loud (the Python listener would crash its IO
                # thread loudly in the same situation).
                import traceback
                traceback.print_exc()


def make_listener(host: str = "127.0.0.1", port: int = 0, *,
                  allow_pickle: bool = True, auth_token: str | None = None):
    """Listener factory honoring NBD_NATIVE (see module docstring).

    Both listeners implement the shared-secret preamble.  An auth
    world on a stale .so (no create_auth export) falls back to Python
    with a loud warning — or raises under NBD_NATIVE=1, which promises
    the native listener — never by silently accepting unauthenticated
    peers.
    """
    if available():
        stale_for_auth = (auth_token is not None
                          and not hasattr(load_library(),
                                          "nbd_listener_create_auth"))
        if not stale_for_auth:
            return NativeCoordinatorListener(host, port,
                                             allow_pickle=allow_pickle,
                                             auth_token=auth_token)
        if knobs.get_str("NBD_NATIVE") == "1":
            raise OSError(
                "NBD_NATIVE=1 but libnbdtransport.so predates the "
                "authenticated preamble; rebuild with native/build.sh")
        print("[nbd] native listener predates the authenticated "
              "preamble; using the Python listener (rebuild with "
              "native/build.sh)", file=sys.stderr)
    from .transport import CoordinatorListener
    return CoordinatorListener(host, port, allow_pickle=allow_pickle,
                               auth_token=auth_token)
