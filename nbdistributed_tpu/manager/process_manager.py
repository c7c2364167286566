"""Worker process lifecycle: spawn, monitor, tiered kill.

Rebuild of the reference's ``ProcessManager`` (reference:
process_manager.py:23-374) with the startup race fixed: instead of
``sleep(2)`` + hope (reference: process_manager.py:136-137), readiness is
the worker's control-plane HELLO, observed via
``CommunicationManager.wait_for_workers`` while this module concurrently
watches for early child death and surfaces captured stdio on failure
(reference collects stdio the same way: process_manager.py:138-150).

A monitor thread reports any child death to the communication manager so
pending requests fail fast instead of hanging (SURVEY §5.3 notes the
reference hangs forever on a dead worker in no-timeout mode).
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Callable

from . import topology


def wait_until_ready(comm, pm, timeout_s: float, *, poll_s: float = 2.0,
                     on_wait=None) -> None:
    """Block until every worker has attached to the control plane.

    Converts an early worker death into a diagnostic RuntimeError (with
    the dead child's stdio) instead of a timeout; raises TimeoutError
    at the deadline.  ``on_wait()`` runs after each poll interval
    (progress display).  The one bring-up loop shared by the magic
    layer, selftest, and the integration tests.
    """
    t0 = time.time()
    deadline = t0 + timeout_s
    while True:
        try:
            comm.wait_for_workers(timeout=poll_s)
            # The spawner's stamps for the merged timeline
            # (``comm.bringup()``): each rank's Popen, this wait.
            comm.note_bringup(pm.spawned_at, (t0, time.time()))
            return
        except TimeoutError:
            pm.check_startup_failure()
            if time.time() > deadline:
                # Re-raise with the *elapsed/budget* picture — the
                # inner error only knows the last poll interval, which
                # once produced "did not attach within 2s" after a
                # 240 s wait — plus each missing rank's exit status
                # and captured stdio, so an attach timeout is
                # diagnosable in one read instead of a separate
                # %dist_logs round.
                missing = sorted(set(range(comm.num_workers))
                                 - set(comm.connected_ranks()))
                diag = ""
                diag_fn = getattr(pm, "startup_diagnostics", None)
                if diag_fn is not None:
                    try:
                        diag = diag_fn(missing)
                    except Exception:
                        diag = ""  # diagnostics must not mask the error
                raise TimeoutError(
                    f"workers {missing} did not attach to the control "
                    f"plane within {time.time() - t0:.0f}s (budget "
                    f"{timeout_s:.0f}s)"
                    + (f"\n{diag}" if diag else "")) from None
            if on_wait is not None:
                on_wait()


def find_free_ports(n: int) -> list[int]:
    """``n`` distinct free ports by bind-to-zero discovery (reference:
    process_manager.py:154-175).  All sockets are held until every port
    is known, so one call can never hand out the same port twice."""
    socks = [socket.socket(socket.AF_INET, socket.SOCK_STREAM)
             for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def find_free_port() -> int:
    """One free port (see :func:`find_free_ports`)."""
    return find_free_ports(1)[0]


class _ChildIO:
    """Drains a child's merged stdout/stderr into a bounded ring buffer so
    early-death diagnostics are available without risking pipe stalls."""

    def __init__(self, proc: subprocess.Popen, rank: int):
        self.lines: deque[str] = deque(maxlen=400)
        self._thread = threading.Thread(
            target=self._drain, args=(proc,),
            name=f"nbd-worker-{rank}-io", daemon=True)
        self._thread.start()

    def _drain(self, proc: subprocess.Popen) -> None:
        try:
            for line in proc.stdout:  # type: ignore[union-attr]
                self.lines.append(line.decode("utf-8", "replace")
                                  if isinstance(line, bytes) else line)
        except ValueError:
            pass  # stream closed during shutdown

    def tail(self, n: int = 40) -> str:
        return "".join(list(self.lines)[-n:])


class _AdoptedProcess:
    """Popen-compatible shim over an externally-discovered pid the
    reattach path adopts (durable sessions: the workers outlived the
    coordinator that spawned them, so they are NOT our children and
    ``Popen.wait``/``poll`` semantics don't exist).  Death-watch is a
    signal-0 probe; the exit code of a non-child is unknowable, so a
    vanished pid reports returncode -1."""

    def __init__(self, pid: int):
        self.pid = int(pid)
        self.stdout = None  # stdio belongs to the dead coordinator
        self.returncode: int | None = None

    def poll(self) -> int | None:
        if self.returncode is not None:
            return self.returncode
        try:
            os.kill(self.pid, 0)
        except ProcessLookupError:
            self.returncode = -1
            return self.returncode
        except PermissionError:
            return None  # alive under another uid
        except OSError:
            self.returncode = -1
            return self.returncode
        return None

    def wait(self, timeout: float | None = None) -> int:
        deadline = None if timeout is None else time.time() + timeout
        while self.poll() is None:
            if deadline is not None and time.time() > deadline:
                raise subprocess.TimeoutExpired(f"pid {self.pid}",
                                                timeout)
            time.sleep(0.05)
        return self.returncode  # type: ignore[return-value]

    def send_signal(self, sig: int) -> None:
        os.kill(self.pid, sig)


class _AdoptedIO:
    """Stdio placeholder for adopted workers — their pipes died with
    the previous coordinator; ``%dist_logs`` should say so instead of
    rendering an empty tail as 'no output'."""

    def __init__(self, pid: int):
        self._pid = pid

    def tail(self, n: int = 40) -> str:
        return (f"(adopted worker pid {self._pid}: stdio was captured "
                "by the previous coordinator and is not available)\n")


class ProcessManager:
    def __init__(self):
        self.processes: dict[int, subprocess.Popen] = {}
        self.io: dict[int, _ChildIO] = {}
        self.backend: str | None = None
        self.world_size = 0
        self.dist_port: int | None = None
        # rank -> host label ("local" for direct children).  Feeds the
        # per-link fault shaping, the partition sentry's failure
        # domains, and per-host status/doctor grouping (ISSUE 6).
        self.hosts: dict[int, str] = {}
        # host label -> AgentClient for agent-launched hosts.
        self._agents: dict = {}
        # rank -> time.time() just before its Popen (or its agent's
        # spawn request): where the bring-up's timeline starts.
        self.spawned_at: dict[int, float] = {}
        self._monitor_thread: threading.Thread | None = None
        self._monitor_stop = threading.Event()
        self._death_callbacks: list[Callable[[int, int | None], None]] = []
        self._reported_dead: set[int] = set()

    # ------------------------------------------------------------------

    def add_death_callback(self, cb: Callable[[int, int | None], None]) -> None:
        """cb(rank, returncode) — invoked once per dead worker by the
        monitor thread."""
        self._death_callbacks.append(cb)

    def remove_death_callback(self, cb: Callable[[int, int | None], None]) \
            -> None:
        """Detach a callback registered above (no-op if absent) — a
        stopped supervisor must not keep receiving death reports."""
        try:
            self._death_callbacks.remove(cb)
        except ValueError:
            pass

    def start_workers(self, num_workers: int, control_port: int, *,
                      backend: str = "auto", coordinator_host: str = "127.0.0.1",
                      chips_per_worker: int = 1,
                      chips: list[int] | None = None,
                      extra_env: dict | None = None) -> None:
        """Spawn ``num_workers`` worker processes on this host.

        ``chips`` pins the workers to an explicit chip set — the
        reference's ``gpu_ids`` analog (reference:
        process_manager.py:107-112); TPU backend only.  Non-contiguous
        ids are fine for single-chip workers; with
        ``chips_per_worker > 1`` each worker's slice must be an
        aligned physical subgrid block (validated pre-spawn).

        The caller (magic layer) pairs this with
        ``CommunicationManager.wait_for_workers``; use
        :meth:`check_startup_failure` inside that wait loop to convert an
        early child death into a diagnostic error instead of a timeout.
        """
        if self.processes:
            raise RuntimeError("workers already running; shutdown first")
        if backend == "auto":
            backend = topology.detect_backend()
        host_chips = tpu_ports = None
        if backend == "tpu":
            # Fail fast, before any child exists, when the topology
            # can't fit this host's chips (reference validates GPU ids
            # against device_count pre-spawn: magic.py:454-488).  The
            # returned probe feeds the env carve so validation and env
            # construction share one host geometry (one probe).
            host_chips = topology.validate_tpu_request(
                num_workers, chips_per_worker, chips=chips)
            # One TPU-runtime port per rank, picked fresh for every
            # fleet: a fixed base would collide with the previous
            # fleet's lingering sockets or a leaked worker.
            tpu_ports = find_free_ports(num_workers)
        self.backend = backend
        self.world_size = num_workers
        self.dist_port = find_free_port() if num_workers > 1 else None

        for rank in range(num_workers):
            env = topology.worker_env(rank, num_workers, backend,
                                      chips_per_worker=chips_per_worker,
                                      chips=chips, host_chips=host_chips,
                                      tpu_ports=tpu_ports)
            if extra_env:
                env.update(extra_env)
            cmd = [sys.executable, "-m", "nbdistributed_tpu.runtime.worker",
                   "--rank", str(rank), "--world-size", str(num_workers),
                   "--coordinator-host", coordinator_host,
                   "--control-port", str(control_port),
                   "--backend", backend]
            if self.dist_port is not None:
                cmd += ["--dist-port", str(self.dist_port)]
            self._spawn(rank, cmd, env)
        self.hosts = {r: "local" for r in range(num_workers)}
        self._start_monitor()

    def start_workers_multihost(self, hosts, control_port: int, *,
                                coordinator_host: str,
                                backend: str = "auto",
                                ssh: str = "ssh",
                                auth_token: str | None = None,
                                agents=None,
                                agent_token: str | None = None,
                                extra_env: dict | None = None) -> int:
        """Launch workers across hosts per a
        :func:`~nbdistributed_tpu.manager.multihost.make_launch_plan`.

        ``hosts``: a spec string (``"h1,h2:2,local"``) or list of
        ``HostSpec``.  Entries with host ``"local"`` spawn directly.
        Remote entries launch through their **host agent** when
        ``agents`` maps their label to an endpoint (``{"h2":
        ("10.0.0.3", 7411)}`` or the ``"h2=10.0.0.3:7411"`` spec
        string — see :mod:`~nbdistributed_tpu.manager.hostagent`),
        and through an ssh proxy process otherwise.  ``extra_env``
        rides every worker's env (session token/epoch, host labels).
        Returns the world size.
        """
        from . import hostagent, multihost

        if self.processes:
            raise RuntimeError("workers already running; shutdown first")
        specs = multihost.parse_hosts(hosts) if isinstance(hosts, str) \
            else list(hosts)
        agent_eps = hostagent.parse_agents(agents)
        unknown = set(agent_eps) - {h.host for h in specs}
        if unknown:
            raise ValueError(
                f"agent endpoints for hosts {sorted(unknown)} that are "
                f"not in the host spec {[h.host for h in specs]}")
        if backend == "auto":
            backend = topology.detect_backend()
        self.backend = backend
        self.world_size = sum(h.workers for h in specs)
        self.dist_port = find_free_port() if self.world_size > 1 else None
        plan = multihost.make_launch_plan(
            specs, coordinator_host=coordinator_host,
            control_port=control_port, dist_port=self.dist_port,
            backend=backend)
        ship = dict(extra_env or {})
        if auth_token:
            # Ship the control-plane shared secret in every worker's
            # env (rides the ssh remote command for remote entries —
            # visible to local `ps` on that host; see multihost.ssh_argv).
            ship["NBD_AUTH_TOKEN"] = auth_token
        if ship:
            import dataclasses as _dc
            plan = [_dc.replace(
                l, env=tuple(sorted({**dict(l.env), **ship}.items())))
                for l in plan]
        try:
            for launch in plan:
                self.hosts[launch.rank] = launch.host
                if launch.host == "local":
                    # Direct spawn: local base env + the plan's
                    # overrides.
                    env = topology.cpu_worker_env() if backend == "cpu" \
                        else dict(os.environ)
                    env.update(dict(launch.env))
                    self._spawn(launch.rank, list(launch.argv), env)
                elif launch.host in agent_eps:
                    client = self._agents.get(launch.host)
                    if client is None:
                        addr, port = agent_eps[launch.host]
                        # The agent's ADMISSION secret (fixed at daemon
                        # start, NBD_AGENT_TOKEN on the kernel side) is
                        # distinct from the per-session control-plane
                        # token the workers dial back with; the latter
                        # is only a usable fallback when the caller
                        # started the daemons with it (tests do).
                        client = hostagent.AgentClient(
                            addr, port,
                            auth_token=(agent_token if agent_token
                                        is not None else auth_token))
                        self._agents[launch.host] = client
                    self.spawned_at[launch.rank] = time.time()
                    pid = client.spawn(launch.rank, launch.argv,
                                       dict(launch.env))
                    self.processes[launch.rank] = \
                        hostagent._AgentWorker(client, launch.rank, pid)
                    self.io[launch.rank] = \
                        hostagent._AgentWorkerIO(client, launch.rank)
                else:
                    self._spawn(launch.rank,
                                multihost.ssh_argv(launch, ssh=ssh),
                                dict(os.environ))
        except Exception:
            # A half-spawned world must not leak children or agent
            # connections: reap what came up, then re-raise.
            try:
                self.shutdown()
            except Exception:
                pass
            raise
        self._start_monitor()
        return self.world_size

    def adopt(self, pids: dict[int, int], *, backend: str | None = None,
              dist_port: int | None = None) -> None:
        """Adopt externally-discovered worker processes this manager
        did not spawn — the ``%dist_attach`` reattach path (durable
        sessions).  Death-watch works through the same monitor thread
        via signal-0 polling (see :class:`_AdoptedProcess`); interrupt
        and tiered shutdown work unchanged (the workers were started
        with their own process groups)."""
        if self.processes:
            raise RuntimeError("workers already running; shutdown first")
        self.backend = backend
        self.world_size = len(pids)
        self.dist_port = dist_port
        for rank, pid in sorted(pids.items()):
            self.processes[rank] = _AdoptedProcess(pid)
            self.io[rank] = _AdoptedIO(pid)
        self.hosts = {r: "local" for r in self.processes}
        self._start_monitor()

    def _spawn(self, rank: int, cmd: list[str], env: dict) -> None:
        self.spawned_at[rank] = time.time()
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, start_new_session=True,  # own pgid for group kill
            cwd=os.getcwd())
        self.processes[rank] = proc
        self.io[rank] = _ChildIO(proc, rank)

    def _start_monitor(self) -> None:
        self._monitor_stop.clear()
        self._monitor_thread = threading.Thread(
            target=self._monitor, name="nbd-child-monitor", daemon=True)
        self._monitor_thread.start()

    # ------------------------------------------------------------------

    def _monitor(self) -> None:
        """Watch children; report deaths (reference's is_running prunes
        as a side effect instead: process_manager.py:229-258)."""
        while not self._monitor_stop.wait(0.25):
            for rank, proc in list(self.processes.items()):
                rc = proc.poll()
                if rc is not None and rank not in self._reported_dead:
                    self._reported_dead.add(rank)
                    for cb in self._death_callbacks:
                        try:
                            cb(rank, rc)
                        except Exception:
                            pass

    def quiesce(self) -> None:
        """Stop death monitoring ahead of an intentional shutdown so
        planned worker exits are not reported as failures."""
        self._monitor_stop.set()
        if self._monitor_thread is not None:
            self._monitor_thread.join(timeout=1)

    def check_startup_failure(self) -> None:
        """Raise with captured stdio if any worker died during bring-up
        (reference: process_manager.py:138-150)."""
        for rank, proc in self.processes.items():
            rc = proc.poll()
            if rc is not None:
                raise RuntimeError(
                    f"worker {rank} exited with code {rc} during startup.\n"
                    f"--- worker {rank} output ---\n{self.io[rank].tail()}")

    def startup_diagnostics(self, ranks: list[int] | None = None,
                            tail_lines: int = 8) -> str:
        """Per-rank exit status + captured stdio tail for the given
        ranks (default: all) — folded into attach-timeout errors so
        "workers [2] did not attach" also says WHY (exit code, the
        ImportError, the bind failure...) without a second probe.  A
        rank that is still running also says which bring-up stage its
        flight record last entered, and for how long ("in `backend`
        for 171 s"): the ring file is readable while its writer
        hangs."""
        from ..observability import bringup, flightrec
        from ..utils import knobs
        run_dir = knobs.get_str("NBD_RUN_DIR")   # the comm exported it
        lines = []
        for rank in sorted(ranks if ranks is not None
                           else self.processes):
            proc = self.processes.get(rank)
            if proc is None:
                lines.append(f"--- rank {rank}: never spawned")
                continue
            rc = proc.poll()
            state = (f"exited with code {rc}" if rc is not None
                     else f"still running (pid {proc.pid}, never "
                          f"attached)")
            if rc is None and run_dir:
                try:
                    ring = flightrec.read_ring(flightrec.ring_path(
                        run_dir, f"rank{rank}", proc.pid))
                    at = bringup.stage_in(ring["events"], time.time())
                except (OSError, KeyError, TypeError):
                    at = None   # a remote rank: its ring is not here
                if at is not None:
                    state += f" in `{at[0]}` for {at[1]:.0f} s"
            lines.append(f"--- rank {rank}: {state}")
            io = self.io.get(rank)
            tail = io.tail(tail_lines) if io is not None else ""
            if tail.strip():
                lines.append(tail.rstrip("\n"))
            else:
                lines.append("    (no output captured)")
        return "\n".join(lines)

    def dump_stacks(self, ranks: list[int] | None = None) -> list[int]:
        """SIGUSR1 the worker process(es): each worker's faulthandler
        appends an all-thread stack dump to its
        ``<run_dir>/stacks-rank{N}.txt`` — the %dist_doctor's way to
        see INSIDE a wedged rank (works even when the main thread is
        stuck in a loop or a native call).  Returns the ranks
        signaled.  Signal delivery is to the worker pid only, not the
        process group (XLA helper subprocesses must not see it)."""
        signaled = []
        for rank, proc in sorted(self.processes.items()):
            if ranks is not None and rank not in ranks:
                continue
            if proc.poll() is None:
                try:
                    proc.send_signal(signal.SIGUSR1)
                    signaled.append(rank)
                except Exception:
                    pass
        return signaled

    def interrupt(self, ranks: list[int] | None = None) -> list[int]:
        """SIGINT the worker process(es) — Jupyter-style cell interrupt.
        The executing cell aborts with a KeyboardInterrupt error
        response; the worker survives.  Returns the ranks signaled."""
        signaled = []
        for rank, proc in sorted(self.processes.items()):
            if ranks is not None and rank not in ranks:
                continue
            if proc.poll() is None:
                try:
                    proc.send_signal(signal.SIGINT)
                    signaled.append(rank)
                except Exception:
                    pass
        return signaled

    def is_running(self) -> bool:
        return any(p.poll() is None for p in self.processes.values())

    def alive_ranks(self) -> list[int]:
        return sorted(r for r, p in self.processes.items()
                      if p.poll() is None)

    # ------------------------------------------------------------------

    def shutdown(self, *, term_grace_s: float = 3.0,
                 kill_grace_s: float = 2.0) -> None:
        """SIGTERM → wait → SIGKILL → wait, per process group
        (reference: process_manager.py:177-227)."""
        self.quiesce()  # stop + join the monitor so no shutdown path
        # reports these intentional exits as worker deaths
        procs = list(self.processes.items())
        for _rank, proc in procs:
            if proc.poll() is None:
                self._signal_group(proc, signal.SIGTERM)
        self._wait_all(procs, term_grace_s)
        for _rank, proc in procs:
            if proc.poll() is None:
                self._signal_group(proc, signal.SIGKILL)
        remaining = self._wait_all(procs, kill_grace_s)
        for rank, proc in remaining:
            print(f"warning: worker {rank} (pid {proc.pid}) survived "
                  "SIGKILL", file=sys.stderr)
        for _rank, proc in procs:
            if proc.stdout:
                try:
                    proc.stdout.close()
                except OSError:
                    pass
        for client in self._agents.values():
            # Belt-and-braces remote reap (the per-rank SIGTERM/SIGKILL
            # above already went through the agent), then drop the
            # connection.
            try:
                client.request("reap", {}, timeout=10.0)
            except Exception:
                pass
            client.close()
        self._agents.clear()
        self.processes.clear()
        self.io.clear()
        self.hosts.clear()
        self.spawned_at.clear()
        self._reported_dead.clear()
        self.world_size = 0

    @staticmethod
    def _signal_group(proc: subprocess.Popen, sig: int) -> None:
        if getattr(proc, "remote", False):
            # Agent-spawned worker: its pid belongs to ANOTHER host's
            # pid namespace — a local killpg on that number could hit
            # an innocent local process.  Route through the agent.
            try:
                proc.send_signal_group(sig)
            except Exception:
                pass
            return
        try:
            os.killpg(os.getpgid(proc.pid), sig)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                proc.send_signal(sig)
            except (ProcessLookupError, OSError):
                pass

    @staticmethod
    def _wait_all(procs, grace_s: float):
        deadline = time.time() + grace_s
        pending = [(r, p) for r, p in procs if p.poll() is None]
        while pending and time.time() < deadline:
            time.sleep(0.05)
            pending = [(r, p) for r, p in pending if p.poll() is None]
        return pending

    # ------------------------------------------------------------------

    def get_status(self) -> dict[int, dict]:
        """Process-level status (reference: process_manager.py:260-295);
        live device details come from the workers over the control plane
        via the magic layer's %dist_status."""
        out = {}
        for rank, proc in self.processes.items():
            rc = proc.poll()
            out[rank] = {
                "pid": proc.pid,
                "running": rc is None,
                "returncode": rc,
                "backend": self.backend,
            }
        return out
