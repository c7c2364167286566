"""Multi-host worker launch: plans + SSH command construction.

The reference is hard-wired single-node (``LOCAL_RANK = rank``,
localhost master — reference: worker.py:129, process_manager.py:60);
SURVEY §5.8/§7 calls multi-host out as the structural gap.  On TPU pods
the natural unit is **one worker process per host** (each owning all
local chips; ``jax.distributed`` stitches hosts over DCN and the TPU
runtime wires ICI within the slice), so a multi-host launch is just:
run the same worker argv on every host with the right rank and a
coordinator address reachable from all of them.

This module builds that as data first — :func:`make_launch_plan`
returns per-rank ``WorkerLaunch`` records (host, argv, env overrides) —
and :func:`ssh_argv` turns a record into an ``ssh`` command line.  The
:class:`~nbdistributed_tpu.manager.process_manager.ProcessManager`
executes plans: ``host == "local"`` spawns directly (how the
integration tests drive the full path in one box), anything else spawns
the ssh proxy process, whose lifetime/stdio/kill handling is identical
to a local child's.

Host specs are strings ``"host"`` or ``"host:workers"``; multiple
workers per host are supported for cpu/test backends only — TPU host
plans are strictly one worker per host (the TPU runtime's cross-host
wiring assumes it; single-host chip carving goes through
``ProcessManager.start_workers(chips_per_worker=...)``, not a plan) —
and ambiguous configs are refused loudly rather than mis-wired.
"""

from __future__ import annotations

import dataclasses
import shlex
import sys

from ..utils import knobs
from . import topology


@dataclasses.dataclass(frozen=True)
class HostSpec:
    host: str
    workers: int = 1


@dataclasses.dataclass(frozen=True)
class WorkerLaunch:
    rank: int
    host: str            # "local" = spawn directly on this machine
    argv: tuple          # worker module command line
    env: tuple           # ((key, value), ...) overrides to ship


def parse_hosts(spec: str) -> list[HostSpec]:
    """``"h1,h2:4,local:2"`` -> [HostSpec("h1",1), HostSpec("h2",4), ...]

    Duplicate hosts are rejected loudly: ``"h1,h1:2"`` is always a
    typo (the launch plan would assign two rank ranges to one box and,
    on TPU, double-book its chips), and the merged meaning the user
    intended is ambiguous — 1+2 workers or 2?
    """
    out = []
    seen: set[str] = set()
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, n = part.partition(":")
        if not host:
            raise ValueError(f"empty host in spec {spec!r}")
        try:
            workers = int(n) if n else 1
        except ValueError:
            raise ValueError(f"bad worker count {n!r} for host {host!r}")
        if workers < 1:
            raise ValueError(f"host {host!r}: workers must be >= 1")
        if host in seen:
            raise ValueError(
                f"host {host!r} listed more than once in {spec!r} — "
                f"merge the entries (e.g. {host}:N) instead of "
                f"repeating the host")
        seen.add(host)
        out.append(HostSpec(host, workers))
    if not out:
        raise ValueError(f"no hosts in spec {spec!r}")
    return out


def make_launch_plan(hosts: list[HostSpec], *, coordinator_host: str,
                     control_port: int, dist_port: int | None,
                     backend: str, python: str = sys.executable
                     ) -> list[WorkerLaunch]:
    """Assign ranks host-major and build each worker's argv + env.

    ``coordinator_host`` must be an address every listed host can reach;
    loopback with remote hosts is rejected (the classic silent-hang
    misconfig).
    """
    dup = {h.host for h in hosts
           if sum(1 for x in hosts if x.host == h.host) > 1}
    if dup:
        # parse_hosts already refuses duplicate spec entries; this
        # guards hand-built HostSpec lists taking the same wrong turn.
        raise ValueError(f"duplicate host(s) {sorted(dup)} in the plan "
                         "— each host appears once, with its worker "
                         "count")
    remote = [h for h in hosts if h.host != "local"]
    if remote and coordinator_host in ("127.0.0.1", "localhost", ""):
        raise ValueError(
            f"coordinator_host {coordinator_host!r} is loopback but the "
            f"plan has remote hosts {[h.host for h in remote]}: workers "
            "there would dial their own loopback. Pass the coordinator's "
            "reachable address (e.g. its pod/VM IP).")
    if backend == "tpu" and any(h.workers > 1 for h in hosts):
        raise ValueError(
            "multi-host TPU runs one worker per host (each owns the "
            "host's chips). For single-host chip carving use "
            "start_workers(chips_per_worker=...) instead of a host plan.")

    # The jax.distributed coordination service is hosted by *rank 0's
    # process*, so its address must be rank 0's host — not the kernel
    # machine (which runs no JAX process).  When rank 0 is "local" it
    # shares the kernel machine and the control-plane address works.
    # The port is picked on the coordinator; as with torchrun's
    # --master-port, it is assumed free on rank 0's host too.
    dist_host = coordinator_host if hosts[0].host == "local" \
        else hosts[0].host

    world = sum(h.workers for h in hosts)
    plan: list[WorkerLaunch] = []
    rank = 0
    for h in hosts:
        for local_rank in range(h.workers):
            argv = [python, "-m", "nbdistributed_tpu.runtime.worker",
                    "--rank", str(rank), "--world-size", str(world),
                    "--coordinator-host", coordinator_host,
                    "--control-port", str(control_port),
                    "--backend", backend]
            if dist_port is not None:
                argv += ["--dist-port", str(dist_port),
                         "--dist-host", dist_host]
            env: dict[str, str] = {
                # Host labels: feed per-link fault shaping, the
                # partition sentry's failure domains, and per-host
                # status grouping (ISSUE 6).  NBD_COORD_HOST is the
                # coordinator's OWN label (its env, else "local") —
                # the worker's half of every link pair; without it a
                # relabelled coordinator would shape frames on a pair
                # the workers never match.
                "NBD_HOST": h.host,
                "NBD_COORD_HOST": knobs.get_str("NBD_HOST") or "local",
            }
            if backend == "cpu":
                # Deterministic worker env regardless of what the
                # remote login shell (or, via the ssh proxy in tests,
                # the coordinator) exports: exactly one CPU device per
                # process, gloo across processes.  Empty string =
                # unset.
                env.update({"JAX_PLATFORMS": "cpu",
                            "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
                            "XLA_FLAGS": ""})
            # backend == "tpu", one worker per host: no carving env —
            # the worker owns every local chip and jax.distributed
            # handles cross-host wiring.
            plan.append(WorkerLaunch(rank=rank, host=h.host,
                                     argv=tuple(argv),
                                     env=tuple(sorted(env.items()))))
            rank += 1
    ranks = [l.rank for l in plan]
    if ranks != list(range(world)):
        # Unreachable by construction today; a refactor that breaks
        # the host-major assignment must fail HERE, not as a silent
        # half-wired world (two workers claiming one rank deadlocks
        # jax.distributed with no error).
        raise ValueError(f"internal error: launch plan ranks {ranks} "
                         f"are not exactly 0..{world - 1}")
    return plan


def ssh_argv(launch: WorkerLaunch, *, ssh: str = "ssh",
             ssh_opts: tuple = ("-o", "BatchMode=yes")) -> list[str]:
    """The local command that runs ``launch`` on its remote host.

    ``exec env K=V ... python -m ...`` under ssh, so killing the local
    ssh process signals the remote worker (ssh forwards the session
    teardown) and remote stdio streams back through the proxy's pipe.

    Caveat: the env rides the remote command line, so values (including
    NBD_AUTH_TOKEN, the control-plane shared secret) are visible to
    `ps` on the remote host for the worker's lifetime.  The token only
    gates the coordinator's listener — acceptable on single-tenant
    workers; shared remote hosts want an ssh-config-level SendEnv
    channel instead.
    """
    remote = "exec env " + " ".join(
        f"{k}={shlex.quote(v)}" for k, v in launch.env)
    remote += " " + " ".join(shlex.quote(a) for a in launch.argv)
    return [ssh, *ssh_opts, launch.host, remote]
