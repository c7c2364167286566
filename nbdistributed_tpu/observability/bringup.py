"""Set-up on one timeline (ISSUE 37): the named stages between the
first ``Popen`` and the first served token, under one set of names in
the flight recorder, ``get_status`` / ``serve_status`` and the status
magics.

A worker's stages are contiguous: each begins at the instant the last
ended, on ``time.time()`` (the clock ``spans.py`` stamps and
``clock.py`` corrects across hosts):

``interpreter``  process creation -> ``main()`` entered: Python's
                 start and the package's own imports
``import_jax``   -> jax imported (the flight recorder opens in here)
``rendezvous``   ``jax.distributed.initialize``, only where
                 ``world_size > 1``: it ends when the slowest rank
                 arrives, so its length on the other ranks is their wait
``backend``      the first touch of the platform: libtpu's start, the
                 chip's claim, the device line that is printed
``namespace``    ``_seed_namespace``: the imports of ``models``,
                 ``parallel``, ``ops``
``connect``      the control-plane dial, to the frame that marks the
                 rank attached

Each is a flight record ``bringup`` (``stage``, ``t0``, ``dur``, and
``next``: the stage that begins, so a rank that never attached says
where it is), and the list rides the worker's telemetry snapshot on the
first heartbeat: no frame is added to the attach.  The spawner stamps
each rank's ``Popen``, the communication manager the instant each rank
attached, and :func:`merge` lays them over the workers' lists.

No recorder of its own and nothing to switch off: a dozen
``time.time()`` calls and flight records a process.  Stdlib only.
"""

from __future__ import annotations

import os
import time

STAGES = ("interpreter", "import_jax", "rendezvous", "backend",
          "namespace", "connect")


def process_start_time() -> float:
    """``time.time()`` at which the kernel created this process, to a
    clock tick: field 22 of ``/proc/self/stat`` is the start in ticks
    since boot, and both clocks are read now so the boot time's whole
    seconds (``btime``) never enter.  Where ``/proc`` cannot say, now:
    the stage that starts here then reads 0, not a guess."""
    now = time.time()
    try:
        with open("/proc/self/stat", "rb") as f:
            # the command (field 2) may hold spaces: count from its ")"
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        up = time.clock_gettime(time.CLOCK_BOOTTIME)
        return now - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return now


class Stages:
    """One process's contiguous stages: ``enter(name)`` ends the stage
    that is open at this instant and opens ``name``; ``finish()`` ends
    the last.  ``done`` is the list that travels: ``[stage, t0, dur]``.
    Stages ended before the flight recorder opened (``interpreter``)
    are written to it when :meth:`bind` hands it over."""

    def __init__(self, first: str, t0: float):
        self.done: list[list] = []
        self._open, self._t = first, t0
        self._flight = None
        self._unwritten: list[dict] = []

    def bind(self, flight) -> None:
        self._flight = flight
        for rec in self._unwritten:
            flight.record("bringup", **rec)
        self._unwritten.clear()

    def enter(self, stage: str | None) -> float:
        """-> seconds of the stage this call ended."""
        now = time.time()
        t0, dur = round(self._t, 6), round(now - self._t, 6)
        self.done.append([self._open, t0, dur])
        rec = {"stage": self._open, "t0": t0, "dur": dur, "next": stage}
        if self._flight is not None:
            self._flight.record("bringup", **rec)
        else:
            self._unwritten.append(rec)
        self._open, self._t = stage, now
        return dur

    def finish(self) -> float:
        return self.enter(None)


def stage_in(events: list[dict], now: float) -> tuple[str, float] | None:
    """From a rank's flight events, the stage it last entered and the
    seconds it has been in it, or None where it finished its bring-up
    (or recorded nothing of it)."""
    last = None
    for ev in events:
        if ev.get("t") == "bringup":
            last = ev
    if last is None or not last.get("next"):
        return None
    return last["next"], max(0.0, now - (last["t0"] + last["dur"]))


def merge(stages: dict[int, list], spawned: dict[int, float],
          attached: dict[int, float],
          wait: tuple[float, float] | None) -> dict:
    """The spawner's view over the workers' lists.  Per rank: the
    stages' seconds, ``attach_s`` (its ``Popen`` stamp to the instant
    it attached) and ``unaccounted_s`` = that less the stages' sum
    (the fork, and the attach frame's way to the listener: near 0).
    Over the fleet: ``critical_rank`` (the rank the others waited for:
    the most seconds outside ``rendezvous``; the last to attach while
    the lists have not arrived), ``spawn_s`` (first ``Popen`` to the
    wait's start), ``wait_s``, ``attach_s`` (their sum: first ``Popen``
    to every rank attached) and the critical rank's
    ``unaccounted_s``.  A rank whose list has not
    arrived yet (the first heartbeat is ``HEARTBEAT_INTERVAL_S`` after
    the attach) has ``stages`` None."""
    ranks: dict[int, dict] = {}
    for rank in sorted(set(spawned) | set(attached) | set(stages)):
        row: dict = {"stages": None}
        got = stages.get(rank)
        if got:
            row["stages"] = {s: d for s, _t0, d in got}
        t_spawn, t_att = spawned.get(rank), attached.get(rank)
        if t_spawn is not None and t_att is not None:
            row["attach_s"] = round(t_att - t_spawn, 6)
            if got:
                row["unaccounted_s"] = round(
                    t_att - t_spawn - sum(d for _s, _t, d in got), 6)
        ranks[rank] = row
    out: dict = {"ranks": ranks, "critical_rank": None}
    if attached:
        # After the rendezvous every rank is let go at once, so which
        # attaches last is a race; the rank the fleet waited for is the
        # one with the most seconds outside `rendezvous`.
        def own_s(rank):
            st = ranks[rank]["stages"] or {}
            return (ranks[rank].get("attach_s", 0.0)
                    - st.get("rendezvous", 0.0), attached[rank], rank)
        crit = max(attached, key=own_s)
        out["critical_rank"] = crit
        if "unaccounted_s" in ranks[crit]:
            out["unaccounted_s"] = ranks[crit]["unaccounted_s"]
    if wait is not None and spawned:
        first = min(spawned.values())
        out["spawn_s"] = round(wait[0] - first, 6)
        out["wait_s"] = round(wait[1] - wait[0], 6)
        out["attach_s"] = round(wait[1] - first, 6)
    return out


def max_compile(splits) -> dict:
    """The compile watch's split over ranks: each number's maximum, and
    the eight longest programs of any rank."""
    out: dict = {}
    slowest: list = []
    for split in splits:
        for key, v in (split or {}).items():
            if key == "slowest":
                slowest.extend(v)
            elif isinstance(v, (int, float)):
                out[key] = max(out.get(key, 0), v)
    if out:
        out["slowest"] = sorted(slowest, key=lambda e: -e[1])[:8]
    return out


def _compile_lines(who: str, c: dict) -> list[str]:
    if not c:
        return []
    lines = [f"   {who} compile: trace {c.get('trace_s', 0):.2f} · "
             f"lower {c.get('lower_s', 0):.2f} · backend "
             f"{c.get('backend_s', 0):.2f} · cache load "
             f"{c.get('cache_load_s', 0):.2f}s · cache "
             f"{c.get('hits', 0)} hits / {c.get('misses', 0)} misses"]
    slow = c.get("slowest") or ()
    if slow:
        lines.append("      slowest: " + ", ".join(
            f"{name} {secs:.2f}s {how}" for name, secs, how in slow[:3]))
    return lines


def format_lines(view: dict) -> list[str]:
    """The timeline as ``%dist_status`` prints it: one line a rank
    (stage seconds, the critical rank marked), the fleet's line, then
    each rank's compile split (``view["compile"]``)."""
    lines = []
    crit = view.get("critical_rank")
    for rank, row in sorted((view.get("ranks") or {}).items(),
                            key=lambda kv: int(kv[0])):
        st = row.get("stages")
        if st is None:
            body = "(stages arrive with the first heartbeat)"
        else:
            body = " · ".join(f"{s} {st[s]:.2f}" for s in STAGES
                              if s in st)
            if "unaccounted_s" in row:
                body += f" · unaccounted {row['unaccounted_s']:.2f}"
        total = (f" = {row['attach_s']:.2f}s" if "attach_s" in row
                 else "")
        mark = " ← critical" if str(rank) == str(crit) else ""
        lines.append(f"   rank {rank}: {body}{total}{mark}")
    if view.get("attach_s") is not None:
        lines.append(f"   fleet: spawn {view['spawn_s']:.2f} · wait "
                     f"{view['wait_s']:.2f} = attach "
                     f"{view['attach_s']:.2f}s")
    for rank, c in sorted((view.get("compile") or {}).items(),
                          key=lambda kv: int(kv[0])):
        lines += _compile_lines(f"rank {rank}", c)
    return lines


def format_pool_lines(block: dict) -> list[str]:
    """The gateway's ``bringup`` block as ``%dist_pool status`` prints
    it: the ranks' lines and the fleet's, the daemon's own stages, the
    serve start's, and the compile split (the maximum over ranks)."""
    a = block.get("attach") or {}
    lines = format_lines({**a, "ranks": block.get("ranks")})
    own = [f"{label} {a[key]:.2f}" for label, key in
           (("daemon", "daemon_s"), ("tenant attach", "tenant_attach_s"))
           if a.get(key) is not None]
    opened = [f"{label} {block['open'][key]:.2f}" for label, key in
              (("spec", "spec_s"), ("build", "build_s"),
               ("kernels", "kernels_s"))
              if (block.get("open") or {}).get(key) is not None]
    if opened:
        own.append("serve open: " + " · ".join(opened))
    if own:
        lines.append("   " + " · ".join(own))
    return lines + _compile_lines("slowest rank's",
                                  block.get("compile") or {})
