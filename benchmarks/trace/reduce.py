"""From a profiler trace to numbers: device busy and idle time, time per
device operation, the longest idle gaps with what the host was doing.

Two steps, so that the second can be checked on a small recorded trace
kept beside this file (``tests/recorded_trace.json``):
``events_of(xplane file)`` lists (plane, line, name, start_ns, dur_ns),
and ``reduce(events)`` is plain arithmetic on that list.

On a TPU plane the line "XLA Ops" holds one event per executed HLO
operation (fusions, custom calls — a Mosaic kernel is a custom call);
"XLA Modules" holds one per run of a compiled program.  Busy time is
the union of the "XLA Ops" intervals: nested or overlapping events are
not counted twice, and a name's seconds leave out the events nested in
it.
"""

from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_name(text: str) -> str:
    """An event's name is the whole HLO instruction; keep its own name
    and its opcode: ``closed_call.62 custom-call``, ``fusion.3 fusion``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    m = _OPCODE.search(rest)
    return head.lstrip("%") + (" " + m.group(1) if m else "")


def events_of(path: str) -> list[list]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.append([plane.name, line.name, e.name,
                            int(e.start_ns), int(e.duration_ns)])
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals) -> tuple[float, list]:
    """Total covered length, and the merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce(events: list[list], top: int = 10) -> dict:
    """Per device plane: busy seconds (union of op intervals), the
    window (first op start to last op end), seconds per op name,
    seconds per program, and the longest idle gaps, each named by the
    host event that covered most of it."""
    planes: dict[str, dict] = {}
    host = []       # (start, end, name) of host-side events
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE):
            p = planes.setdefault(plane, {"ops": [], "modules": []})
            if line == OPS_LINE:
                p["ops"].append((start, start + dur, name))
            elif line == MODULES_LINE:
                p["modules"].append((start, start + dur, name))
        elif plane.startswith("/host:") and dur > 0:
            host.append((start, start + dur, name))
    out = {}
    for plane, p in planes.items():
        if not p["ops"]:
            continue
        busy, merged = _union((s, e) for s, e, _ in p["ops"])
        t0, t1 = merged[0][0], merged[-1][1]
        text = {short_name(n): n[:1500] for _, _, n in p["ops"]}
        p["ops"] = [(s, e, short_name(n)) for s, e, n in p["ops"]]
        by_op = _self_seconds(p["ops"])
        by_module: dict[str, float] = {}
        for s, e, name in p["modules"]:
            by_module[name] = by_module.get(name, 0.0) + (e - s) / 1e9
        gaps = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(merged, merged[1:])), reverse=True)
        out[plane] = {
            "busy_s": busy / 1e9, "window_s": (t1 - t0) / 1e9,
            "ops": by_op, "op_text": text, "modules": by_module,
            "op_calls": dict(collections.Counter(
                n for _, _, n in p["ops"])),
            "gaps": [[_host_during(host, s, e), g / 1e9]
                     for g, s, e in gaps[:top]],
        }
    return out


def _self_seconds(ops) -> dict:
    """Seconds per op name, each event counted without the events
    nested in it (a ``while`` holds its body's ops as children on the
    same line), so that the names add up to the busy time."""
    out: dict[str, float] = {}
    stack: list[list] = []      # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def _host_during(host, s, e) -> str:
    """The host event that overlaps [s, e) longest."""
    best, name = 0, "host_idle_or_untraced"
    for hs, he, hn in host:
        o = min(e, he) - max(s, hs)
        if o > best:
            best, name = o, hn
    return name


def reduce_dir(trace_dir: str) -> dict:
    """One process's trace: its device planes, reduced."""
    return reduce(events_of(find_xplane(trace_dir)))


def mean_over_chips(runs: list[dict]) -> dict:
    """Several processes' traces as one reading: busy and window
    averaged over the chips used, op seconds averaged likewise, the
    longest gaps of any chip."""
    planes = [p for run in runs for p in run.values()]
    if not planes:
        return {}
    n = len(planes)
    ops: dict[str, float] = {}
    calls: dict[str, int] = {}
    modules: dict[str, float] = {}
    text: dict[str, str] = {}
    for p in planes:
        text.update(p["op_text"])
        for k, v in p["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / n
        for k, v in p["modules"].items():
            modules[k] = modules.get(k, 0.0) + v / n
        for k, v in p["op_calls"].items():
            calls[k] = calls.get(k, 0) + v
    gaps = sorted((g for p in planes for g in p["gaps"]),
                  key=lambda g: -g[1])[:10]
    return {"chips": n, "busy_s": sum(p["busy_s"] for p in planes) / n,
            "window_s": sum(p["window_s"] for p in planes) / n,
            "ops": ops, "op_text": text, "op_calls": calls,
            "modules": modules, "gaps": gaps}


def breakdown(trace: dict, top: int = 10) -> dict:
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in trace["gaps"][:top]]}
