"""Device operations split by the program that ran them.

``reduce.py`` keys an operation by its HLO name, and two programs name
their instructions alike (``ragged-dot-none.3`` is in the decode step
and in the prefill chunk), so their seconds arrive merged.  Here an
"XLA Ops" event goes to the "XLA Modules" event of the same plane that
contains its start: ``{program: {op: [seconds, calls, text]}}``, the
program's name without its fingerprint, seconds and calls summed over
the planes.  Durations are the events' own (an operation that nests
others, a ``while``, is counted whole): read leaves from it, such as
custom calls.
"""

from __future__ import annotations

import bisect

from .reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, short_name


def ops_by_module(events: list[list]) -> dict:
    planes: dict[str, dict] = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE) and line in (OPS_LINE,
                                                       MODULES_LINE):
            planes.setdefault(plane, {OPS_LINE: [], MODULES_LINE: []})[
                line].append((start, start + dur, name))
    out: dict[str, dict] = {}
    for p in planes.values():
        mods = sorted(p[MODULES_LINE])
        starts = [m[0] for m in mods]
        for s, e, name in p[OPS_LINE]:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue            # outside every program of the slice
            prog = mods[i][2].partition("(")[0]
            rec = out.setdefault(prog, {}).setdefault(
                short_name(name), [0.0, 0, name[:400]])
            rec[0] += (e - s) / 1e9
            rec[1] += 1
    return out


def chips_of(events: list[list]) -> int:
    return len({plane for plane, line, *_ in events
                if plane.startswith(DEVICE_PLANE) and line == OPS_LINE})
