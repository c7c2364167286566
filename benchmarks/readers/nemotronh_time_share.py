"""Share of the device's busy time spent in operations whose HLO text
matches one of ``args.match``, summed program by program
(``trace/by_module.py``): ``trace_share`` keys an operation by its HLO
name, and the decode step and the chunk programs give different
operations the same name (``multiply_reduce_fusion.1`` is a state
update in one and a norm in another), so a match by text finds some of
them and misses others.  Operations that nest others (a ``while`` and
its kin, whose time is their bodies') are left out: the leaves are
counted."""

import re

NESTING = ("while", "conditional", "call")


def read(obs: dict, args: dict):
    trace, progs = obs.get("trace") or {}, obs.get("trace_by_module")
    if not trace.get("busy_s") or not progs:
        return None
    pats = [re.compile(p) for p in args["match"]]
    hit = [s for ops in progs.values() for op, (s, _n, text) in ops.items()
           if op.rpartition(" ")[2] not in NESTING
           and any(p.search(text) for p in pats)]
    if not hit:
        return None
    return 100.0 * sum(hit) / trace["busy_s"]
