"""Share of the device's busy time spent in operations whose HLO
text matches one of ``args.match`` (regular expressions, searched): a
Mosaic kernel is a ``custom-call``, a collective an ``all-reduce``."""

import re


def read(obs: dict, args: dict):
    trace = obs.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    pats = [re.compile(p) for p in args["match"]]
    hit = [v for k, v in trace["ops"].items()
           if any(p.search(trace["op_text"].get(k, k)) for p in pats)]
    if not hit:
        return None
    return 100.0 * sum(hit) / trace["busy_s"]
