"""Share of the device's program time spent in the programs (XLA
modules, one event a run on the profile's "XLA Modules" line) whose
name matches ``args.match`` (a regular expression, searched), over the
seconds of all programs.  The serving programs carry their own names
(``jit_nbd_prefill_paged``, ``jit_nbd_decode_step_paged``); a program
that names nothing (``jit_fn``) matches nothing, and the reader then
returns nothing."""

import re


def read(obs: dict, args: dict):
    modules = (obs.get("trace") or {}).get("modules") or {}
    total = sum(modules.values())
    if not total:
        return None
    pat = re.compile(args["match"])
    hit = [v for k, v in modules.items() if pat.search(k)]
    if not hit:
        return None
    return 100.0 * sum(hit) / total
