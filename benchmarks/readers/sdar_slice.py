"""What the block server counted between the two instants the profiler
was switched (``obs["slice_totals"]``: the difference of two readings of
``ticks.totals``), brought to the passes the trace itself holds, and
the seconds of the denoise program's operations that ``args.match``
names (``trace/by_module.py`` tells them from the prefill chunk's,
which carry the same names).

The two readings are a tick or two off the profiler's own window.  The
denoise program's calls of one operation in the trace
(``args.step_match``) over the calls a pass makes of it
(``args.step_calls_a_layer`` a layer) give the passes the trace held;
where the program counted more, its counts are scaled down to the
trace's passes (a share cannot pass 100% by counting passes the trace
did not hold), and never up."""

import re

DENOISE = "jit_nbd_denoise_step_paged"


def denoise_ops(obs: dict, match: str) -> tuple[float, int]:
    """Seconds and calls of the denoise program's operations whose HLO
    text ``match`` finds; zeros where there is no such trace or no such
    program (a tree that has no block server)."""
    ops = (obs.get("trace_by_module") or {}).get(DENOISE) or {}
    pat = re.compile(match)
    hit = [(s, n) for s, n, text in ops.values() if pat.search(text)]
    return sum(s for s, _ in hit), sum(n for _, n in hit)


def counted(obs: dict, args: dict):
    """-> (seconds of the operations ``args.match`` names, the slice's
    totals scaled to the trace's passes), or None where there is
    nothing to read."""
    seconds, calls = denoise_ops(obs, args["match"])
    _, marks = denoise_ops(obs, args["step_match"])
    totals = obs.get("slice_totals")
    if not calls or not marks or not totals or not totals.get("steps") \
            or "peak" not in obs:
        return None
    layers = obs["cfg"]["num_hidden_layers"]
    traced = marks / (args["step_calls_a_layer"] * layers)
    scale = min(1.0, traced / totals["steps"])
    return seconds, {k: v * scale for k, v in totals.items()}
