"""What the program counted between the two instants the profiler was
switched (``obs["slice_totals"]``: the difference of two readings of
``ticks.totals``), brought to the steps the trace itself holds.

The two readings are a tick or two off the profiler's own window (a
tick of eight steps may straddle either switch).  The decode step's
calls of one operation in the trace (``args.step_match``) over the
calls a step makes of it (``args.step_calls``) give the steps the
trace held; where the program counted more, its counts
are scaled down to the trace's steps (a share cannot pass 100% by
counting steps the trace did not hold), and never up."""

from benchmarks.readers.joyai_expert_read_roofline import decode_ops


def counted(obs: dict, args: dict):
    """-> (seconds of the decode step's operations ``args.match``
    names, the slice's totals scaled to the trace's steps), or None
    where there is nothing to read: no trace, no totals (a program
    that keeps none), no such operation."""
    seconds, calls = decode_ops(obs, args)
    _, marks = decode_ops(obs, {"match": args["step_match"]})
    totals = obs.get("slice_totals")
    if not calls or not marks or not totals or not totals.get("steps") \
            or "peak" not in obs:
        return None
    traced = marks / args["step_calls"]
    scale = min(1.0, traced / totals["steps"])
    return seconds, {k: v * scale for k, v in totals.items()}
