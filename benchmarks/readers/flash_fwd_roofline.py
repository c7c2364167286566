"""The flash forward kernel's share of its roofline: the least time the
chip could take for its calls (the larger of operations over the bf16
peak and bytes over the HBM peak, both counted from shapes in
``model/flops.py``) over the device time of the events ``args.match``
names."""

import re

from benchmarks.model import flops


def read(obs: dict, args: dict):
    trace = obs.get("trace") or {}
    if not trace.get("ops") or "peak" not in obs:
        return None
    pat = re.compile(args["match"])
    names = [k for k in trace["ops"]
             if pat.search(trace["op_text"].get(k, k))]
    seconds = sum(trace["ops"][k] for k in names)
    calls = sum(trace["op_calls"][k] for k in names)
    if not calls or seconds <= 0:
        return None
    t = obs["traffic"]
    counts = flops.flash_fwd_counts(obs["cfg"], t["rows_per_rank"],
                                    t["seq_len"])
    least = flops.roofline_seconds(counts, obs["peak"])["seconds"]
    # op seconds are a mean over chips; calls are summed over them
    return 100.0 * least * (calls / trace["chips"]) / seconds
