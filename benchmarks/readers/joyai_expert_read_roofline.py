"""The routed experts' grouped matmuls' share of their roofline in the
decode step: the least time the chip could take to read the experts a
step touches and move the routed rows (``model/joyai_flops.py``), over
the device time of the decode step's events that ``args.match`` names.

Only the decode step's calls are taken (``trace/by_module.py`` tells
them from the prefill chunk's, which carry the same names, have 16
times the rows and touch every expert).  Three calls (gate, up, down)
make one expert layer of one step; the experts touched and the rows
routed are the program's own counts (``ticks.moe``, means over the
decode steps)."""

import re

from benchmarks.model import joyai_flops as F

DECODE = "jit_nbd_decode_step_paged"


def ticks_of(obs: dict) -> dict:
    return ((((obs.get("serve_status") or {}).get("lat") or {})
             .get("summary") or {}).get("ticks") or {})


def decode_ops(obs: dict, args: dict) -> tuple[float, int]:
    """Seconds and calls of the decode step's operations whose HLO text
    ``args.match`` finds; zeros where there is no such trace."""
    ops = (obs.get("trace_by_module") or {}).get(DECODE) or {}
    pat = re.compile(args["match"])
    hit = [(s, n) for s, n, text in ops.values() if pat.search(text)]
    return sum(s for s, _ in hit), sum(n for _, n in hit)


def read(obs: dict, args: dict):
    seconds, calls = decode_ops(obs, args)
    moe = ticks_of(obs).get("moe")
    if not calls or "peak" not in obs or not moe:
        return None
    counts = F.expert_layer_counts(obs["cfg"], moe["experts_touched"],
                                   moe["rows_routed"])
    least = F.roofline_seconds(counts, obs["peak"])["seconds"]
    return 100.0 * least * (calls / 3) / seconds
