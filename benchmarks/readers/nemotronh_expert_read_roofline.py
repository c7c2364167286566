"""The routed experts' grouped matmuls' share of their roofline in the
decode step: the least time the chip could take to read the experts the
slice's steps touched and move their routed rows
(``model/nemotronh_flops.py``), over the device time of the decode
step's events that ``args.match`` names.  Experts touched and rows
routed are the program's own counts inside the traced slice
(``readers/nemotronh_slice.py``); ``ticks.totals`` keeps them as means
over the expert layers, so the layers multiply them back."""

from benchmarks.model import nemotronh_flops as F
from benchmarks.readers.nemotronh_slice import counted


def read(obs: dict, args: dict):
    got = counted(obs, args)
    if got is None:
        return None
    seconds, totals = got
    layers = F.kinds(obs["cfg"]).count("experts")
    counts = F.expert_read_counts(obs["cfg"],
                                  totals["moe_touched"] * layers,
                                  totals["moe_rows"] * layers)
    if not counts["bytes"]:
        return None
    return 100.0 * F.roofline_seconds(counts, obs["peak"])["seconds"] \
        / seconds
