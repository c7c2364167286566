"""The routed experts' grouped matmuls' share of their roofline in the
denoise program: the least time the chip could take to read the experts
the slice's passes touched and move their routed rows
(``model/sdar_flops.py``), over the device time of that program's
events that ``args.match`` names.  Experts touched and rows routed are
the program's own counts inside the traced slice
(``readers/sdar_slice.py``); ``ticks.totals`` keeps them as means over
the layers, so the layers multiply them back."""

from benchmarks.model import sdar_flops as F
from benchmarks.readers.sdar_slice import counted


def read(obs: dict, args: dict):
    got = counted(obs, args)
    if got is None:
        return None
    seconds, totals = got
    layers = obs["cfg"]["num_hidden_layers"]
    counts = F.expert_read_counts(obs["cfg"],
                                  totals["moe_touched"] * layers,
                                  totals["moe_rows"] * layers)
    if not counts["bytes"]:
        return None
    return 100.0 * F.roofline_seconds(counts, obs["peak"])["seconds"] \
        / seconds
