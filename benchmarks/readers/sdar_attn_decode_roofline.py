"""The paged decode kernel's share of its roofline in the denoise
program, where a row's queries are a block: the least time the chip
could take to fetch the K and V pages the slice's passes read (the
program's count, ``ticks.totals.kv_bytes``: a row's live pages once a
layer a pass) and move the rows' queries and outputs
(``model/sdar_flops.py``), over the device time of that program's
events that ``args.match`` names.  Counted in the traced slice and
never more passes than the trace holds (``readers/sdar_slice.py``)."""

from benchmarks.model import sdar_flops as F
from benchmarks.readers.sdar_slice import counted


def read(obs: dict, args: dict):
    got = counted(obs, args)
    if got is None:
        return None
    seconds, totals = got
    if not totals.get("kv_bytes"):
        return None
    counts = F.attn_decode_counts(
        obs["cfg"], totals["kv_bytes"],
        totals["passes"] + totals["commits"], obs["cfg"]["block_length"])
    return 100.0 * F.roofline_seconds(counts, obs["peak"])["seconds"] \
        / seconds
