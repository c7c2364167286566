"""The paged decode kernel's share of its roofline in a model whose
decode step calls it for two kinds of K/V (window rings, and one full
layer's pages read by that layer and every cross-attention layer): the
least time the chip could take for the calls of the traced slice (the
larger of operations over the bf16 peak and bytes over the HBM peak,
counted in ``model/phi4flash_flops.py``: K and V pages once a reading
layer, q in, o out) over the device time of the decode step's events
that ``args.match`` names (``trace/by_module.py`` tells them from a
chunk program's).

Bytes are counted in the traced slice, not at the window's end: the
driver hands over the position of every token a client received inside
the slice (``obs["slice_positions"]``: one entry a row and step), since
rows grow all through a window of long outputs.  The kernel's calls in
the slice over the calls a step makes give the steps; where the two
disagree by more than a tick's worth, the count with fewer steps
bounds the bytes (a share cannot pass 100% by counting steps the trace
did not hold)."""

from benchmarks.model import phi4flash_flops as F
from benchmarks.readers.joyai_expert_read_roofline import decode_ops


def read(obs: dict, args: dict):
    seconds, calls = decode_ops(obs, args)
    positions = obs.get("slice_positions")
    if not calls or not positions or "peak" not in obs:
        return None
    cfg, block = obs["cfg"], obs["geo"]["kv_block_tokens"]
    r = F.readers(cfg)
    counts = F.attn_decode_counts(cfg, positions, block)
    # the trace's own count of (row, step) pairs, were every row live
    traced = calls / (r["full"] + r["window"]) * obs["geo"]["max_batch"]
    scale = min(1.0, traced / len(positions))
    least = F.roofline_seconds(
        {k: v * scale for k, v in counts.items()}, obs["peak"])["seconds"]
    return 100.0 * least / seconds
