"""The absorbed paged latent decode kernel's share of its roofline: the
least time the chip could take for its calls (the larger of operations
over the bf16 peak and bytes over the HBM peak, counted from shapes in
``model/joyai_flops.py``) over the device time of the decode step's
events that ``args.match`` names (``trace/by_module.py``).

A call is one layer of one decode step.  What it must read is the
latent rows of the live pages, which the program counts
(``ticks.kv_read_bytes``: the mean bytes of pages a step fetched, all
layers, at the width the pool stores: rows padded to whole 128-lane
tiles); counted here at the latent's own width, so the padding is not
credited to the kernel."""

from benchmarks.model import joyai_flops as F
from benchmarks.readers.joyai_expert_read_roofline import (decode_ops,
                                                           ticks_of)


def read(obs: dict, args: dict):
    seconds, calls = decode_ops(obs, args)
    ticks = ticks_of(obs)
    if not calls or "peak" not in obs or not ticks.get("kv_read_bytes"):
        return None
    cfg = obs["cfg"]
    stored = -(-F.latent_bytes_per_token(cfg) // 256) * 256
    page_tokens = ticks["kv_read_bytes"] / (cfg["num_hidden_layers"]
                                            * stored)
    counts = F.mla_decode_counts(cfg, obs["geo"]["max_batch"], page_tokens)
    least = F.roofline_seconds(counts, obs["peak"])["seconds"]
    return 100.0 * least * calls / seconds
