"""Model FLOP/s utilisation of a serving window (host clock): counted
FLOPs of the tokens the clients received and of the prompt tokens
prefilled, over the window and the bf16 peak.  A decode token costs the
whole stack at its position, a prompt token what the chunk programs run
of it (``model/nemotronh_flops.py``); the trailing expert layers and
the head run once a prompt, counted as one more decode token.  The
routed experts are counted as the program routed them here: rows routed
a layer over tokens decoded (``ticks.totals`` over the window), about
half of ``num_experts_per_tok`` at a half share.  The share of the
whole step's peak: it bounds later claims in the cell."""

from benchmarks.model import nemotronh_flops as F


def read(obs: dict, args: dict):
    served, totals = obs.get("served"), obs.get("window_totals")
    if (not served or "peak" not in obs or not served.get("seconds")
            or not totals or not totals.get("dc")):
        return None
    cfg = obs["cfg"]
    kept = totals["moe_rows"] / totals["dc"]
    prompt = served["prompt_tokens"] / max(1, served["prompts"])
    flops = (served["decode_tokens"] + served["prompts"]) \
        * F.decode_flops_per_token(cfg, served["mean_position"], kept) \
        + served["prompt_tokens"] * F.prefill_flops_per_token(cfg, kept,
                                                              prompt)
    return 100.0 * flops / (served["seconds"] * obs["chips"]
                            * obs["peak"]["bf16_flops"])
