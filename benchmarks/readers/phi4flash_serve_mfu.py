"""Model FLOP/s utilisation of a serving window (host clock): counted
FLOPs of the tokens the clients received and of the prompt tokens
prefilled, over the window and the bf16 peak.  A decode token costs the
whole stack at its position; a prompt token what the chunk program runs
of it (``model/phi4flash_flops.py``); the layers past the shared K/V
run once a prompt, counted as one more decode token.  The share of the
whole step's peak: it bounds later claims in the cell."""

from benchmarks.model import phi4flash_flops as F


def read(obs: dict, args: dict):
    served = obs.get("served")
    if not served or "peak" not in obs or not served.get("seconds"):
        return None
    cfg = obs["cfg"]
    flops = (served["decode_tokens"] + served["prompts"]) \
        * F.decode_flops_per_token(cfg, served["mean_position"]) \
        + served["prompt_tokens"] * F.prefill_flops_per_token(cfg)
    return 100.0 * flops / (served["seconds"] * obs["chips"]
                            * obs["peak"]["bf16_flops"])
