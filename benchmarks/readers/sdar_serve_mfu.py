"""Model FLOP/s utilisation of a block server's window (host clock):
counted FLOPs of the tokens the clients received and of the prompt
tokens prefilled, over the window and the bf16 peak
(``model/sdar_flops.py``).  A token received is counted **once**, as
one position's pass through the held stack at its position with the
experts it chose, however many passes its block took: passes that are
wasted must not raise it.  A prompt token costs what the chunk
programs run of it (no head).  The share of the whole step's peak: it
bounds later claims in the cell."""

from benchmarks.model import sdar_flops as F


def read(obs: dict, args: dict):
    served = obs.get("served")
    if not served or "peak" not in obs or not served.get("seconds") \
            or not served.get("decode_tokens"):
        return None
    cfg = obs["cfg"]
    prompt = served["prompt_tokens"] / max(1, served["prompts"])
    flops = served["decode_tokens"] \
        * F.decode_flops_per_token(cfg, served["mean_position"]) \
        + served["prompt_tokens"] * F.prefill_flops_per_token(cfg, prompt)
    return 100.0 * flops / (served["seconds"] * obs["chips"]
                            * obs["peak"]["bf16_flops"])
