"""Model FLOP/s utilisation: analytic FLOPs per token (forward times
three, recomputation not counted) times tokens per second, over the
chips used times the bf16 peak of the device kind."""

from benchmarks.model import flops


def read(obs: dict, args: dict):
    rate = (obs.get("e2e") or {}).get("train_tokens_per_s")
    if not rate or "peak" not in obs:
        return None
    per_token = flops.train_flops_per_token(obs["cfg"],
                                            obs["traffic"]["seq_len"])
    return 100.0 * rate * per_token / (obs["chips"]
                                       * obs["peak"]["bf16_flops"])
