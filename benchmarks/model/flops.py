"""Operations and bytes, counted from shapes.  The yardstick's own
arithmetic: the analytic FLOPs per token is a copy of ``bench.py``'s
(PERF.md lists the original for deletion); the flash kernel's count is
checked against a count by hand in ``tests/test_counts.py``.
"""

from __future__ import annotations

from .weights import sizes


def fwd_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Matmul FLOPs of one token's forward pass: q, k, v and output
    projections, SwiGLU, the two attention products at the causal
    average of S/2 keys, and the head.  Training is three times this
    (PaLM's convention; recomputation is not counted)."""
    z = sizes(cfg)
    d, h, hkv, dh, f = z["D"], z["H"], z["Hkv"], z["Dh"], z["F"]
    per_layer = (2 * d * h * dh + 2 * d * 2 * hkv * dh + 2 * h * dh * d
                 + 3 * 2 * d * f)
    attn = 2 * 2 * (seq_len / 2) * h * dh
    return z["L"] * (per_layer + attn) + 2 * d * z["V"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3 * fwd_flops_per_token(cfg, seq_len)


def causal_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a causal mask limited to ``window`` keeps."""
    w = min(window or seq_len, seq_len)
    # the first w queries see 1..w keys, the rest see w each
    return w * (w + 1) // 2 + (seq_len - w) * w


def flash_fwd_counts(cfg: dict, batch: int, seq_len: int,
                     itemsize: int = 2) -> dict:
    """What one call of the flash forward kernel has to do: the two
    products over the kept pairs (2 FLOPs per multiply-add, head_dim
    deep, for every query head), and the bytes it must move: q and o
    once per query head, k and v once per KV head, and the float32
    log-sum-exp it writes for the backward pass."""
    z = sizes(cfg)
    pairs = causal_pairs(seq_len, cfg.get("sliding_window"))
    flops = batch * z["H"] * pairs * z["Dh"] * 2 * 2
    qo = 2 * batch * seq_len * z["H"] * z["Dh"] * itemsize
    kv = 2 * batch * seq_len * z["Hkv"] * z["Dh"] * itemsize
    lse = batch * seq_len * z["H"] * 4
    return {"flops": flops, "bytes": qo + kv + lse}


def roofline_seconds(counts: dict, peak: dict) -> dict:
    """The least time the chip could take, and which peak bounds it."""
    t_c = counts["flops"] / peak["bf16_flops"]
    t_m = counts["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
