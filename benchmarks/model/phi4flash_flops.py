"""Operations and bytes of Phi-4-mini-flash's serving programs, counted
from shapes: what a call has to do, not what it could skip (the zeros
that pad a differential query to its pair's width are not counted).
Checked against counts by hand in ``tests/test_counts_phi4flash.py``.
"""

from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401
from .phi4flash_weights import kinds, sizes


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One token's keys and values in one attention layer."""
    z = sizes(cfg)
    return 2 * z["Hkv"] * z["Dh"] * itemsize


def readers(cfg: dict) -> dict:
    """Layers whose decode attention reads each kind of K/V: the window
    layers their own rings, the full layer and every cross layer the
    shared pages."""
    ks = kinds(cfg)
    return {"window": ks.count("window"),
            "full": ks.count("full") + ks.count("cross")}


def live_pages(pos: int, block: int, window: int | None = None) -> int:
    """Pages a query at ``pos`` attends: from its window's first to
    the one it lies in."""
    first = max(0, pos + 1 - window) // block if window else 0
    return pos // block - first + 1


def attn_decode_counts(cfg: dict, positions, block: int,
                       itemsize: int = 2) -> dict:
    """All calls of the paged decode kernel that serve the tokens at
    ``positions`` (one entry a row and step): every live page of K and
    V once a reading layer, the queries in and the outputs out (a
    pair's four heads at the pair's width: 2 x H x Dh a token a layer,
    each), and two products a query head and key, 2 a multiply-add,
    over Dh for the scores and 2 Dh for the weighted sum."""
    z, r = sizes(cfg), readers(cfg)
    page = kv_bytes_per_token(cfg, itemsize) * block
    pages = sum(r["full"] * live_pages(p, block)
                + r["window"] * live_pages(p, block, z["window"])
                for p in positions)
    calls = len(positions) * (r["full"] + r["window"])
    qo = calls * 2 * (2 * z["H"] * z["Dh"]) * itemsize
    return {"bytes": pages * page + qo,
            "flops": pages * block * z["H"] * 3 * z["Dh"] * 2}


def matmul_params(cfg: dict, kind: str) -> int:
    """Matrix entries a token of a layer of ``kind`` is multiplied by."""
    z = sizes(cfg)
    d, c, n, f = z["D"], z["C"], z["N"], z["F"]
    q, kv = z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
    mixer = {"ssm": d * 2 * c + c * (z["R"] + 2 * n) + z["R"] * c + c * d,
             "window": d * q + 2 * d * kv + q * d,
             "full": d * q + 2 * d * kv + q * d,
             "cross": 2 * d * q, "gmu": 2 * d * c}[kind]
    return mixer + 3 * d * f


def decode_flops_per_token(cfg: dict, pos: float) -> float:
    """One decode step's FLOPs for a row at position ``pos``: every
    layer's matrices and the head at 2 a multiply-add, the attention
    products over the keys each kind attends, and the state update
    (three products and two sums a channel and state, the
    convolution's taps)."""
    z, r = sizes(cfg), readers(cfg)
    ks = kinds(cfg)
    mats = sum(matmul_params(cfg, k) for k in ks) + z["D"] * z["V"]
    keys = r["full"] * (pos + 1) + r["window"] * min(pos + 1, z["window"])
    ssm = ks.count("ssm") * z["C"] * (6 * z["N"] + 2 * z["K"])
    return 2 * mats + keys * z["H"] * 3 * z["Dh"] * 2 + ssm


def prefill_flops_per_token(cfg: dict) -> float:
    """What the chunk program runs of a prompt token: the layers up to
    the full layer's K and V projection (window attention over a full
    window: an upper bound for the first 512).  The layers past it run
    once a prompt: count that as one decode token."""
    z = sizes(cfg)
    ks = kinds(cfg)
    below = ks[:ks.index("full")]
    mats = (sum(matmul_params(cfg, k) for k in below)
            + 2 * z["D"] * z["Hkv"] * z["Dh"])
    keys = below.count("window") * z["window"]
    ssm = below.count("ssm") * z["C"] * (6 * z["N"] + 2 * z["K"])
    return 2 * mats + keys * z["H"] * 3 * z["Dh"] * 2 + ssm
