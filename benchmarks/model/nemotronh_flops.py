"""Operations and bytes of Nemotron-3-Nano's serving programs, counted
from shapes: what a call has to do, not what it could skip (the zero
columns that pad an expert's ``w_up`` to whole lanes are not counted).
Checked against counts by hand in ``tests/test_counts_nemotronh.py``.
"""

from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401
from .nemotronh_weights import kinds, sizes


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One routed expert's two matrices."""
    z = sizes(cfg)
    return 2 * z["D"] * z["Fe"] * itemsize


def expert_read_counts(cfg: dict, touched: float, rows: float,
                       itemsize: int = 2) -> dict:
    """The two grouped matmuls (up, down) of the expert layers of some
    decode steps: ``touched`` experts read in all (summed over layers
    and steps), ``rows`` routed rows in all.  Bytes: each touched
    expert's two matrices once, and each row in and out of each matmul.
    FLOPs: 2 a multiply-add."""
    z = sizes(cfg)
    d, f = z["D"], z["Fe"]
    return {"flops": rows * 2 * 2 * d * f,
            "bytes": touched * expert_bytes(cfg, itemsize)
            + rows * 2 * (d + f) * itemsize}


def state_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One row's recurrent state (float32) and convolution tail in one
    Mamba-2 layer."""
    z = sizes(cfg)
    return z["Hm"] * z["P"] * z["N"] * 4 + (z["K"] - 1) * z["W"] * itemsize


def state_update_counts(cfg: dict, state_bytes: float) -> dict:
    """The state updates of some decode steps that read and wrote
    ``state_bytes`` in all (the program's count: every row's state and
    tail, in and out, a Mamba-2 layer a step).  Three products and two
    sums a value of the state (decay, outer product, the read-out
    ``s . C``); the bytes bound it by a hundred to one."""
    return {"bytes": state_bytes, "flops": 5 * state_bytes / 8}


def matmul_params(cfg: dict, kind: str, kept: float) -> float:
    """Matrix entries a token of a layer of ``kind`` is multiplied by;
    ``kept``: the routed choices of a token that fall on the experts
    held (about half of ``num_experts_per_tok`` at a half share)."""
    z = sizes(cfg)
    d = z["D"]
    if kind == "mamba2":
        return d * (z["C"] + z["W"] + z["Hm"]) + z["C"] * d
    if kind == "attention":
        q, kv = z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
        return 2 * d * q + 2 * d * kv
    return d * z["Er"] + 2 * d * z["Fs"] + kept * 2 * d * z["Fe"]


def decode_flops_per_token(cfg: dict, pos: float, kept: float) -> float:
    """One decode step's FLOPs for a row at position ``pos``: every
    layer's matrices and the head at 2 a multiply-add, the attention
    products over the keys held, and the state update (five operations
    a value of the state, the convolution's taps)."""
    z, ks = sizes(cfg), kinds(cfg)
    mats = sum(matmul_params(cfg, k, kept) for k in ks) + z["D"] * z["V"]
    attn = ks.count("attention") * (pos + 1) * z["H"] * z["Dh"] * 2 * 2
    ssm = ks.count("mamba2") * (5 * z["Hm"] * z["P"] * z["N"]
                                + 2 * z["K"] * z["W"])
    return 2 * mats + attn + ssm


def prefill_flops_per_token(cfg: dict, kept: float,
                            prompt_len: float) -> float:
    """What the chunk programs run of a prompt token: the layers up to
    the last one that keeps a cache (the trailing expert layers and the
    head run once a prompt: count that as one decode token), attention
    at the causal average of half the prompt, and the block form of the
    recurrence: a block of Q tokens costs, a head, ``C B^T`` (shared by
    a group's heads), the masked product with ``x`` and the two
    products with the state, 2 a multiply-add."""
    z, ks = sizes(cfg), kinds(cfg)
    last = max(i for i, k in enumerate(ks) if k != "experts")
    below = ks[:last + 1]
    mats = sum(matmul_params(cfg, k, kept) for k in below)
    attn = (below.count("attention") * (prompt_len / 2) * z["H"] * z["Dh"]
            * 2 * 2)
    q = cfg["chunk_size"]
    ssd = below.count("mamba2") * 2 * (
        z["G"] * q * z["N"] + z["Hm"] * q * z["P"]
        + 2 * z["Hm"] * z["P"] * z["N"])
    return 2 * mats + attn + ssd
