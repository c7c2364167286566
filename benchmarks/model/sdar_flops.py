"""Operations and bytes of SDAR-30B-A3B's serving programs, counted
from shapes: what a call has to do, not what it could skip.  Checked
against counts by hand in ``tests/test_counts_sdar.py``.
"""

from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401
from .sdar_weights import sizes


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    z = sizes(cfg)
    return 3 * z["D"] * z["Fe"] * itemsize


def expert_read_counts(cfg: dict, touched: float, rows: float,
                       itemsize: int = 2) -> dict:
    """The three grouped matmuls (gate, up, down) of the expert layers
    of some passes: ``touched`` experts read in all (summed over layers
    and passes), ``rows`` routed rows in all.  Bytes: each touched
    expert's three matrices once, and each row in and out of each
    matmul.  FLOPs: 2 a multiply-add."""
    z = sizes(cfg)
    d, f = z["D"], z["Fe"]
    return {"flops": rows * 3 * 2 * d * f,
            "bytes": touched * expert_bytes(cfg, itemsize)
            + rows * 3 * (d + f) * itemsize}


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One token's K and V in one layer."""
    z = sizes(cfg)
    return 2 * z["Hkv"] * z["Dh"] * itemsize


def attn_decode_counts(cfg: dict, kv_bytes: float, row_passes: float,
                       block: int, itemsize: int = 2) -> dict:
    """The paged decode kernel's calls of some passes: ``kv_bytes`` of
    K and V pages fetched in all (the program's count: a row's live
    pages once a layer a pass) and ``row_passes`` rows that took part,
    each with ``block`` queries a head.  Bytes: the pages, and a row's
    queries in and outputs out, a layer.  FLOPs: every query of the
    block against every key fetched, the score over ``head_dim`` and
    the weighted sum over it, 2 a multiply-add."""
    z = sizes(cfg)
    keys = kv_bytes / kv_bytes_per_token(cfg, itemsize)   # over layers
    qo = row_passes * z["L"] * 2 * block * z["H"] * z["Dh"] * itemsize
    return {"flops": keys * block * z["H"] * z["Dh"] * 2 * 2,
            "bytes": kv_bytes + qo}


def layer_params(cfg: dict) -> int:
    """Matrix entries a token of one layer is multiplied by: q and o,
    k and v, the router, and the experts it chose."""
    z = sizes(cfg)
    d, q, kv = z["D"], z["H"] * z["Dh"], z["Hkv"] * z["Dh"]
    return 2 * d * q + 2 * d * kv + d * z["E"] + z["k"] * 3 * d * z["Fe"]


def decode_flops_per_token(cfg: dict, pos: float) -> float:
    """One position's pass through the held stack at position ``pos``:
    every layer's matrices and the head at 2 a multiply-add, and the
    attention products over the keys before it.  What a token a client
    receives is counted as, once: the passes a block takes beyond one a
    position are the loop's cost, not the model's."""
    z = sizes(cfg)
    attn = z["L"] * (pos + 1) * z["H"] * z["Dh"] * 2 * 2
    return 2 * (z["L"] * layer_params(cfg) + z["D"] * z["V"]) + attn


def prefill_flops_per_token(cfg: dict, prompt_len: float) -> float:
    """What the chunk programs run of a prompt token: the layers (no
    head: nothing is sampled from a prompt), attention at the causal
    average of half the prompt."""
    z = sizes(cfg)
    attn = z["L"] * (prompt_len / 2) * z["H"] * z["Dh"] * 2 * 2
    return 2 * z["L"] * layer_params(cfg) + attn
