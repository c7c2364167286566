"""Operations and bytes of JoyAI-LLM-Flash's two distinctive kernels,
counted from shapes: what one call has to do, not what it could skip.
Checked against counts by hand in ``tests/test_counts_joyai.py``.
"""

from __future__ import annotations

from .flops import roofline_seconds  # noqa: F401
from .joyai_weights import sizes


def latent_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One token's latent row in one layer: ``c_kv`` and ``k_rope``."""
    z = sizes(cfg)
    return (z["r"] + z["dr"]) * itemsize


def mla_decode_counts(cfg: dict, rows: int, page_tokens: float,
                      itemsize: int = 2) -> dict:
    """One call (one layer, one decode step) of the absorbed paged
    latent decode kernel over ``rows`` slots whose live pages hold
    ``page_tokens`` tokens in all.  Bytes: every live page once, the
    absorbed queries in (H x (r + dr) a row) and the latent outputs out
    (H x r a row).  FLOPs: for every query head and key, the score over
    r + dr and the weighted sum over r, 2 a multiply-add."""
    z = sizes(cfg)
    w = z["r"] + z["dr"]
    return {"flops": z["H"] * (w + z["r"]) * 2 * page_tokens,
            "bytes": (page_tokens * w + rows * z["H"] * (w + z["r"]))
            * itemsize}


def expert_layer_counts(cfg: dict, touched: float, rows: float,
                        itemsize: int = 2) -> dict:
    """The three grouped matmuls of one expert layer (gate, up, down)
    over ``rows`` routed rows that touch ``touched`` experts.  Bytes:
    each touched expert's three matrices once, and each row in and out
    of each matmul.  FLOPs: 2 a multiply-add."""
    z = sizes(cfg)
    d, f = z["D"], z["Fe"]
    return {"flops": rows * 3 * 2 * d * f,
            "bytes": (touched * 3 * d * f + rows * 3 * (d + f)) * itemsize}
