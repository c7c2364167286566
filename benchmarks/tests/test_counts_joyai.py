"""The latent decode kernel's and the expert layer's counts against
counts by hand, at the published widths."""

import json
import os

from benchmarks.model import joyai_flops as F

HERE = os.path.dirname(os.path.abspath(__file__))


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "joyai-flash-serve.json")) as f:
        return json.load(f)


def test_a_latent_row_is_576_values():
    assert F.latent_bytes_per_token(_cfg()) == (512 + 64) * 2 == 1152


def test_mla_decode_counts_by_hand():
    # 32 rows holding 100,000 tokens of live pages in all
    c = F.mla_decode_counts(_cfg(), 32, 100_000)
    # every key: 32 heads x (576 for the score + 512 for the sum) x 2
    assert c["flops"] == 100_000 * 32 * 1088 * 2
    # pages once, q in (32 x 576 a row), o out (32 x 512 a row), bf16
    assert c["bytes"] == (100_000 * 576 + 32 * 32 * (576 + 512)) * 2


def test_expert_layer_counts_by_hand():
    # 163 experts touched by 256 routed rows
    c = F.expert_layer_counts(_cfg(), 163, 256)
    one_expert = 3 * 2048 * 768 * 2             # 9.44 MB
    assert one_expert == 9_437_184
    rows = 256 * 3 * (2048 + 768) * 2           # in and out of 3 matmuls
    assert c["bytes"] == 163 * one_expert + rows
    assert c["flops"] == 256 * 3 * 2 * 2048 * 768
    # memory bound on a v5e: 1.54 GB at 819 GB/s
    t = F.roofline_seconds(c, {"bf16_flops": 197e12,
                               "hbm_bytes_per_s": 819e9})
    assert t["bound"] == "memory" and 1.8e-3 < t["seconds"] < 1.95e-3
