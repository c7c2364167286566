"""Phi-4-mini-flash's counts against counts by hand, at the published
widths, and the two readers on observations made by hand."""

import json
import os

from benchmarks.model import phi4flash_flops as F
from benchmarks.readers import (phi4flash_attn_decode_roofline,
                                phi4flash_serve_mfu)

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "phi4-mini-flash-serve.json")) as f:
        return json.load(f)


def test_a_token_is_5120_bytes_a_layer_and_sixteen_layers_read():
    assert F.kv_bytes_per_token(_cfg()) == 2 * 20 * 64 * 2 == 5120
    assert F.readers(_cfg()) == {"window": 8, "full": 8}


def test_live_pages_follow_the_window():
    assert F.live_pages(2499, 64) == 40
    assert F.live_pages(2499, 64, 512) == 39 - 31 + 1 == 9
    assert F.live_pages(100, 64, 512) == 2


def test_attn_decode_counts_by_hand():
    # 64 rows at position 2499: 40 pages of the shared layer for each
    # of 8 readers, 9 pages of each of 8 window rings
    c = F.attn_decode_counts(_cfg(), [2499] * 64, 64)
    pages = 64 * 8 * (40 + 9)
    qo = 64 * 16 * 2 * (2 * 40 * 64) * 2
    assert c["bytes"] == pages * 64 * 5120 + qo
    assert c["flops"] == pages * 64 * 40 * 3 * 64 * 2
    t = F.roofline_seconds(c, PEAK)
    # 8.2 GB at 819 GB/s: memory bound, 10 ms a step (ISSUE 31's budget:
    # 8.0 ms for the shared pages, 1.6 for the windows at 512 tokens;
    # whole pages make that 1.8)
    assert t["bound"] == "memory" and 9.9e-3 < t["seconds"] < 10.2e-3


def test_matrices_by_kind_add_up_to_the_models():
    cfg = _cfg()
    mlp = 3 * 2560 * 10240
    assert F.matmul_params(cfg, "window") - mlp == 3 * 2560 * 2560
    assert F.matmul_params(cfg, "cross") - mlp == 2 * 2560 * 2560
    assert F.matmul_params(cfg, "gmu") - mlp == 2 * 2560 * 5120
    assert F.matmul_params(cfg, "ssm") - mlp == (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    # a decode token at position 0: twice the matrices (3.85 B less the
    # vectors) and little else
    f0 = F.decode_flops_per_token(cfg, 0)
    assert 2 * 3.850e9 < f0 < 2 * 3.855e9
    # at position 2499 the sixteen reading layers add 0.37 GFLOP
    grown = F.decode_flops_per_token(cfg, 2499) - f0
    assert grown == (8 * 2499 + 8 * 511) * 40 * 3 * 64 * 2
    # a prompt token runs 17 of 32 layers and no head
    assert 0.46 < F.prefill_flops_per_token(cfg) / f0 < 0.50


def _obs(seconds, calls, positions):
    text = "%nbd_flash_decode_paged.3 = bf16[64,10,4,128] custom-call("
    return {"cfg": _cfg(), "peak": PEAK, "chips": 1,
            "geo": {"kv_block_tokens": 64, "max_batch": 64},
            "slice_positions": positions,
            "trace_by_module": {"jit_nbd_decode_step_paged": {
                "nbd_flash_decode_paged.3 custom-call":
                    [seconds, calls, text]}}}


def test_roofline_reader_counts_the_slice_and_never_more_than_it_held():
    args = {"match": "^%nbd_flash_decode_paged"}
    one_step = [2499] * 64
    # one step's 16 calls in 20 ms: half the 10 ms roofline
    got = phi4flash_attn_decode_roofline.read(_obs(0.020, 16, one_step),
                                              args)
    assert 49.5 < got < 51.0
    # the client saw two steps' tokens where the trace held one: the
    # trace's count bounds the bytes
    both = phi4flash_attn_decode_roofline.read(
        _obs(0.020, 16, one_step * 2), args)
    assert abs(both - got) < 1e-9
    # nothing to read: no trace, no positions
    assert phi4flash_attn_decode_roofline.read(
        {"cfg": _cfg(), "peak": PEAK}, args) is None
    assert phi4flash_attn_decode_roofline.read(
        _obs(0.020, 16, []), args) is None


def test_serve_mfu_reader_by_hand():
    cfg = _cfg()
    obs = {"cfg": cfg, "peak": PEAK, "chips": 1,
           "served": {"seconds": 50.0, "decode_tokens": 150_000,
                      "prompt_tokens": 46_000, "prompts": 80,
                      "mean_position": 1500.0}}
    want = (150_080 * F.decode_flops_per_token(cfg, 1500.0)
            + 46_000 * F.prefill_flops_per_token(cfg)) / (50 * 197e12)
    got = phi4flash_serve_mfu.read(obs, {})
    assert abs(got - 100 * want) < 1e-9 and 11.0 < got < 14.0
    assert phi4flash_serve_mfu.read({"cfg": cfg, "peak": PEAK}, {}) is None
