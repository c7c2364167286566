"""SDAR-30B-A3B's counts against counts by hand, at the published
widths and the cell's cut, and the readers on observations made by
hand."""

import json
import os

from benchmarks import harness as H
from benchmarks.model import sdar_flops as F
from benchmarks.readers import (sdar_attn_decode_roofline,
                                sdar_expert_read_roofline,
                                sdar_passes_per_token, sdar_serve_mfu)

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
EXPERTS = {"match": "^%ragged-dot", "step_match": "^%ragged-dot",
           "step_calls_a_layer": 3}
KERNEL = {"match": "^%nbd_flash_decode_paged",
          "step_match": "^%nbd_flash_decode_paged", "step_calls_a_layer": 1}
DENOISE = "jit_nbd_denoise_step_paged"


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "sdar-30b-a3b-serve.json")) as f:
        return json.load(f)


def test_an_expert_is_three_matrices_of_2048_by_768():
    assert F.expert_bytes(_cfg()) == 3 * 2048 * 768 * 2 == 9_437_184


def test_expert_read_counts_by_hand():
    # one pass at 128 rows of 4 positions: all 128 experts of 7 layers,
    # 512 x 8 = 4,096 rows routed a layer
    c = F.expert_read_counts(_cfg(), 128 * 7, 4096 * 7)
    assert c["bytes"] == 896 * 9_437_184 + 28672 * 3 * (2048 + 768) * 2
    assert c["flops"] == 28672 * 3 * 2 * 2048 * 768
    t = F.roofline_seconds(c, PEAK)
    # 8.94 GB at 819 GB/s
    assert t["bound"] == "memory" and 10.8e-3 < t["seconds"] < 11.0e-3


def test_a_token_of_kv_is_2048_bytes_a_layer():
    cfg = _cfg()
    assert F.kv_bytes_per_token(cfg) == 2 * 4 * 128 * 2 == 2048
    # a pass at 128 rows holding 960 tokens each, 7 layers: 1.76 GB of
    # pages, and a row's 4 x 32 queries in and out a layer
    kv = 128 * 960 * 7 * 2048
    c = F.attn_decode_counts(cfg, kv, 128, 4)
    assert c["bytes"] == kv + 128 * 7 * 2 * 4 * 32 * 128 * 2
    # every key meets 4 x 32 queries of 128, score and weighted sum
    assert c["flops"] == 128 * 960 * 7 * 4 * 32 * 128 * 2 * 2
    t = F.roofline_seconds(c, PEAK)
    assert t["bound"] == "memory" and 2.1e-3 < t["seconds"] < 2.3e-3


def test_a_layers_matrices_and_a_tokens_flops():
    cfg = _cfg()
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
             + 8 * 3 * 2048 * 768)
    assert F.layer_params(cfg) == layer == 56_885_248
    # position 0: twice the matrices of 7 layers and the head, one key
    assert F.decode_flops_per_token(cfg, 0) == 2 * (
        7 * layer + 2048 * 151_936) + 7 * 32 * 128 * 4
    # a prompt token: no head, half the prompt's keys
    assert F.prefill_flops_per_token(cfg, 640) == 2 * 7 * layer \
        + 7 * 320 * 32 * 128 * 4


def _obs(steps=10, scale=1.0):
    """Ten passes at 128 rows in the slice, every expert touched."""
    cfg = _cfg()
    ops = {"%ragged-dot-none.1": [0.2, int(21 * steps * scale), "%ragged-dot-none.1 = bf16[4096,768] custom-call("],
           "%nbd_flash_decode_paged.1": [0.04, int(7 * steps * scale), "%nbd_flash_decode_paged.1 = bf16[128,4,32,128] custom-call("]}
    return {"cfg": cfg, "peak": PEAK, "chips": 1,
            "trace_by_module": {DENOISE: ops,
                                "jit_nbd_prefill_paged": dict(ops)},
            "slice_totals": {"steps": steps, "moe_touched": 128.0 * steps,
                             "moe_rows": 4096.0 * steps,
                             "kv_bytes": 128 * 960 * 7 * 2048.0 * steps,
                             "passes": 102.0 * steps,
                             "commits": 26.0 * steps}}


def test_expert_read_roofline_reads_the_denoise_programs_calls():
    got = sdar_expert_read_roofline.read(_obs(), EXPERTS)
    least = F.roofline_seconds(F.expert_read_counts(
        _cfg(), 128 * 7 * 10, 4096 * 7 * 10), PEAK)["seconds"]
    assert abs(got - 100 * least / 0.2) < 1e-9 and 50 < got < 60


def test_a_slice_that_counted_more_passes_than_the_trace_is_scaled_down():
    full = sdar_expert_read_roofline.read(_obs(), EXPERTS)
    # the program counted 10 passes, the trace holds 5: half the bytes
    half = sdar_expert_read_roofline.read(_obs(scale=0.5), EXPERTS)
    assert abs(half - full / 2) < 1e-9
    # and never up: a trace that holds more passes changes nothing
    more = _obs()
    more["slice_totals"]["steps"] = 5
    assert sdar_expert_read_roofline.read(more, EXPERTS) == full


def test_attn_decode_roofline_by_hand():
    got = sdar_attn_decode_roofline.read(_obs(), KERNEL)
    least = F.roofline_seconds(F.attn_decode_counts(
        _cfg(), 128 * 960 * 7 * 2048.0 * 10, 1280, 4), PEAK)["seconds"]
    assert abs(got - 100 * least / 0.04) < 1e-9 and 50 < got < 60


def test_readers_find_nothing_in_a_tree_without_a_block_server():
    parent = _obs()
    parent["trace_by_module"] = {
        "jit_nbd_decode_step_paged": parent["trace_by_module"][DENOISE]}
    for reader, args in ((sdar_expert_read_roofline, EXPERTS),
                         (sdar_attn_decode_roofline, KERNEL)):
        assert reader.read(parent, args) is None
        assert reader.read({"cfg": _cfg()}, args) is None
    assert sdar_passes_per_token.read({}, {}) is None
    assert sdar_passes_per_token.read(
        {"window_totals": {"dc": 10.0, "steps": 3.0}}, {}) is None
    assert sdar_serve_mfu.read({"cfg": _cfg()}, {}) is None


def test_passes_per_token_is_five_passes_a_block_of_four():
    totals = {"dc": 4000.0, "passes": 4000.0, "commits": 1000.0}
    assert sdar_passes_per_token.read({"window_totals": totals}, {}) == 1.25


def test_mfu_counts_a_token_once_whatever_its_passes():
    cfg = _cfg()
    served = {"seconds": 50.0, "decode_tokens": 150_000, "prompts": 230,
              "prompt_tokens": 147_200, "mean_position": 960.0}
    obs = {"cfg": cfg, "peak": PEAK, "chips": 1, "served": served}
    got = sdar_serve_mfu.read(obs, {})
    flops = 150_000 * F.decode_flops_per_token(cfg, 960.0) \
        + 147_200 * F.prefill_flops_per_token(cfg, 640.0)
    assert abs(got - 100 * flops / (50 * 197e12)) < 1e-12 and 2 < got < 4
    # wasted passes change the program's totals and not this
    obs["window_totals"] = {"dc": 150_000.0, "passes": 900_000.0,
                            "commits": 37_500.0}
    assert sdar_serve_mfu.read(obs, {}) == got


def test_the_cell_is_found_by_name_and_lists_its_metrics():
    bench = H.load_benchmark()
    cell = H.find_cell(bench, "sdar_serve_blocks")
    cfg = H.config_of(bench, cell, rehearse=False)
    assert cfg["hidden_size"] == 2048 and cfg["num_hidden_layers"] >= 5
    assert cfg["reduced"] == ["num_hidden_layers"]
    traffic = H.traffic_of(cell, rehearse=False)
    __import__("benchmarks.drivers." + traffic["driver"])
    names = [m["name"] for m in H.metrics_for(bench, cell["name"],
                                              "per_layer")]
    assert {"fleet_attach_s", "warm_compile_s"} < set(names)
    assert sum(n.startswith("sdar_") for n in names) == 11
    for n in names:
        spec = H.load_json("metrics", n + ".json")
        __import__("benchmarks.readers." + spec["reader"])
    e2e = [m["name"] for m in H.metrics_for(bench, cell["name"],
                                            "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
