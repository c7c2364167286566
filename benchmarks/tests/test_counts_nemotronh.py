"""Nemotron-3-Nano's counts against counts by hand, at the published
widths and the cell's cut, and the readers on observations made by
hand."""

import json
import os

from benchmarks.model import nemotronh_flops as F
from benchmarks.readers import (nemotronh_expert_read_roofline,
                                nemotronh_serve_mfu,
                                nemotronh_state_update_roofline,
                                nemotronh_time_share)

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
STATE = r"f32\[[0-9,]*64,128\]"
STEPS = {"step_match": "^%gmm", "step_calls": 12}


def _cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "nemotron3-nano-serve.json")) as f:
        return json.load(f)


def test_an_expert_is_two_matrices_of_2688_by_1856():
    assert F.expert_bytes(_cfg()) == 2 * 2688 * 1856 * 2 == 19_955_712


def test_expert_read_counts_by_hand():
    # one step at 128 rows: all 64 held experts of 6 layers, 384 rows
    # routed a layer (half of 128 x 6)
    c = F.expert_read_counts(_cfg(), 64 * 6, 384 * 6)
    assert c["bytes"] == 384 * 19_955_712 + 2304 * 2 * (2688 + 1856) * 2
    assert c["flops"] == 2304 * 2 * 2 * 2688 * 1856
    t = F.roofline_seconds(c, PEAK)
    # 7.7 GB at 819 GB/s (ISSUE 34's budget: 9.4 ms)
    assert t["bound"] == "memory" and 9.3e-3 < t["seconds"] < 9.5e-3


def test_a_row_of_state_is_2_134_mb_a_layer():
    cfg = _cfg()
    assert F.state_row_bytes(cfg) == 64 * 64 * 128 * 4 + 3 * 6144 * 2 \
        == 2_134_016
    # a step at 128 rows reads and writes 6 layers' worth: 3.28 GB, 4 ms
    step = 2 * 128 * 6 * F.state_row_bytes(cfg)
    t = F.roofline_seconds(F.state_update_counts(cfg, step), PEAK)
    assert t["bound"] == "memory" and 3.9e-3 < t["seconds"] < 4.1e-3


def test_matrices_by_kind_add_up_to_the_models():
    cfg = _cfg()
    assert F.matmul_params(cfg, "mamba2", 3) == (
        2688 * (4096 + 6144 + 64) + 4096 * 2688) == 38_707_200
    assert F.matmul_params(cfg, "attention", 3) == (
        2 * 2688 * 4096 + 2 * 2688 * 256) == 23_396_352
    # router, shared expert and three routed experts of the six chosen
    assert F.matmul_params(cfg, "experts", 3) == (
        2688 * 128 + 2 * 2688 * 3712 + 3 * 2 * 2688 * 1856)
    # a decode token at position 0, three of six choices kept: twice
    # (6 x 38.7 M + 2 x 23.4 M + 6 x 50.2 M + the head's 352 M), one
    # key a head in two layers, and six state updates of 2.6 M values
    f0 = F.decode_flops_per_token(cfg, 0, 3.0)
    assert f0 == 2 * 932_757_504 + 2 * 32 * 128 * 4 + 6 * (
        5 * 64 * 64 * 128 + 2 * 4 * 6144) == 1_881_571_328
    grown = F.decode_flops_per_token(cfg, 1999, 3.0) - f0
    assert grown == 2 * 1999 * 32 * 128 * 2 * 2
    # a prompt token runs 13 of 14 layers and no head
    assert 0.55 < F.prefill_flops_per_token(cfg, 3.0, 1280) / f0 < 0.70
    # all six choices kept costs three more experts a layer
    assert (F.decode_flops_per_token(cfg, 0, 6.0) - f0
            == 2 * 6 * 3 * 2 * 2688 * 1856)


def _obs(seconds, calls, totals, text="%gmm.3 = bf16[768,1920]"
         " custom-call(", marks=None):
    ops = {"op custom-call": [seconds, calls, text]}
    if marks is not None:
        ops["gmm.9 custom-call"] = [
            1.0, marks, "%gmm.9 = bf16[768,2688] custom-call("]
    return {"cfg": _cfg(), "peak": PEAK, "chips": 1,
            "slice_totals": totals,
            "trace_by_module": {"jit_nbd_decode_step_paged": ops}}


def _totals(steps):
    return {"steps": steps, "dc": 128.0 * steps, "pf": 0.0, "chunks": 0.0,
            "state_bytes": 2.0 * 128 * 6 * 2_134_016 * steps,
            "moe_touched": 64.0 * steps, "moe_rows": 384.0 * steps}


def test_expert_roofline_reader_counts_the_slice_and_no_more_than_it_held():
    args = {"match": "^%gmm", **STEPS}
    # ten steps' 120 calls in 188 ms: half the 9.4 ms a step
    got = nemotronh_expert_read_roofline.read(_obs(0.188, 120, _totals(10)),
                                              args)
    assert 49.5 < got < 50.5
    # the program counted twelve steps where the trace held ten: the
    # trace's count bounds the bytes; where it counted eight, its own
    more = nemotronh_expert_read_roofline.read(
        _obs(0.188, 120, _totals(12)), args)
    assert abs(more - got) < 1e-9
    fewer = nemotronh_expert_read_roofline.read(
        _obs(0.188, 120, _totals(8)), args)
    assert abs(fewer - 0.8 * got) < 1e-9
    # nothing to read: no trace, no totals (the parent keeps none)
    assert nemotronh_expert_read_roofline.read(
        {"cfg": _cfg(), "peak": PEAK}, args) is None
    assert nemotronh_expert_read_roofline.read(
        _obs(0.188, 120, None), args) is None


def test_state_update_reader_by_hand():
    args = {"match": STATE, **STEPS}
    text = ("%multiply_reduce_fusion.5 = (f32[128,64,64]{2,1,0}, "
            "f32[128,64,64,128]{3,2,1,0}) fusion(")
    # ten steps (120 grouped-matmul calls) whose 60 state fusions took
    # 50 ms: 4.0 ms a step at the peak over 5.0
    got = nemotronh_state_update_roofline.read(
        _obs(0.050, 60, _totals(10), text, marks=120), args)
    assert 79.0 < got < 81.0
    # K/V pages are bfloat16 and carry no such shape
    assert nemotronh_state_update_roofline.read(
        _obs(0.050, 60, _totals(10), "%dus = bf16[2,8193,2,64,128] d(",
             marks=120), args) is None


def test_serve_mfu_reader_by_hand():
    cfg = _cfg()
    obs = {"cfg": cfg, "peak": PEAK, "chips": 1,
           "served": {"seconds": 50.0, "decode_tokens": 170_000,
                      "prompt_tokens": 230_000, "prompts": 180,
                      "mean_position": 1900.0},
           "window_totals": {"dc": 171_000.0, "moe_rows": 513_000.0}}
    want = (170_180 * F.decode_flops_per_token(cfg, 1900.0, 3.0)
            + 230_000 * F.prefill_flops_per_token(cfg, 3.0, 230_000 / 180)
            ) / (50 * 197e12)
    got = nemotronh_serve_mfu.read(obs, {})
    assert abs(got - 100 * want) < 1e-9 and 5.0 < got < 7.0
    del obs["window_totals"]
    assert nemotronh_serve_mfu.read(obs, {}) is None


def test_time_share_reader_counts_each_programs_own_text_and_no_loop_twice():
    state = "(f32[128,64,64]{2,1,0}, f32[128,64,64,128]{3,2,1,0}) fusion("
    obs = {"trace": {"busy_s": 2.0}, "trace_by_module": {
        # one name, two programs, two operations: the step's is a state
        # update, the chunk's is not
        "jit_nbd_decode_step_paged": {
            "multiply_reduce_fusion.1 fusion": [
                0.3, 60, "%multiply_reduce_fusion.1 = " + state]},
        "jit_nbd_prefill_paged": {
            "multiply_reduce_fusion.1 fusion": [
                0.5, 60, "%multiply_reduce_fusion.1 = bf16[512,2688] fusion("],
            # a loop's time is its body's: the body's operation counts
            "while.3 while": [
                0.2, 30, "%while.3 = (s32[], f32[1,8,8,64,128]) while("],
            "fusion.7 fusion": [
                0.1, 120, "%fusion.7 = f32[1,8,8,64,128]{4,3,2,1,0} fusion("]}}}
    got = nemotronh_time_share.read(obs, {"match": [STATE]})
    assert abs(got - 100 * 0.4 / 2.0) < 1e-9
    assert nemotronh_time_share.read({"trace": {"busy_s": 2.0}},
                                     {"match": [STATE]}) is None
    assert nemotronh_time_share.read(obs, {"match": ["^%gmm"]}) is None
