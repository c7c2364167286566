"""The flash kernel's operations and bytes against a count by hand at
Mistral's head shapes, and no share can pass 100%."""

from benchmarks import harness as H
from benchmarks.model import flops

CFG = H.load_json("configs", "mistral7b-train.json")


def test_flash_forward_counts_agree_with_a_count_by_hand():
    c = flops.flash_fwd_counts(CFG, batch=1, seq_len=4096)
    # causal, window 4096 >= S: 4096 * 4097 / 2 = 8,390,656 pairs per head;
    # 32 heads, two products of 128 multiply-adds each, 2 FLOPs apiece
    assert flops.causal_pairs(4096, 4096) == 8_390_656
    assert c["flops"] == 32 * 8_390_656 * 128 * 2 * 2 == 137_472_507_904
    # q and o: 2 * 4096 * 32 * 128 * 2 bytes; k and v: 2 * 4096 * 8 * 128 * 2;
    # lse: 4096 * 32 * 4
    assert c["bytes"] == 67_108_864 + 16_777_216 + 524_288
    r = flops.roofline_seconds(c, H.peak_for("TPU v5 lite"))
    assert r["bound"] == "compute" and abs(r["seconds"] - 6.978e-4) < 1e-6


def test_window_shorter_than_the_sequence_keeps_fewer_pairs():
    assert flops.causal_pairs(8, 4) == 10 + 4 * 4
    assert flops.causal_pairs(8, None) == 36


def test_fwd_flops_per_token_by_hand():
    # one layer: q,o 2*(2*4096*4096); k,v 2*(2*4096*1024); mlp 3*2*4096*14336
    per_layer = 4 * 4096 * 4096 + 4 * 4096 * 1024 + 6 * 4096 * 14336
    attn = 4 * 2048 * 32 * 128
    want = 3 * (per_layer + attn) + 2 * 4096 * 32000
    assert flops.fwd_flops_per_token(CFG, 4096) == want
