"""``trace/by_module.py`` on a hand-made list of events: operations go
to the program that contains their start, names that two programs share
stay apart, and what lies outside every program is left out."""

from benchmarks.trace.by_module import chips_of, ops_by_module

P = "/device:TPU:0"
EVENTS = [
    [P, "XLA Modules", "jit_nbd_prefill_paged(11)", 0, 100],
    [P, "XLA Modules", "jit_nbd_decode_step_paged(22)", 200, 50],
    [P, "XLA Modules", "jit_nbd_decode_step_paged(22)", 300, 50],
    [P, "XLA Ops", "%ragged-dot-none.3 = bf16[4096,768]{1,0} custom-call(%a)", 10, 40],
    [P, "XLA Ops", "%ragged-dot-none.3 = bf16[256,768]{1,0} custom-call(%a)", 210, 5],
    [P, "XLA Ops", "%ragged-dot-none.3 = bf16[256,768]{1,0} custom-call(%a)", 310, 7],
    [P, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(%b)", 150, 10],     # between programs
    ["/host:CPU", "python3", "serve/step/sync", 0, 400],
]


def test_operations_go_to_the_program_that_ran_them():
    got = ops_by_module(EVENTS)
    assert set(got) == {"jit_nbd_prefill_paged", "jit_nbd_decode_step_paged"}
    pre = got["jit_nbd_prefill_paged"]["ragged-dot-none.3 custom-call"]
    dec = got["jit_nbd_decode_step_paged"]["ragged-dot-none.3 custom-call"]
    assert pre[:2] == [40e-9, 1] and "4096" in pre[2]
    assert abs(dec[0] - 12e-9) < 1e-15 and dec[1] == 2 and "[256," in dec[2]
    assert chips_of(EVENTS) == 1
