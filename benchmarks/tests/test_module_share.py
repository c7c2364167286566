"""``trace_module_share``: seconds of the programs whose name matches
over the seconds of all programs, on a trace small enough to count by
hand and on the slice recorded on the chip (``recorded_trace.json``:
two runs of one program, ``jit_step_on_mesh``)."""

import json
import os

from benchmarks.readers import trace_module_share
from benchmarks.trace import reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def test_by_hand():
    ev = [[DEV, "XLA Ops", "%fusion.1 = f32[4]{0} fusion(%b)", 0, 10],
          [DEV, "XLA Modules", "jit_nbd_decode_step_paged(11)", 0, 200],
          [DEV, "XLA Modules", "jit_nbd_prefill_paged(22)", 200, 50],
          [DEV, "XLA Modules", "jit_nbd_decode_step_paged(11)", 250, 200],
          [DEV, "XLA Modules", "jit_nbd_prefill_paged(22)", 450, 30],
          [DEV, "XLA Modules", "jit__at_set(33)", 480, 20]]
    obs = {"trace": T.mean_over_chips([T.reduce(ev)])}
    # 50 + 30 of 200 + 50 + 200 + 30 + 20 = 500 ns
    share = trace_module_share.read(obs, {"match": "nbd_prefill"})
    assert abs(share - 100 * 80 / 500) < 1e-9
    share = trace_module_share.read(obs, {"match": "nbd_decode_step"})
    assert abs(share - 100 * 400 / 500) < 1e-9
    # a program without its own name (the parent's jit_fn) reads nothing
    assert trace_module_share.read(obs, {"match": "jit_fn"}) is None
    assert trace_module_share.read({}, {"match": "x"}) is None
    assert trace_module_share.read({"trace": {"modules": {}}},
                                   {"match": "x"}) is None


def test_recorded_slice():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    r = T.mean_over_chips([T.reduce(rec["events"])])
    runs = [e for e in rec["events"] if e[1] == "XLA Modules"]
    assert len(runs) == 2 and {e[2][:16] for e in runs} == {
        "jit_step_on_mesh"}
    # by hand: the two runs last 195,562,746 ns and 195,579,473 ns and
    # there is no other program, so the one name is all of it
    assert abs(sum(r["modules"].values())
               - (195562746 + 195579473) / 1e9) < 1e-12
    obs = {"trace": r}
    assert trace_module_share.read(
        obs, {"match": "jit_step_on_mesh"}) == 100.0
    assert trace_module_share.read(obs, {"match": "nbd_prefill"}) is None
