"""The reduction from trace events to busy, idle and per-kernel time,
on a trace small enough to count by hand and on a slice recorded on the
chip (``recorded_trace.json``, written by ``record_trace.py``)."""

import json
import os

from benchmarks.readers import trace_idle, trace_share
from benchmarks.trace import reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def test_by_hand():
    k = ('%closed_call.1 = (bf16[8,4,128,128]{3,2,1,0}, f32[8,4,128]{2,1,0}) '
         'custom-call(s32[2]{0} %x), custom_call_target="tpu_custom_call"')
    ev = [[DEV, "XLA Ops", "%while.1 = () while(%a)", 0, 100],
          [DEV, "XLA Ops", "%fusion.1 = f32[4]{0} fusion(%b)", 10, 30],
          [DEV, "XLA Ops", k, 50, 40],
          [DEV, "XLA Ops", "%fusion.1 = f32[4]{0} fusion(%b)", 150, 50],
          [DEV, "XLA Modules", "jit_step(1)", 0, 200],
          ["/host:CPU", "python", "fetch", 100, 50]]
    r = T.mean_over_chips([T.reduce(ev)])
    assert abs(r["busy_s"] - 150e-9) < 1e-15       # [0,100) and [150,200)
    assert abs(r["window_s"] - 200e-9) < 1e-15
    # the while holds 70 ns of children: 30 ns are its own
    assert abs(r["ops"]["while.1 while"] - 30e-9) < 1e-15
    assert abs(r["ops"]["fusion.1 fusion"] - 80e-9) < 1e-15
    assert abs(r["ops"]["closed_call.1 custom-call"] - 40e-9) < 1e-15
    assert abs(sum(r["ops"].values()) - r["busy_s"]) < 1e-15
    assert r["gaps"][0] == ["fetch", 50e-9]
    obs = {"trace": r}
    assert abs(trace_idle.read(obs, {}) - 25.0) < 1e-9
    share = trace_share.read(obs, {"match": ["tpu_custom_call"]})
    assert abs(share - 100 * 40 / 150) < 1e-9
    assert trace_share.read(obs, {"match": ["all-reduce"]}) is None


def test_recorded_slice_reads_the_same():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    r = T.mean_over_chips([T.reduce(rec["events"])])
    want = rec["expected"]
    assert abs(r["busy_s"] - want["busy_s"]) < 1e-12
    assert abs(r["window_s"] - want["window_s"]) < 1e-12
    assert r["busy_s"] <= r["window_s"]
    assert abs(sum(r["ops"].values()) - r["busy_s"]) < 1e-9
    for name, seconds in want["ops"].items():
        assert abs(r["ops"][name] - seconds) < 1e-12, name
    mosaic = trace_share.read({"trace": r}, {"match": ["tpu_custom_call"]})
    assert abs(mosaic - want["mosaic_time_share"]) < 1e-9 and mosaic < 100
