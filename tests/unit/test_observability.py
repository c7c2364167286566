"""Unit tests for the observability layer (ISSUE 2): span tracer
semantics, metrics registry (counter/histogram + Prometheus golden),
NTP-style clock-offset estimation on synthetic RTTs, Chrome-trace
export roundtrip, and the codec's optional ``tr`` header."""

import json
import struct

import pytest

from nbdistributed_tpu.messaging import codec
from nbdistributed_tpu.observability.clock import ClockEstimator
from nbdistributed_tpu.observability.export import merge_trace, save_trace
from nbdistributed_tpu.observability.metrics import (MetricsRegistry,
                                                     registry)
from nbdistributed_tpu.observability.spans import Tracer

pytestmark = [pytest.mark.unit, pytest.mark.obs]


# ---------------------------------------------------------------------
# spans


def test_tracer_disabled_records_nothing():
    tr = Tracer()
    assert tr.begin("x") is None
    with tr.span("y") as s:
        assert s is None
    tr.instant("z")
    assert len(tr) == 0
    assert tr.context() is None  # no wire header when off


def test_tracer_nesting_and_ids():
    tr = Tracer()
    tid = tr.start()
    with tr.span("outer", kind="coordinator") as outer:
        assert outer.trace_id == tid
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == tid
    dump = tr.dump()
    assert {s["name"] for s in dump["spans"]} == {"outer", "inner"}
    # inner ended first (stack order) and both have durations set
    assert all(s["dur"] >= 0.0 for s in dump["spans"])


def test_tracer_explicit_wire_parent_wins():
    tr = Tracer()
    tr.start()
    sp = tr.begin("handle/execute", trace_id="remotetid", parent_id="abc")
    tr.end(sp)
    d = tr.dump()["spans"][0]
    assert d["trace_id"] == "remotetid" and d["parent_id"] == "abc"


def test_tracer_activate_crosses_threads():
    import threading
    tr = Tracer()
    tr.start()
    parent = tr.begin("cell/distributed")
    child_parent = []

    def work():
        with tr.activate(parent):
            sp = tr.begin("send/execute")
            child_parent.append(sp.parent_id)
            tr.end(sp)

    t = threading.Thread(target=work)
    t.start()
    t.join()
    tr.end(parent)
    assert child_parent == [parent.span_id]


def test_tracer_start_clears_and_stop_keeps():
    tr = Tracer()
    tr.start()
    tr.end(tr.begin("a"))
    assert tr.stop() == 1
    assert len(tr) == 1          # buffered for dump after stop
    tr.start()
    assert len(tr) == 0          # new session clears


def test_tracer_span_cap():
    from nbdistributed_tpu.observability import spans as spans_mod
    tr = Tracer()
    tr.start()
    old = spans_mod.MAX_SPANS
    spans_mod.MAX_SPANS = 3  # the cap is read at end() time
    try:
        for _ in range(5):
            tr.end(tr.begin("s"))
    finally:
        spans_mod.MAX_SPANS = old
    assert len(tr) == 3
    assert tr.dump()["dropped"] == 2


def test_context_carries_current_span():
    tr = Tracer()
    tr.start()
    with tr.span("outer") as s:
        ctx = tr.context()
        assert ctx == {"tid": s.trace_id, "sid": s.span_id}


# ---------------------------------------------------------------------
# codec tr header


def test_codec_trace_header_roundtrip():
    m = codec.Message(msg_type="execute", data={"code": "1"},
                      trace={"tid": "t1", "sid": "s1"})
    out = codec.decode(codec.encode(m))
    assert out.trace == {"tid": "t1", "sid": "s1"}


def test_codec_no_trace_no_header():
    """The acceptance bar: no wire header emitted unless a trace is
    active — untraced frames stay byte-identical to the old format."""
    frame = codec.encode(codec.Message(msg_type="execute", data="x"))
    hlen = struct.unpack_from("<4sIQ", frame, 0)[1]
    header = json.loads(bytes(frame[codec.HEADER_SIZE:
                                    codec.HEADER_SIZE + hlen]))
    assert "tr" not in header
    assert codec.decode(frame).trace is None


# ---------------------------------------------------------------------
# metrics registry


def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("hits", "help text")
    c.inc()
    c.inc(2)
    assert reg.counter("hits").value == 3  # get-or-create returns same
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("hits")  # kind clash is an error, not silent


def test_labeled_series_are_independent():
    reg = MetricsRegistry()
    reg.counter("msgs", labels={"dir": "tx"}).inc(5)
    reg.counter("msgs", labels={"dir": "rx"}).inc(7)
    j = reg.to_json()
    assert j["counters"]['msgs{dir="tx"}'] == 5
    assert j["counters"]['msgs{dir="rx"}'] == 7


def test_histogram_bucket_placement():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    cum = dict(h.cumulative())
    assert cum["0.01"] == 1
    assert cum["0.1"] == 3
    assert cum["1"] == 4
    assert cum["+Inf"] == 5
    assert h.count == 5
    assert abs(h.sum - 5.605) < 1e-9


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    reg.counter("nbd_wire_bytes_total", "bytes moved",
                {"dir": "tx"}).inc(1024)
    reg.gauge("nbd_dedup_hits").set(2)
    reg.histogram("nbd_cell_seconds", "cell time",
                  buckets=(0.1, 1.0)).observe(0.5)
    # Golden: series sorted by name, HELP only where help was given.
    expected = (
        '# HELP nbd_cell_seconds cell time\n'
        '# TYPE nbd_cell_seconds histogram\n'
        'nbd_cell_seconds_bucket{le="0.1"} 0\n'
        'nbd_cell_seconds_bucket{le="1"} 1\n'
        'nbd_cell_seconds_bucket{le="+Inf"} 1\n'
        'nbd_cell_seconds_sum 0.5\n'
        'nbd_cell_seconds_count 1\n'
        '# TYPE nbd_dedup_hits gauge\n'
        'nbd_dedup_hits 2\n'
        '# HELP nbd_wire_bytes_total bytes moved\n'
        '# TYPE nbd_wire_bytes_total counter\n'
        'nbd_wire_bytes_total{dir="tx"} 1024\n'
    )
    assert reg.prometheus_text() == expected


def test_wire_hook_counts_actual_socket_writes():
    """tx is counted per ACTUAL socket write (fan-out = one per rank;
    chaos drops = zero, duplicates = two), rx per decoded frame."""
    import threading

    from nbdistributed_tpu.messaging.transport import (
        CoordinatorListener, WorkerChannel)
    from nbdistributed_tpu.observability.metrics import install_wire_hook
    from nbdistributed_tpu.resilience.faults import FaultPlan

    install_wire_hook()
    reg = registry()

    def total(name, direction):
        return sum(v for k, v in reg.to_json()["counters"].items()
                   if k.startswith(name) and f'dir="{direction}"' in k)

    listener = CoordinatorListener()
    connected = threading.Event()
    ranks: set = set()

    def on_conn(r):
        ranks.add(r)
        if len(ranks) == 2:
            connected.set()

    listener.on_connect = on_conn
    listener.start()
    chans = [WorkerChannel("127.0.0.1", listener.port, rank=r)
             for r in (0, 1)]
    try:
        assert connected.wait(10)
        # fan-out: one encode, TWO socket writes -> two tx counts
        before = total("nbd_wire_messages_total", "tx")
        listener.send_to_ranks([0, 1],
                               codec.Message(msg_type="execute", data="x"))
        assert total("nbd_wire_messages_total", "tx") == before + 2
        # duplicate plan: one send call -> two actual writes
        listener.fault_plan = FaultPlan(duplicate=1.0, exempt=())
        before = total("nbd_wire_messages_total", "tx")
        listener.send_to_rank(0, codec.Message(msg_type="execute"))
        assert total("nbd_wire_messages_total", "tx") == before + 2
        # drop plan: the frame never touched a socket -> zero counts
        listener.fault_plan = FaultPlan(drop=1.0, exempt=())
        before = total("nbd_wire_messages_total", "tx")
        listener.send_to_rank(0, codec.Message(msg_type="execute"))
        assert total("nbd_wire_messages_total", "tx") == before
        listener.fault_plan = None
        # rx side: each frame the worker channel decodes counts once
        before = total("nbd_wire_messages_total", "rx")
        before_b = total("nbd_wire_bytes_total", "rx")
        msg = chans[0].recv(timeout=10)
        assert msg.msg_type == "execute"
        assert total("nbd_wire_messages_total", "rx") == before + 1
        assert total("nbd_wire_bytes_total", "rx") > before_b
    finally:
        for c in chans:
            c.close()
        listener.close()


# ---------------------------------------------------------------------
# clock offset estimation


def test_clock_estimator_recovers_offset_from_noisy_rtts():
    import random
    rng = random.Random(7)
    est = ClockEstimator()
    true_offset = 0.350  # worker clock runs 350 ms ahead
    t = 1000.0
    for _ in range(200):
        t += rng.uniform(0.01, 0.05)
        # asymmetric network + handler time: the reply stamp sits
        # somewhere inside the interval, not at the midpoint
        up = rng.uniform(0.0005, 0.003)
        handler = rng.expovariate(1 / 0.002)
        down = rng.uniform(0.0005, 0.003)
        t_send = t
        t_remote = t_send + up + handler + true_offset
        t_recv = t_send + up + handler + down
        est.add(1, t_send, t_remote, t_recv)
    assert abs(est.offset(1) - true_offset) < 0.005
    stats = est.stats()[1]
    assert stats["samples"] == 200
    assert stats["min_rtt_s"] is not None


def test_clock_estimator_defaults_and_negative_rtt():
    est = ClockEstimator()
    assert est.offset(3) == 0.0          # no samples: identity merge
    est.add(0, 100.0, 100.5, 99.0)       # clock stepped: rejected
    assert est.offsets() == {}
    est.add(0, 100.0, 100.2, 100.01)
    assert abs(est.offset(0) - 0.195) < 1e-9


def test_clock_estimator_keeps_lowest_rtt_samples():
    est = ClockEstimator(keep=2)
    # Two clean samples with offset ~0.1, then many inflated ones with
    # a wild offset — the min-RTT filter must ignore the inflated ones.
    est.add(0, 0.0, 0.105, 0.01)
    est.add(0, 1.0, 1.105, 1.01)
    for i in range(20):
        est.add(0, 10.0 + i, 15.0 + i, 12.0 + i)  # rtt 2s, offset 4s
    assert abs(est.offset(0) - 0.1) < 1e-6


# ---------------------------------------------------------------------
# chrome trace export


def _dump_with(spans, instants=(), trace_id="t0"):
    return {"trace_id": trace_id, "spans": list(spans),
            "instants": list(instants), "dropped": 0}


def test_merge_trace_is_valid_chrome_format(tmp_path):
    coord = _dump_with([
        {"name": "send/execute", "kind": "coordinator", "tid": 0,
         "trace_id": "t0", "span_id": "c1", "t0": 100.0, "dur": 0.5},
    ])
    ranks = {
        r: _dump_with([
            {"name": "handle/execute", "kind": "worker", "tid": 0,
             "trace_id": "t0", "span_id": f"w{r}", "parent_id": "c1",
             "t0": 100.25 + 0.2, "dur": 0.1},
        ])
        for r in (0, 1)
    }
    merged = merge_trace(coord, ranks, {0: 0.2, 1: 0.2},
                         coordinator_faults=[
                             {"ts": 100.1, "index": 3,
                              "actions": ["drop"], "kind": "execute"}])
    evs = merged["traceEvents"]
    # every event is well-formed chrome-trace (metadata events carry
    # no timestamp, by the format)
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] != "M":
            assert "ts" in e
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {-1, 0, 1}
    # clock correction puts the worker span INSIDE the coordinator one
    c = next(e for e in spans if e["pid"] == -1)
    for r in (0, 1):
        w = next(e for e in spans if e["pid"] == r)
        assert c["ts"] <= w["ts"] <= c["ts"] + c["dur"]
        # parent/span ids surfaced for Perfetto's detail pane
        assert w["args"]["parent_id"] == "c1"
    faults = [e for e in evs if e["ph"] == "i" and e["cat"] == "fault"]
    assert len(faults) == 1 and faults[0]["name"] == "fault:drop"
    # file roundtrip: valid JSON, event count excludes metadata
    path = str(tmp_path / "trace.json")
    n = save_trace(path, merged)
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["traceEvents"] == evs
    assert n == len([e for e in evs if e["ph"] != "M"])


def test_merge_trace_rebases_timestamps():
    coord = _dump_with([{"name": "a", "kind": "", "tid": 0,
                         "trace_id": "t", "span_id": "s",
                         "t0": 1.75e9, "dur": 0.001}])
    merged = merge_trace(coord, {}, {})
    ev = [e for e in merged["traceEvents"] if e["ph"] == "X"][0]
    assert ev["ts"] == 0.0  # rebased to the earliest event
    assert merged["otherData"]["base_unix_s"] == 1.75e9


def test_merge_trace_empty_inputs():
    merged = merge_trace(None, {}, {})
    assert merged["traceEvents"] == []


def test_fault_plan_records_timestamped_events():
    from nbdistributed_tpu.resilience.faults import FaultPlan
    plan = FaultPlan(seed=3, drop=1.0)  # every frame dropped
    sent = []
    plan.transmit(b"xxxx", sent.append, kind="execute")
    assert sent == []
    evs = plan.events()
    assert len(evs) == 1
    assert evs[0]["actions"] == ["drop"]
    assert evs[0]["kind"] == "execute"
    assert evs[0]["ts"] > 0


# ---------------------------------------------------------------------
# named phases of the serving tick (ISSUE 25)


def test_phase_is_the_null_context_with_tracer_off_and_no_jax(
        monkeypatch):
    import sys

    from nbdistributed_tpu.observability import spans
    assert not spans.tracer().enabled
    # a process that never imported jax: the gateway, the kernel
    monkeypatch.setattr(spans, "_ANNOTATION", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    assert spans.phase("serve/tick/place", 3) is spans._NULL_CTX
    assert spans.phase("serve/tick", 3, wall=1.5) is spans._NULL_CTX
    assert spans._ANNOTATION is None        # and it imported nothing


def test_phase_is_the_bare_annotation_where_jax_is_imported():
    import jax.profiler

    from nbdistributed_tpu.observability import spans
    assert not spans.tracer().enabled
    for args in (("serve/step/sync", 7), ("serve/step/sync",),
                 ("serve/step/admit", 7, 12.5)):
        ctx = spans.phase(*args)
        assert type(ctx) is jax.profiler.TraceAnnotation
        with ctx:                   # no profile runs: a flag check
            pass


def test_phase_records_tick_wall_and_parent_when_the_tracer_is_on(
        monkeypatch):
    from nbdistributed_tpu.observability import spans
    tr = Tracer()
    tr.start()
    monkeypatch.setattr(spans, "_TRACER", tr)
    with spans.phase("serve/tick", 9) as outer:
        with spans.phase("serve/tick/place", 9, wall=5.0) as inner:
            assert inner.parent_id == outer.span_id
    by_name = {d["name"]: d for d in tr.dump()["spans"]}
    assert by_name["serve/tick"]["attrs"] == {"tick": 9}
    assert by_name["serve/tick/place"]["attrs"] == {"tick": 9,
                                                    "wall": 5.0}
    assert by_name["serve/tick/place"]["kind"] == "serve"
    assert "parent_id" not in by_name["serve/tick"]


def test_tick_spans_chain_from_the_gateway_into_the_worker(
        tmp_path, monkeypatch):
    """gateway ``serve/tick/roundtrip`` -> the worker's
    ``handle/serve_step`` (through the ``tr`` header, as the worker's
    loop opens it) -> ``serve/step/*``, all under one ``tick``."""
    import time

    import jax
    import jax.numpy as jnp

    from nbdistributed_tpu.gateway.serving import ServingManager
    from nbdistributed_tpu.messaging import Message
    from nbdistributed_tpu.models import init_params, tiny_config
    from nbdistributed_tpu.models.serving import DecodeServer
    from nbdistributed_tpu.observability import spans
    from nbdistributed_tpu.runtime import worker as worker_mod

    tr = Tracer()
    tr.start()
    monkeypatch.setattr(spans, "_TRACER", tr)
    cfg = tiny_config(dtype=jnp.float32, use_flash=False, n_layers=1)
    server = DecodeServer(init_params(jax.random.PRNGKey(0), cfg), cfg,
                          max_batch=2, max_len=32, pad_to=4,
                          kv_block_tokens=8)
    w = object.__new__(worker_mod.DistributedWorker)
    w.rank, w._serve_snap = 0, None
    w._serve = {"serve": worker_mod._WorkerServe(server)}

    class BridgeComm:
        """One in-process worker behind the comm's surface; does what
        the coordinator's transmit and the worker's loop do to a
        request's trace context."""
        num_workers = 1
        tracer = tr

        def dead_ranks(self):
            return set()

        def post(self, ranks, msg_type, data=None):
            pass

        def send_to_ranks(self, ranks, msg_type, data=None, **kw):
            if msg_type != "serve_step":
                return {0: Message(msg_type="response",
                                   data={"status": "open"})}
            msg = Message(msg_type=msg_type, data=data)
            msg.trace = tr.context()
            span = tr.begin("handle/serve_step", kind="worker",
                            trace_id=msg.trace["tid"],
                            parent_id=msg.trace["sid"])
            with tr.activate(span):
                reply = w._handle_serve_step(msg)
            tr.end(span)
            return {0: reply}

    mgr = ServingManager(BridgeComm(), str(tmp_path), world_size=1,
                         max_batch=2, max_len=32, pad_to=4, steps=2,
                         kv_block_tokens=8, step_timeout=30.0)
    mgr.start()
    try:
        rid = mgr.submit("t1", [5, 9, 2], 6)["rid"]
        deadline = time.monotonic() + 60
        while not mgr.result(rid)["done"]:
            assert time.monotonic() < deadline
            time.sleep(0.02)
    finally:
        mgr.stop()
    sp = tr.dump()["spans"]
    by_id = {d["span_id"]: d for d in sp}
    handles = [d for d in sp if d["name"] == "handle/serve_step"]
    assert len(handles) >= 2
    for h in handles:
        rt = by_id[h["parent_id"]]
        assert rt["name"] == "serve/tick/roundtrip"
        tick = rt["attrs"]["tick"]
        assert by_id[rt["parent_id"]]["name"] == "serve/tick"
        assert by_id[rt["parent_id"]]["attrs"]["tick"] == tick
        kids = [d for d in sp if d.get("parent_id") == h["span_id"]]
        assert {d["name"] for d in kids} >= {
            "serve/step/admit", "serve/step/collect",
            "serve/step/prefill"}
        assert all(d["name"].startswith("serve/step/") for d in kids)
        assert {d["attrs"]["tick"] for d in kids} == {tick}
        assert sum("wall" in d["attrs"] for d in kids) == 1
    step_names = {d["name"] for d in sp
                  if d["name"].startswith("serve/step/")}
    assert step_names == {"serve/step/" + p for p in (
        "admit", "prefill", "dispatch", "sync", "emit", "collect")}
    tick_names = {d["name"] for d in sp
                  if d["name"].startswith("serve/tick")}
    assert tick_names == {"serve/tick"} | {"serve/tick/" + p for p in (
        "place", "roundtrip", "apply", "util")}
    # one set of names, three ways: the same ticks are in the ring
    tk = mgr.describe()["lat"]["summary"]["ticks"]
    assert tk["count"] == len(handles) and tk["sync"]["mean"] > 0


def test_fleet_trace_drives_the_tracer_and_every_rank():
    """``%dist_trace`` for a fleet or a pool is one function over the
    coordinator-side comm; its results cross the wire (string keys)."""
    import types

    from nbdistributed_tpu.observability.export import fleet_trace

    class Comm:
        def __init__(self):
            self.tracer = Tracer()
            self.sent = []
            self.clock = types.SimpleNamespace(
                offsets=lambda: {0: 0.001, 1: -0.002})

        def fault_plan(self):
            return None

        def send_to_all(self, msg_type, data, timeout=None):
            self.sent.append((msg_type, dict(data)))
            reply = {"status": "tracing", "spans": 2}
            if data["action"] == "dump":
                reply = {"trace": {"trace_id": "t", "spans": [
                    {"name": "serve/step/sync", "kind": "serve",
                     "tid": 0, "trace_id": "t", "span_id": "s",
                     "t0": 10.0, "dur": 0.5,
                     "attrs": {"tick": 3}}],
                    "instants": [], "dropped": 0}}
            return {r: types.SimpleNamespace(data=reply)
                    for r in (0, 1)}

    comm = Comm()
    tid = fleet_trace(comm, "start")["trace_id"]
    assert comm.tracer.enabled and comm.tracer.trace_id == tid
    assert comm.sent[0] == ("trace", {"action": "start",
                                      "trace_id": tid})
    with comm.tracer.span("serve/tick/roundtrip", attrs={"tick": 3}):
        pass
    st = fleet_trace(comm, "status")
    assert st["enabled"] and st["spans"] == 1
    assert st["ranks"] == {"0": {"status": "tracing", "spans": 2},
                           "1": {"status": "tracing", "spans": 2}}
    saved = fleet_trace(comm, "save")
    assert saved["spans"] == 1 and saved["ranks"] == {"0": 1, "1": 1}
    assert saved["offsets_ms"] == {"0": 1.0, "1": -2.0}
    json.dumps(saved)                           # crosses the wire
    names = [e["name"] for e in saved["merged"]["traceEvents"]
             if e["ph"] == "X"]
    assert names.count("serve/step/sync") == 2
    assert "serve/tick/roundtrip" in names
    ticks = [e["args"].get("tick") for e in
             saved["merged"]["traceEvents"] if e["ph"] == "X"]
    assert ticks == [3, 3, 3]
    out = fleet_trace(comm, "stop")
    assert not comm.tracer.enabled and out["spans"] == 1
