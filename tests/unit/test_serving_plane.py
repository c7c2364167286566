"""Serving-plane units (ISSUE 11): the journal, the offset-dedup
merge, and the ServingManager's admission/failover/delivery machinery
driven against a fake comm — no pool, no jax, no sleeps beyond the
driver's own polling.

The fake workers decode a DETERMINISTIC position-weighted stream
(next token is a function of the whole sequence so far), which mirrors
the property the real greedy decoder has: re-prefilling from
``prompt + emitted-prefix`` continues the stream bit-identically.
That is exactly what makes journal-replay failover exact.
"""

from __future__ import annotations

import threading
import time
import types

import pytest

from nbdistributed_tpu.gateway.serving import (ServeJournal,
                                               ServingManager,
                                               journal_path,
                                               merge_emission)
from nbdistributed_tpu.messaging.coordinator import WorkerDied
from nbdistributed_tpu.observability.metrics import MetricsRegistry

pytestmark = [pytest.mark.unit, pytest.mark.serve, pytest.mark.gateway]


def next_tok(seq: list[int]) -> int:
    """Deterministic 'model': the continuation depends on the WHOLE
    sequence, so prompt+prefix re-admission must reproduce it."""
    return (sum((i + 1) * t for i, t in enumerate(seq)) + 7) % 50


def expected_stream(prompt: list[int], n: int) -> list[int]:
    seq = list(prompt)
    out = []
    for _ in range(n):
        t = next_tok(seq)
        out.append(t)
        seq.append(t)
    return out


# ----------------------------------------------------------------------
# journal + merge


def test_merge_emission_dedup_and_gap():
    # Fresh emission.
    assert merge_emission(0, 0, 0, [1, 2]) == ([1, 2], 0)
    # Append at the cursor.
    assert merge_emission(2, 0, 2, [3, 4]) == ([3, 4], 0)
    # Replayed overlap: the first 2 are already delivered.
    assert merge_emission(2, 0, 0, [1, 2, 3]) == ([3], 2)
    # Fully duplicated emission.
    assert merge_emission(3, 0, 0, [1, 2, 3]) == ([], 3)
    # Re-admission base: worker offset 0 maps to global offset 4.
    assert merge_emission(4, 4, 0, [9]) == ([9], 0)
    # Gap: refused, not silently journaled around.
    new, dup = merge_emission(1, 0, 3, [8])
    assert new is None and dup == 0


def test_journal_roundtrip_and_torn_tail(tmp_path):
    path = journal_path(str(tmp_path), "serve")
    j = ServeJournal(path)
    j.accept("r0", "t1", [5, 9], 4, 2)
    j.emit("r0", 0, [11, 12])
    j.accept("r1", "t2", [7], 3, 0)
    j.emit("r1", 0, [13])
    j.done("r1", "completed")
    j.close()
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"e": "emit", "rid": "r0", "o"')  # torn tail
    state = ServeJournal.load(path)
    assert state["r0"]["tokens"] == [11, 12]
    assert state["r0"]["done"] is None
    assert state["r1"] == {"tenant": "t2", "prompt": [7], "max_new": 3,
                           "prio": 0, "tokens": [13],
                           "done": "completed"}
    plan = ServeJournal.unfinished(state)
    assert plan == [{"rid": "r0", "tenant": "t1",
                     "prompt": [5, 9, 11, 12], "max_new": 2,
                     "base": 2, "prio": 2}]


def test_journal_load_dedups_replayed_emissions(tmp_path):
    path = journal_path(str(tmp_path), "serve")
    j = ServeJournal(path)
    j.accept("r0", "t", [1], 4, 0)
    j.emit("r0", 0, [10, 11])
    j.emit("r0", 0, [10, 11, 12])   # replayed + one new token
    j.emit("r0", 3, [13])
    j.close()
    state = ServeJournal.load(path)
    assert state["r0"]["tokens"] == [10, 11, 12, 13]


# ----------------------------------------------------------------------
# fake pool


class FakeComm:
    """A fake CommunicationManager speaking the serve_* protocol with
    per-rank in-memory 'workers' running the deterministic stream
    above.  Per-tick emission is capped so requests stay mid-decode
    long enough to be killed."""

    def __init__(self, num_workers: int = 2, per_tick: int = 2,
                 tick_delay: float = 0.0):
        self.num_workers = num_workers
        self.per_tick = per_tick
        self.tick_delay = tick_delay  # slow decode so tests can
        #                               interleave mid-stream faults
        self._dead: set[int] = set()
        self._lock = threading.Lock()
        self.open_fail_ranks: set[int] = set()  # serve_open errors
        # rank -> {rid: {"seq": [...], "emitted": n, "max": n}}
        self._srv: dict[int, dict] = {}
        self._replay: dict[str, dict] = {}
        self.overlap_next_reply = 0   # test hook: re-send n tokens
        self.fail_next = 0            # test hook: raise TimeoutError
        self.steps_seen: list[dict] = []
        # ISSUE 25: a worker that speaks the tick account echoes the
        # payload's seq with its phases; seqs in slow_seqs report a
        # sync of slow_sync_s (a stalled chip), serve_open the view.
        self.tick_ph: dict | None = None
        self.slow_seqs: set[int] = set()
        self.slow_sync_s = 5.0
        self.kv_view_bytes = 0
        self._ticked: set[int] = set()

    # --- the surface ServingManager uses ------------------------------

    def dead_ranks(self):
        return set(self._dead)

    def kill(self, rank: int):
        with self._lock:
            self._dead.add(rank)
            self._srv.pop(rank, None)

    def post(self, ranks, msg_type, data=None):
        pass

    def send_to_ranks(self, ranks, msg_type, data=None, *, tenant=None,
                      priority=0, msg_id=None, timeout=None,
                      on_verdict=None, collective="unknown",
                      bufs=None):
        [rank] = ranks
        if rank in self._dead:
            raise WorkerDied(f"workers [{rank}] are dead")
        if msg_type == "execute":
            return {rank: types.SimpleNamespace(data={"output": "ok"})}
        if msg_type == "serve_open":
            if rank in self.open_fail_ranks:
                return {rank: types.SimpleNamespace(
                    data={"error": "injected serve_open failure"})}
            self._srv[rank] = {}
            self._ticked.discard(rank)
            return {rank: types.SimpleNamespace(
                data={"status": "open",
                      "kv_view_bytes": self.kv_view_bytes})}
        if msg_type == "serve_close":
            self._srv.pop(rank, None)
            return {rank: types.SimpleNamespace(data={"status": "ok"})}
        assert msg_type == "serve_step"
        if self.tick_delay:
            time.sleep(self.tick_delay)
            if [r for r in ranks if r in self._dead]:
                # Killed while this tick was in flight: the reply is
                # lost with the rank, like a real SIGKILL mid-step.
                raise WorkerDied(f"workers {ranks} are dead")
        if self.fail_next > 0:
            self.fail_next -= 1
            raise TimeoutError("injected step timeout")
        if msg_id in self._replay:   # redelivery: cached reply
            return {rank: types.SimpleNamespace(
                data=self._replay[msg_id])}
        srv = self._srv.setdefault(rank, {})
        self.steps_seen.append(dict(data))
        for a in data.get("admit") or ():
            srv[a["rid"]] = {"seq": list(a["prompt"]), "emitted": 0,
                             "base_len": len(a["prompt"]),
                             "max": a["max_new"]}
        for rid in data.get("release") or ():
            srv.pop(rid, None)
        emitted, finished = {}, []
        for rid, st in srv.items():
            if st["emitted"] >= st["max"]:
                finished.append(rid)
                continue
            o = st["emitted"]
            new = []
            for _ in range(min(self.per_tick,
                               st["max"] - st["emitted"])):
                t = next_tok(st["seq"])
                st["seq"].append(t)
                new.append(t)
            st["emitted"] += len(new)
            back = min(self.overlap_next_reply, o)
            if back:
                # Test hook: pretend this reply re-sends `back`
                # already-reported tokens (a replayed emission).
                new = st["seq"][st["base_len"] + o - back:
                               st["base_len"] + st["emitted"]]
                o -= back
                self.overlap_next_reply = 0
            emitted[rid] = {"o": o, "t": list(new)}
            if st["emitted"] >= st["max"]:
                finished.append(rid)
        reply = {"status": "ok", "emitted": emitted,
                 "finished": finished, "errors": {},
                 "active": len(srv), "slots": 8, "pending": 0}
        if self.tick_ph is not None:
            seq = data.get("seq")
            ph = dict(self.tick_ph)
            if seq in self.slow_seqs:
                ph["sync"] = self.slow_sync_s
            reply["tick"] = {"seq": seq, "ph": ph, "cmp": [0, 0.0],
                             "pf": 0, "dc": len(emitted)}
            if rank in self._ticked:
                reply["tick"]["turnaround"] = 0.001
            self._ticked.add(rank)
        if msg_id is not None:
            self._replay[msg_id] = reply
        return {rank: types.SimpleNamespace(data=reply)}


def make_mgr(tmp_path, comm, **kw):
    delivered: list = []
    notices: list = []
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("steps", 2)
    kw.setdefault("step_timeout", 5.0)
    kw.setdefault("queue_depth", 8)
    kw.setdefault("inflight", 8)
    mgr = ServingManager(
        comm, str(tmp_path), world_size=comm.num_workers,
        deliver=lambda t, m: delivered.append((t, m)),
        notify=lambda t, m: notices.append((t, m)), **kw)
    return mgr, delivered, notices


def wait_done(mgr, rids, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(mgr.result(r)["done"] for r in rids):
            return
        time.sleep(0.02)
    raise AssertionError(
        f"requests not done: "
        f"{({r: mgr.result(r) for r in rids})}; {mgr.describe()}")


# ----------------------------------------------------------------------
# manager behavior


def test_manager_serves_exact_streams_and_delivers_once(tmp_path):
    comm = FakeComm()
    mgr, delivered, notices = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        prompts = [[5, 9, 2], [7, 1], [3, 4, 8]]
        rids = [mgr.submit("t1", p, 5)["rid"] for p in prompts]
        wait_done(mgr, rids)
        for rid, p in zip(rids, prompts):
            r = mgr.result(rid)
            assert r["status"] == "completed"
            assert r["tokens"] == expected_stream(p, 5)
        # Terminal delivery exactly once per request, via serve_done.
        done_rids = [m.data["rid"] for _t, m in delivered
                     if m.msg_type == "serve_done"]
        assert sorted(done_rids) == sorted(rids)
        # Incremental notices carry contiguous offsets per rid.
        for rid in rids:
            offs = [(m.data["o"], len(m.data["t"]))
                    for _t, m in notices
                    if m.msg_type == "serve_tokens"
                    and m.data["rid"] == rid]
            pos = 0
            for o, n in offs:
                assert o == pos
                pos += n
        d = mgr.describe()
        assert d["completed"] == 3 and d["dup_dropped"] == 0
        assert d["failovers"] == 0
        # The journal replays to the exact streams.
        state = ServeJournal.load(journal_path(str(tmp_path),
                                               "serve"))
        for rid, p in zip(rids, prompts):
            assert state[rid]["tokens"] == expected_stream(p, 5)
            assert state[rid]["done"] == "completed"
    finally:
        mgr.stop()


def test_admission_verdicts_rejected_and_shed(tmp_path):
    comm = FakeComm()
    # 1 KV slot, queue depth 1, per-tenant cap 2: the third same-
    # tenant submit must be REJECTED at the cap; a low-priority
    # pending request must be SHED by a higher-priority burst.
    mgr, delivered, _ = make_mgr(tmp_path, comm, max_batch=1,
                                 queue_depth=1, inflight=2)
    # Driver NOT started: requests stay pending, so verdicts are
    # deterministic.
    v0 = mgr.submit("t1", [1], 4, priority=0)
    assert v0["status"] == "accepted" and not v0["queued"]
    v1 = mgr.submit("t1", [2], 4, priority=0)
    assert v1["status"] == "accepted" and v1["queued"]
    v2 = mgr.submit("t1", [3], 4)
    assert v2["status"] == "rejected"
    assert "in-flight" in v2["error"]
    # Higher-priority tenant floods: t1's queued request is the
    # lowest-priority pending one and sheds with a delivered verdict.
    v3 = mgr.submit("t2", [4], 4, priority=5)
    assert v3["status"] == "accepted"
    shed = [m for _t, m in delivered
            if m.data.get("status") == "shed"]
    assert len(shed) == 1 and shed[0].data["rid"] == v1["rid"]
    assert mgr.result(v1["rid"])["status"] == "shed"
    # Too-long requests are refused with a named verdict.
    v4 = mgr.submit("t2", [1] * 60, 10)
    assert v4["status"] == "rejected" and v4["reason"] == "too-long"
    mgr.stop()


def test_failover_readmits_from_journal_exactly(tmp_path):
    comm = FakeComm(num_workers=3, per_tick=1, tick_delay=0.05)
    mgr, delivered, _ = make_mgr(tmp_path, comm, steps=1)
    mgr.start()
    try:
        prompt = [5, 9, 2]
        rid = mgr.submit("t1", prompt, 8)["rid"]
        # Decode places on the HIGHEST live rank (2); let it emit a
        # few tokens, then SIGKILL that rank.
        deadline = time.monotonic() + 10
        while len(mgr.result(rid)["tokens"]) < 3:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert mgr.describe()["decode_rank"] == 2
        comm.kill(2)
        wait_done(mgr, [rid])
        r = mgr.result(rid)
        assert r["status"] == "completed"
        assert r["tokens"] == expected_stream(prompt, 8)
        d = mgr.describe()
        assert d["failovers"] >= 1
        assert d["replayed"] >= 1
        assert d["dup_dropped"] == 0
        assert d["decode_rank"] == 1
        # The re-admission carried prompt + emitted prefix and the
        # REMAINING budget (the journal-replay contract).
        readmits = [a for s in comm.steps_seen
                    for a in (s.get("admit") or ())
                    if a["rid"] == rid and len(a["prompt"]) >
                    len(prompt)]
        assert readmits, "no journal re-admission seen"
        ra = readmits[0]
        k = len(ra["prompt"]) - len(prompt)
        assert ra["prompt"] == prompt + expected_stream(prompt, k)
        assert ra["max_new"] == 8 - k
    finally:
        mgr.stop()


def test_replayed_emission_overlap_is_dropped(tmp_path):
    comm = FakeComm(per_tick=1, tick_delay=0.05)
    mgr, _d, _n = make_mgr(tmp_path, comm, steps=1)
    mgr.start()
    try:
        rid = mgr.submit("t1", [7, 1], 6)["rid"]
        deadline = time.monotonic() + 10
        while len(mgr.result(rid)["tokens"]) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        comm.overlap_next_reply = 2   # next reply re-sends 2 tokens
        wait_done(mgr, [rid])
        r = mgr.result(rid)
        assert r["tokens"] == expected_stream([7, 1], 6)
        assert mgr.describe()["dup_dropped"] >= 2
    finally:
        mgr.stop()


def test_step_timeout_redelivers_same_msg_id(tmp_path):
    comm = FakeComm()
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        comm.fail_next = 1   # first tick times out, retry succeeds
        rid = mgr.submit("t1", [9], 4)["rid"]
        wait_done(mgr, [rid])
        assert mgr.result(rid)["tokens"] == expected_stream([9], 4)
        d = mgr.describe()
        assert d["step_retries"] >= 1 and d["dup_dropped"] == 0
    finally:
        mgr.stop()


def test_stream_resume_from_acked_offset(tmp_path):
    comm = FakeComm()
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        prompt = [3, 4]
        rid = mgr.submit("t1", prompt, 6)["rid"]
        wait_done(mgr, [rid])
        full = expected_stream(prompt, 6)
        s = mgr.stream(rid, 4)
        assert s["tokens"] == full[4:] and s["offset"] == 4
        assert s["done"] is True
        assert mgr.describe()["resumed"] == 1
        assert mgr.stream(rid, 0)["tokens"] == full
    finally:
        mgr.stop()


def test_successor_plane_recovers_journal(tmp_path):
    """Gateway-death durability: a NEW ServingManager over the same
    run dir + tenant re-enters every journaled-but-unfinished request
    and completes it exactly — 'accepted' survives the daemon too."""
    comm_a = FakeComm(per_tick=1, tick_delay=0.05)
    mgr_a, _d, _n = make_mgr(tmp_path, comm_a, steps=1)
    mgr_a.start()
    prompt = [5, 9, 2]
    rid = mgr_a.submit("t1", prompt, 8)["rid"]
    deadline = time.monotonic() + 10
    while len(mgr_a.result(rid)["tokens"]) < 3:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    mgr_a.stop(close_workers=False)   # daemon dies mid-stream
    prefix = mgr_a.result(rid)["tokens"]
    assert 0 < len(prefix) < 8

    comm_b = FakeComm()
    mgr_b, delivered, _ = make_mgr(tmp_path, comm_b)
    mgr_b.start()
    try:
        wait_done(mgr_b, [rid])
        r = mgr_b.result(rid)
        assert r["status"] == "completed"
        assert r["tokens"] == expected_stream(prompt, 8)
        d = mgr_b.describe()
        assert d["replayed"] >= 1 and d["dup_dropped"] == 0
        # The terminal result still reaches the submitter's mailbox.
        assert [m.data["rid"] for _t, m in delivered
                if m.msg_type == "serve_done"] == [rid]
        # Fresh submissions never reuse a journaled rid.
        rid2 = mgr_b.submit("t1", [1], 2)["rid"]
        assert rid2 != rid
        assert int(rid2.lstrip("r")) > int(rid.lstrip("r"))
        wait_done(mgr_b, [rid2])
    finally:
        mgr_b.stop()


def test_open_failure_backs_off_to_lower_rank(tmp_path):
    """A rank whose serve_open fails (lost namespace, OOM) is backed
    off so the plane fails over to a lower live rank instead of
    wedging on retries."""
    comm = FakeComm(num_workers=2)
    comm.open_fail_ranks.add(1)   # the preferred (highest) rank
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        rid = mgr.submit("t1", [7, 1], 4)["rid"]
        wait_done(mgr, [rid])
        r = mgr.result(rid)
        assert r["status"] == "completed"
        assert r["tokens"] == expected_stream([7, 1], 4)
        assert mgr.describe()["decode_rank"] == 0
    finally:
        mgr.stop()


# ----------------------------------------------------------------------
# metrics satellite


def test_metrics_remove_label_series():
    reg = MetricsRegistry()
    reg.counter("nbd_x_total", "x", {"tenant": "a"}).inc()
    reg.counter("nbd_x_total", "x", {"tenant": "b"}).inc(2)
    reg.gauge("nbd_y", "y", {"tenant": "a", "kind": "k"}).set(1)
    reg.counter("nbd_z_total", "z").inc()
    assert reg.remove_label_series("tenant", "a") == 2
    j = reg.to_json()
    assert 'nbd_x_total{tenant="a"}' not in j["counters"]
    assert j["counters"]['nbd_x_total{tenant="b"}'] == 2
    assert j["gauges"] == {}
    assert j["counters"]["nbd_z_total"] == 1
    # Removing again is a no-op; the metric NAME stays registered
    # with its kind (a later re-create cannot flip kinds).
    assert reg.remove_label_series("tenant", "a") == 0
    with pytest.raises(ValueError):
        reg.gauge("nbd_x_total")


# ----------------------------------------------------------------------
# serving SLO histograms (ISSUE 13)


def test_slo_histograms_per_tenant_and_eviction(tmp_path):
    """Completed requests observe TTFT / TPOT / queue-wait / e2e into
    per-SUBMITTING-tenant histograms; describe() carries the p50/p99
    block split per tenant; tenant eviction's remove_label_series
    really retires the series."""
    from nbdistributed_tpu.observability import metrics as obs_metrics
    comm = FakeComm()
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        rids = [mgr.submit("nb1", [5, 9, 2], 5)["rid"],
                mgr.submit("nb2", [7, 1], 4)["rid"]]
        wait_done(mgr, rids)
    finally:
        mgr.stop()
    text = obs_metrics.registry().prometheus_text()
    for name in ("nbd_serve_ttft_seconds",
                 "nbd_serve_queue_wait_seconds",
                 "nbd_serve_e2e_seconds"):
        assert f'{name}_count{{tenant="nb1"}} 1' in text
        assert f'{name}_count{{tenant="nb2"}} 1' in text
    # 5 tokens at 2/tick = 3 emissions: 2 inter-emission gaps observe
    # the per-token rate (the first batch is TTFT, never TPOT)
    assert 'nbd_serve_tpot_seconds_count{tenant="nb1"} 2' in text

    slo = mgr.describe()["slo"]
    assert slo["e2e_ms"]["n"] == 2
    assert slo["ttft_ms"]["p99"] >= slo["ttft_ms"]["p50"] >= 0
    assert set(slo["tenants"]) == {"nb1", "nb2"}
    assert slo["tenants"]["nb1"]["e2e_ms"]["n"] == 1

    # eviction hygiene: dropping nb1 removes ITS series, keeps nb2's
    assert obs_metrics.registry().remove_label_series(
        "tenant", "nb1") >= 4
    text = obs_metrics.registry().prometheus_text()
    assert 'nbd_serve_ttft_seconds_count{tenant="nb1"}' not in text
    assert 'nbd_serve_ttft_seconds_count{tenant="nb2"} 1' in text


def test_slo_queue_wait_counts_first_placement_only(tmp_path):
    """A failover re-admission is a heal, not queue wait: the queue
    histogram observes once per request even when the decode rank dies
    mid-stream and the request is re-placed."""
    from nbdistributed_tpu.observability import metrics as obs_metrics
    reg = obs_metrics.registry()

    def qcount():
        j = reg.to_json()["histograms"]
        e = j.get('nbd_serve_queue_wait_seconds{tenant="qw1"}')
        return e["count"] if e else 0

    base = qcount()
    comm = FakeComm(per_tick=1, tick_delay=0.05)
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        rid = mgr.submit("qw1", [5, 9, 2], 6)["rid"]
        deadline = time.monotonic() + 10
        while mgr.result(rid)["tokens"] == [] \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        comm.kill(1)          # decode rank dies mid-stream
        wait_done(mgr, [rid])
        assert mgr.describe()["failovers"] >= 1
    finally:
        mgr.stop()
    assert qcount() - base == 1


# ----------------------------------------------------------------------
# the tick's account, gateway side (ISSUE 25)

WORKER_PH = {"admit": 0.0001, "prefill": 0.0, "dispatch": 0.0002,
             "sync": 0.0004, "emit": 0.0001, "collect": 0.0001}


class FakeFlight:
    def __init__(self):
        self.events: list[tuple[str, dict]] = []

    def record(self, event, **kw):
        self.events.append((event, kw))


def test_tick_seq_is_monotone_rides_the_payload_and_comes_back(tmp_path):
    comm = FakeComm(per_tick=1)
    comm.tick_ph = dict(WORKER_PH)
    comm.kv_view_bytes = 4096
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        rids = [mgr.submit("t1", [5, 9, 2], 6)["rid"],
                mgr.submit("t1", [7, 1], 4)["rid"]]
        wait_done(mgr, rids)
        st = mgr.describe()
    finally:
        mgr.stop()
    seqs = [p["seq"] for p in comm.steps_seen]
    assert seqs == sorted(set(seqs)) and seqs[0] >= 1, seqs
    assert all(p["steps"] == 2 for p in comm.steps_seen)
    tk = st["lat"]["summary"]["ticks"]
    # every step that came back under its seq is in the ring
    assert tk["count"] == min(len(seqs), 64)
    assert tk["kv_view_bytes"] == 4096 and tk["compiles"] == 0
    assert tk["sync"]["p50"] == 0.4 and tk["host"]["p50"] == 0.5
    # the gateway's own phases were timed, and the wire is what the
    # round trip holds beyond the worker's handler: never negative
    for k in ("place", "roundtrip", "apply", "util", "journal",
              "notify", "gateway_self", "wire"):
        assert tk[k]["p50"] >= 0 and tk[k]["p99"] >= tk[k]["p50"], k
    assert tk["roundtrip"]["mean"] > 0 and tk["apply"]["mean"] > 0
    assert tk["journal"]["mean"] > 0
    assert tk["apply"]["mean"] >= tk["journal"]["mean"]
    # the first tick followed a wait for work and a fresh open: it is
    # in the ring and out of the period
    assert "period_ms" in tk and tk["slow"] == []


def test_a_worker_without_the_account_leaves_the_ring_empty(tmp_path):
    comm = FakeComm()                   # replies carry no tick block
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        wait_done(mgr, [mgr.submit("t1", [5, 9, 2], 5)["rid"]])
        tk = mgr.describe()["lat"]["summary"]["ticks"]
    finally:
        mgr.stop()
    assert tk["count"] == 0 and tk["slow"] == []
    assert all("seq" in p for p in comm.steps_seen)


def test_forced_slow_tick_is_kept_and_written_to_the_flight_once(
        tmp_path):
    comm = FakeComm(per_tick=1)
    comm.tick_ph = dict(WORKER_PH)
    flight = FakeFlight()
    mgr, _d, _n = make_mgr(tmp_path, comm, flight=flight)
    mgr.start()
    try:
        wait_done(mgr, [mgr.submit("t1", [5, 9, 2], 24)["rid"]])
        nxt = mgr._seq + 2
        comm.slow_seqs = {nxt}          # a tick 3x over any median
        wait_done(mgr, [mgr.submit("t1", [7, 1], 8)["rid"]])
        st = mgr.describe()
        mgr.describe()                  # reading twice writes nothing
    finally:
        mgr.stop()
    slow = st["lat"]["summary"]["ticks"]["slow"]
    assert [t["seq"] for t in slow] == [nxt]
    (t,) = slow
    assert t["worker_ms"]["sync"] == 5000.0 and t["period_ms"] > 5000.0
    assert set(t["worker_ms"]) == set(WORKER_PH)
    assert {"place", "roundtrip", "apply", "util"} <= set(t["gateway_ms"])
    written = [kw for ev, kw in flight.events if ev == "serve_slow_tick"]
    assert len(written) == 1 and written[0] == t


def test_serve_status_renders_the_tick_line(tmp_path, capsys):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    comm = FakeComm(per_tick=1)
    comm.tick_ph = dict(WORKER_PH)
    mgr, _d, _n = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        wait_done(mgr, [mgr.submit("t1", [5, 9, 2], 6)["rid"]])
        st = mgr.describe()
    finally:
        mgr.stop()
    DistributedMagics._render_serve_status(st)
    out = capsys.readouterr().out
    assert "ticks: period p50/p99" in out and "sync 0.4" in out
    assert "compiles 0" in out and "slow 0" in out
