"""Multi-rank continuous batching + block-bounded admission
(ISSUE 17): the ServingManager driving SEVERAL decode ranks at once
against a fake comm — placement across ranks, per-rank failover
surgery (only the dead rank's requests replay), KV-block admission
verdicts, and journal durability with a multi-rank plane.

The fake workers decode the same deterministic position-weighted
stream as ``test_serving_plane`` so every exactness assertion is
closed-form.
"""

from __future__ import annotations

import threading
import time
import types

import pytest

from nbdistributed_tpu.gateway.serving import ServingManager
from nbdistributed_tpu.messaging.coordinator import WorkerDied

pytestmark = [pytest.mark.unit, pytest.mark.serve, pytest.mark.gateway]


def next_tok(seq: list[int]) -> int:
    return (sum((i + 1) * t for i, t in enumerate(seq)) + 7) % 50


def expected_stream(prompt: list[int], n: int) -> list[int]:
    seq = list(prompt)
    out = []
    for _ in range(n):
        t = next_tok(seq)
        out.append(t)
        seq.append(t)
    return out


class FakeComm:
    """Like test_serving_plane's fake, with per-RANK step attribution:
    ``steps_seen`` records ``(rank, payload)`` and ``active_seen``
    records each tick's concurrent stream count, so multi-rank
    placement and block-bounded admission are directly assertable."""

    def __init__(self, num_workers: int = 3, per_tick: int = 2,
                 tick_delay: float = 0.0):
        self.num_workers = num_workers
        self.per_tick = per_tick
        self.tick_delay = tick_delay
        self._dead: set[int] = set()
        self._lock = threading.Lock()
        self._srv: dict[int, dict] = {}
        self._replay: dict[str, dict] = {}
        self.steps_seen: list[tuple[int, dict]] = []
        self.active_seen: list[tuple[int, int]] = []

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
            self._srv[rank] = {}
            return {rank: types.SimpleNamespace(
                data={"status": "open", "step_kernels": rank})}
        if msg_type == "serve_close":
            self._srv.pop(rank, None)
            return {rank: types.SimpleNamespace(data={"status": "ok"})}
        assert msg_type == "serve_step"
        if self.tick_delay:
            time.sleep(self.tick_delay)
            if rank in self._dead:
                raise WorkerDied(f"workers {ranks} are dead")
        if msg_id in self._replay:
            return {rank: types.SimpleNamespace(
                data=self._replay[msg_id])}
        srv = self._srv.setdefault(rank, {})
        self.steps_seen.append((rank, dict(data)))
        for a in data.get("admit") or ():
            srv[a["rid"]] = {"seq": list(a["prompt"]), "emitted": 0,
                             "base_len": len(a["prompt"]),
                             "max": a["max_new"]}
        for rid in data.get("release") or ():
            srv.pop(rid, None)
        self.active_seen.append((rank, len(srv)))
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
            emitted[rid] = {"o": o, "t": list(new)}
            if st["emitted"] >= st["max"]:
                finished.append(rid)
        reply = {"status": "ok", "emitted": emitted,
                 "finished": finished, "errors": {},
                 "active": len(srv), "slots": 8, "pending": 0}
        if msg_id is not None:
            self._replay[msg_id] = reply
        return {rank: types.SimpleNamespace(data=reply)}


def make_mgr(tmp_path, comm, **kw):
    delivered: list = []
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("steps", 1)
    kw.setdefault("step_timeout", 5.0)
    kw.setdefault("queue_depth", 8)
    kw.setdefault("inflight", 16)
    kw.setdefault("decode_ranks", 2)
    mgr = ServingManager(
        comm, str(tmp_path), world_size=comm.num_workers,
        deliver=lambda t, m: delivered.append((t, m)),
        notify=lambda _t, _m: None, **kw)
    return mgr, delivered


def wait_done(mgr, rids, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(mgr.result(r)["done"] for r in rids):
            return
        time.sleep(0.02)
    raise AssertionError(
        f"requests not done: "
        f"{({r: mgr.result(r) for r in rids})}; {mgr.describe()}")


def admits_by_rank(comm) -> dict[int, list[str]]:
    out: dict[int, list[str]] = {}
    for rank, data in comm.steps_seen:
        for a in data.get("admit") or ():
            out.setdefault(rank, []).append(a["rid"])
    return out


# ----------------------------------------------------------------------


def test_multi_rank_decode_uses_both_ranks_exactly(tmp_path):
    """decode_ranks=2 on a 3-rank world: requests shard across ranks
    2 and 1 (rank 0 stays clear — it hosts jax.distributed), BOTH
    ranks demonstrably decode, and every stream is bit-identical to
    the single-rank reference."""
    comm = FakeComm(num_workers=3, per_tick=1, tick_delay=0.01)
    mgr, delivered = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        prompts = [[5, 9, 2], [7, 1], [3, 4, 8], [2, 6]]
        rids = [mgr.submit("t1", p, 5)["rid"] for p in prompts]
        wait_done(mgr, rids)
        for rid, p in zip(rids, prompts):
            r = mgr.result(rid)
            assert r["status"] == "completed"
            assert r["tokens"] == expected_stream(p, 5), rid
        # Per-rank telemetry: both decode ranks took admissions (4
        # requests into 2 slots/rank cannot fit on one), none leaked
        # onto rank 0.
        by_rank = admits_by_rank(comm)
        assert set(by_rank) == {1, 2}, by_rank
        assert sorted(r for rs in by_rank.values() for r in rs) \
            == sorted(rids)
        d = mgr.describe()
        assert d["decode_ranks"] == [1, 2]
        assert d["decode_rank"] == 2          # legacy headline rank
        assert set(d["ranks"]) == {"1", "2"}
        # what each rank's own server reported at serve_open
        assert {r: v["step_kernels"] for r, v in d["ranks"].items()} \
            == {"1": 1, "2": 2}
        assert d["failovers"] == 0 and d["dup_dropped"] == 0
        done_rids = [m.data["rid"] for _t, m in delivered
                     if m.msg_type == "serve_done"]
        assert sorted(done_rids) == sorted(rids)
    finally:
        mgr.stop()


def test_single_rank_loss_replays_only_its_requests(tmp_path):
    """SIGKILL ONE of two decode ranks mid-stream: only the dead
    rank's requests re-admit from the journal (the survivor's streams
    are never disturbed), and every stream stays bit-exact."""
    comm = FakeComm(num_workers=3, per_tick=1, tick_delay=0.05)
    mgr, _d = make_mgr(tmp_path, comm)
    mgr.start()
    try:
        prompts = [[5, 9, 2], [7, 1], [3, 4, 8], [2, 6]]
        rids = [mgr.submit("t1", p, 8)["rid"] for p in prompts]
        deadline = time.monotonic() + 10
        while any(len(mgr.result(r)["tokens"]) < 2 for r in rids):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        on_dead = set(admits_by_rank(comm).get(2, ()))
        assert on_dead, "rank 2 never took a request"
        comm.kill(2)
        wait_done(mgr, rids)
        for rid, p in zip(rids, prompts):
            r = mgr.result(rid)
            assert r["status"] == "completed"
            assert r["tokens"] == expected_stream(p, 8), rid
        d = mgr.describe()
        assert d["failovers"] >= 1
        assert 1 <= d["replayed"] <= len(on_dead)
        assert d["dup_dropped"] == 0
        # The failover pulled in rank 0: the two highest LIVE ranks.
        assert d["decode_ranks"] == [0, 1]
        # Re-admissions (prompt grew by the emitted prefix) happened
        # ONLY for requests the dead rank held.
        readmitted = {a["rid"] for _rank, data in comm.steps_seen
                      for a in (data.get("admit") or ())
                      if len(a["prompt"]) > len(prompts[
                          rids.index(a["rid"])])}
        assert readmitted and readmitted <= on_dead, \
            (readmitted, on_dead)
    finally:
        mgr.stop()


def test_kv_exhausted_submit_verdict(tmp_path):
    """A request whose worst-case block need exceeds a whole rank's
    pool can never be placed: refused AT SUBMIT with an explicit
    kv-exhausted verdict instead of starving in the queue."""
    comm = FakeComm()
    mgr, _d = make_mgr(tmp_path, comm, kv_block_tokens=4, kv_blocks=2)
    # Driver not started: the verdict is synchronous and
    # deterministic.  2 blocks/rank * 4 tok = 8 tokens of capacity.
    v = mgr.submit("t1", [1] * 6, 6)          # needs 3 blocks
    assert v["status"] == "rejected"
    assert v["reason"] == "kv-exhausted"
    assert "3 KV blocks" in v["error"]
    # A fitting request is still admitted.
    assert mgr.submit("t1", [1, 2], 4)["status"] == "accepted"
    mgr.stop()


def test_block_bounded_admission_defers_not_drops(tmp_path):
    """Free sequence slots but NO free blocks: admission defers (the
    finer-grained block gate under the scheduler ticket) and resumes
    as finishing requests free their blocks — nothing sheds, nothing
    hangs, streams stay exact."""
    comm = FakeComm(num_workers=2, per_tick=1, tick_delay=0.01)
    # One decode rank, 4 sequence slots, but a 1-block pool: only one
    # request's worst case (<= 8 tokens) fits at a time.
    mgr, _d = make_mgr(tmp_path, comm, decode_ranks=1, max_batch=4,
                       kv_block_tokens=8, kv_blocks=1)
    mgr.start()
    try:
        reqs = [([i + 1, i + 2], 4) for i in range(3)]
        rids = [mgr.submit("t1", p, n)["rid"] for p, n in reqs]
        wait_done(mgr, rids)
        for rid, (p, n) in zip(rids, reqs):
            r = mgr.result(rid)
            assert r["status"] == "completed"
            assert r["tokens"] == expected_stream(p, n), rid
        # The block gate, not the slot count, bounded concurrency.
        assert max(n for _rank, n in comm.active_seen) == 1
        d = mgr.describe()
        assert d["shed"] == 0 and d["rejected"] == 0
        assert d["completed"] == 3
        # Every block returned to the gateway's accounting pool.
        assert d["kv"] == {"block_tokens": 8, "blocks_per_rank": 1,
                           "used": 0, "free": 1, "tenants": {}}
    finally:
        mgr.stop()


def test_describe_kv_and_per_rank_occupancy(tmp_path):
    """The status surface mid-decode: per-rank placed/kv_used
    telemetry and per-submitting-tenant block counts."""
    comm = FakeComm(num_workers=3, per_tick=1, tick_delay=0.05)
    mgr, _d = make_mgr(tmp_path, comm, kv_block_tokens=8)
    mgr.start()
    try:
        rids = [mgr.submit("tA", [5, 9, 2], 8)["rid"],
                mgr.submit("tB", [7, 1], 8)["rid"]]
        deadline = time.monotonic() + 10
        while any(len(mgr.result(r)["tokens"]) < 1 for r in rids):
            assert time.monotonic() < deadline
            time.sleep(0.02)
        d = mgr.describe()
        assert d["kv"]["block_tokens"] == 8
        # 2 slots/rank * ceil(64/8) blocks each (dense capacity).
        assert d["kv"]["blocks_per_rank"] == 2 * 8
        assert d["kv"]["used"] >= 2           # both requests hold KV
        # Per-tenant attribution: each submitted one live request.
        assert set(d["kv"]["tenants"]) == {"tA", "tB"}
        assert sum(v["kv_used"] for v in d["ranks"].values()) \
            == d["kv"]["used"]
        assert sum(v["placed"] for v in d["ranks"].values()) == 2
        wait_done(mgr, rids)
        assert mgr.describe()["kv"]["used"] == 0
    finally:
        mgr.stop()


def test_successor_plane_recovers_journal_multi_rank(tmp_path):
    """Gateway-death durability is preserved under multi-rank decode:
    a NEW manager over the same run dir re-enters every unfinished
    request across a FRESH 2-rank plane and completes it exactly."""
    comm_a = FakeComm(num_workers=3, per_tick=1, tick_delay=0.05)
    mgr_a, _d = make_mgr(tmp_path, comm_a)
    mgr_a.start()
    prompts = [[5, 9, 2], [7, 1]]
    rids = [mgr_a.submit("t1", p, 8)["rid"] for p in prompts]
    deadline = time.monotonic() + 10
    while any(len(mgr_a.result(r)["tokens"]) < 2 for r in rids):
        assert time.monotonic() < deadline
        time.sleep(0.02)
    mgr_a.stop(close_workers=False)   # daemon dies mid-stream
    for rid in rids:
        assert 0 < len(mgr_a.result(rid)["tokens"]) < 8

    comm_b = FakeComm(num_workers=3)
    mgr_b, delivered = make_mgr(tmp_path, comm_b)
    mgr_b.start()
    try:
        wait_done(mgr_b, rids)
        for rid, p in zip(rids, prompts):
            r = mgr_b.result(rid)
            assert r["status"] == "completed"
            assert r["tokens"] == expected_stream(p, 8)
        d = mgr_b.describe()
        assert d["replayed"] >= len(rids) and d["dup_dropped"] == 0
        assert sorted(m.data["rid"] for _t, m in delivered
                      if m.msg_type == "serve_done") == sorted(rids)
    finally:
        mgr_b.stop()
