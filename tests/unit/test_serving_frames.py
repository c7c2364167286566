"""Emission a step (ISSUE 38): the worker sends what each decode step
fetched as an unsolicited ``serve_emit`` frame, the gateway applies what
has arrived beside the tick, and the tick's reply stays authoritative.

Two halves.  The gateway's, against the fake pool of
``test_serving_plane`` taught to speak frames: a stream fed by frames and
then the reply is the reply-only stream, token for token and in the
journal, whatever happens to a frame; a token is journaled before it is
pushed, pushed once, and ``serve_done`` comes once.  The worker's, against
a real :class:`DecodeServer` over ``tiny_config``: one frame between two
steps whatever the rows, the reply from the offsets the tick began at.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from test_serving_plane import (FakeComm, expected_stream, make_mgr,
                                wait_done)
from test_serving_tick import _step, setup, solo  # noqa: F401 (fixture)

from nbdistributed_tpu.gateway.serving import (ServeJournal, journal_path,
                                               merge_frames)
from nbdistributed_tpu.messaging import Message
from nbdistributed_tpu.models.serving import DecodeServer
from nbdistributed_tpu.observability.servingobs import (TICK_TOTALS,
                                                        ServingObservatory)
from nbdistributed_tpu.runtime import worker as worker_mod

pytestmark = [pytest.mark.unit, pytest.mark.serve, pytest.mark.gateway]


# ----------------------------------------------------------------------
# a fake pool that speaks frames


def frames_of(reply: dict, tenant: str, seq) -> list[dict]:
    """The frames a worker would have sent during the tick that
    ``reply`` answers: the k-th holds the k-th token of every request
    that has one (a row gets one token a step)."""
    emitted = reply.get("emitted") or {}
    steps = max((len(em["t"]) for em in emitted.values()), default=0)
    return [{"tenant": tenant, "seq": seq, "now": time.time(),
             "emitted": {rid: {"o": em["o"] + k, "t": [em["t"][k]]}
                         for rid, em in emitted.items()
                         if k < len(em["t"])}}
            for k in range(steps)]


class FrameComm(FakeComm):
    """``FakeComm`` whose workers also send a frame a step.  What
    becomes of a tick's frames is ``deliver(rank, frames)``'s to say,
    called before the tick's reply returns (``None``: the frames go to
    the registered sinks, as the comm's IO thread would hand them on,
    ``frame_gap`` apart, and the reply follows ``reply_delay`` later)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.sinks: list = []
        self.deliver = None
        self.reply_delay = 0.0
        self.frame_gap = 0.0

    def add_notify_callback(self, cb):
        self.sinks.append(cb)

    def remove_notify_callback(self, cb):
        self.sinks = [c for c in self.sinks if c != cb]

    def send_to_ranks(self, ranks, msg_type, data=None, **kw):
        out = super().send_to_ranks(ranks, msg_type, data, **kw)
        if msg_type != "serve_step":
            return out
        [rank] = ranks
        frames = frames_of(out[rank].data, data["tenant"],
                           data.get("seq"))
        if self.deliver is not None:
            self.deliver(rank, frames)
        else:
            for f in frames:
                for cb in self.sinks:
                    cb(rank, frame_msg(rank, f))
                time.sleep(self.frame_gap)
            time.sleep(self.reply_delay)
        return out


def frame_msg(rank: int, data: dict) -> Message:
    return Message(msg_type="serve_emit", rank=rank, data=data,
                   tenant=data.get("tenant"))


def feed(mgr, rank, frames) -> None:
    """Hand frames to the manager as the IO thread and then the applier
    would, on this thread: what arrived is applied before returning."""
    for f in frames:
        mgr._on_frame(rank, frame_msg(rank, f))
    mgr._drain_frames()


def journal_lines(tmp_path) -> list[dict]:
    with open(journal_path(str(tmp_path), "serve"),
              encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def emit_spans(lines, rid) -> list[tuple[int, int]]:
    return [(r["o"], r["o"] + len(r["t"])) for r in lines
            if r["e"] == "emit" and r["rid"] == rid]


def pushes_of(notices, delivered, rid) -> list[tuple[int, list]]:
    """(offset, tokens) of every push a client got for ``rid``, a
    ``serve_done`` as the tokens it adds to what was pushed."""
    out = [(m.data["o"], list(m.data["t"])) for _t, m in notices
           if m.msg_type == "serve_tokens" and m.data["rid"] == rid]
    return out, [m for _t, m in delivered
                 if m.msg_type == "serve_done" and m.data["rid"] == rid]


def assert_stream_is_the_reply_only_stream(mgr, tmp_path, rid, prompt, n,
                                           notices, delivered):
    want = expected_stream(prompt, n)
    assert mgr.result(rid)["tokens"] == want
    lines = journal_lines(tmp_path)
    # the journal holds every token once, in order: no overlap, no hole
    pos = 0
    for a, b in emit_spans(lines, rid):
        assert a == pos
        pos = b
    assert pos == n
    assert ServeJournal.load(
        journal_path(str(tmp_path), "serve"))[rid]["tokens"] == want
    assert [r for r in lines if r["e"] == "done" and r["rid"] == rid] \
        == [{"e": "done", "rid": rid, "status": "completed"}]
    # and so do the pushes; the terminal signal comes once, complete
    pushed, done = pushes_of(notices, delivered, rid)
    pos = 0
    for o, toks in pushed:
        assert o == pos and toks == want[o:o + len(toks)]
        pos += len(toks)
    assert len(done) == 1 and done[0].data["tokens"] == want


def run_ticks(mgr, rids, limit=40):
    for _ in range(limit):
        if all(mgr.result(r)["done"] for r in rids):
            return
        mgr._tick()
    raise AssertionError(f"not done after {limit} ticks: {mgr.describe()}")


# ----------------------------------------------------------------------
# frames, then the reply


def test_stream_fed_by_frames_then_reply_equals_reply_only(tmp_path):
    comm = FrameComm(per_tick=3)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=3)
    # every step's frame but the tick's last, as the worker sends
    # them, each applied before the next arrives
    comm.deliver = lambda rank, frames: [feed(mgr, rank, [f])
                                         for f in frames[:-1]]
    prompts = [[5, 9, 2], [7, 1]]
    rids = [mgr.submit("t1", p, 8)["rid"] for p in prompts]
    run_ticks(mgr, rids)
    for rid, p in zip(rids, prompts):
        assert_stream_is_the_reply_only_stream(
            mgr, tmp_path, rid, p, 8, notices, delivered)
        # a stream heard every step: no push of more than one token
        pushed, _ = pushes_of(notices, delivered, rid)
        assert pushed and all(len(t) == 1 for _o, t in pushed)
    d = mgr.describe()
    # what a reply repeats of its own tick's frames is no redelivery
    assert d["dup_dropped"] == 0 and d["tokens_total"] == 16
    mgr.stop()


def _drop_one(mgr, rank, frames):
    feed(mgr, rank, frames[:1] + frames[2:])      # the second is lost


def _duplicate(mgr, rank, frames):
    feed(mgr, rank, [f for f in frames for _ in (0, 1)])


def _duplicate_apart(mgr, rank, frames):
    for f in frames:        # each applied, then delivered once more
        feed(mgr, rank, [f])
        feed(mgr, rank, [f])


def _reorder(mgr, rank, frames):
    feed(mgr, rank, frames[::-1])


def _stale_seq(mgr, rank, frames):
    for f in frames:
        feed(mgr, rank, [dict(f, seq=f["seq"] - 1)])


def _other_tenant(mgr, rank, frames):
    feed(mgr, rank, [dict(f, tenant="someone-else") for f in frames])


def _wrong_rank(mgr, rank, frames):
    for f in frames:
        feed(mgr, 1 - rank, [f])


def _old_epoch(mgr, rank, frames):
    mgr.comm.session_epoch = 3
    for f in frames:
        m = frame_msg(rank, f)
        m.epoch = 2
        mgr._on_frame(rank, m)
    mgr._drain_frames()
    assert not mgr._frames


@pytest.mark.parametrize("mishap", [
    _drop_one, _duplicate, _duplicate_apart, _reorder, _stale_seq,
    _other_tenant, _wrong_rank, _old_epoch],
    ids=lambda f: f.__name__.strip("_"))
def test_a_frames_mishap_leaves_the_stream_as_the_reply_alone_would(
        tmp_path, mishap):
    comm = FrameComm(per_tick=4)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=4)
    comm.deliver = lambda rank, frames: mishap(mgr, rank, frames)
    rid = mgr.submit("t1", [5, 9, 2], 10)["rid"]
    run_ticks(mgr, [rid])
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 10, notices, delivered)
    d = mgr.describe()
    assert d["dup_dropped"] == 0 and d["failovers"] == 0
    assert d["last_error"] is None
    if mishap in (_stale_seq, _other_tenant, _wrong_rank, _old_epoch):
        # not one of these frames was applied: a tick a push
        pushed, _ = pushes_of(notices, delivered, rid)
        assert [len(t) for _o, t in pushed] == [4, 4]
    mgr.stop()


def test_frames_delivered_after_their_ticks_reply_change_nothing(tmp_path):
    comm = FrameComm(per_tick=3)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=3)
    late: list = []
    comm.deliver = lambda rank, frames: late.append((rank, frames))
    rid = mgr.submit("t1", [5, 9, 2], 9)["rid"]
    mgr._tick()
    before = (mgr.result(rid), journal_lines(tmp_path), list(notices))
    for rank, frames in late:       # the tick's reply is applied
        feed(mgr, rank, frames)
    assert (mgr.result(rid), journal_lines(tmp_path), notices) == before
    assert mgr.describe()["dup_dropped"] == 0
    run_ticks(mgr, [rid])
    # and after the request's end: frames for a finished request
    n_done = len(delivered)
    for rank, frames in late:
        feed(mgr, rank, [dict(f, seq=mgr._seq) for f in frames])
    assert len(delivered) == n_done
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 9, notices, delivered)
    assert mgr.describe()["dup_dropped"] == 0
    mgr.stop()


def test_frames_from_a_lost_rank_are_dropped(tmp_path):
    comm = FrameComm(per_tick=2)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=2)
    kept: list = []
    comm.deliver = lambda rank, frames: kept.append((rank, frames))
    rid = mgr.submit("t1", [5, 9, 2], 8)["rid"]
    mgr._tick()
    rank0, frames = kept[-1]
    have = mgr.result(rid)["tokens"]
    # the rank dies; what it sent while alive arrives after the loss,
    # under the sequence number of the tick that is still the newest
    comm.kill(rank0)
    mgr._on_rank_lost(rank0)
    ahead = [{**f, "emitted": {rid: {"o": len(have), "t": [49]}}}
             for f in frames[:1]]
    feed(mgr, rank0, ahead)
    assert mgr.result(rid)["tokens"] == have
    run_ticks(mgr, [rid])
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 8, notices, delivered)
    d = mgr.describe()
    assert d["failovers"] == 1 and d["replayed"] == 1
    assert d["dup_dropped"] == 0
    mgr.stop()


def test_a_first_frame_that_outlived_its_placement_is_not_taken_for_new(
        tmp_path):
    """The dangerous one: offset 0 of an old placement, delivered after
    the request was placed again on the same rank, would sit exactly
    at the new placement's base."""
    comm = FrameComm(num_workers=1, per_tick=2)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=2)
    kept: list = []
    comm.deliver = lambda rank, frames: kept.append((rank, frames))
    rid = mgr.submit("t1", [5, 9, 2], 8)["rid"]
    mgr._tick()
    rank0, frames = kept[0]
    assert frames[0]["emitted"][rid]["o"] == 0
    # the server lost its state and is opened again: same rank, new base
    with mgr._lock:
        mgr._open.pop(rank0)
        mgr._unbind_rank_locked(rank0)
    # the old frame arrives while the next tick runs, before its reply
    comm.deliver = lambda rank, _new: feed(mgr, rank, frames[:1])
    mgr._tick()
    comm.deliver = None
    run_ticks(mgr, [rid])
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 8, notices, delivered)
    mgr.stop()


def test_a_hole_in_a_reply_still_fails_the_request_loudly(tmp_path):
    comm = FrameComm(per_tick=2)
    mgr, delivered, _ = make_mgr(tmp_path, comm, steps=2)
    rid = mgr.submit("t1", [5, 9, 2], 8)["rid"]
    mgr._tick()
    # a frame past the stream's end waits for the reply: no failure
    feed(mgr, mgr.describe()["decode_rank"],
         [{"tenant": "serve", "seq": mgr._seq,
           "emitted": {rid: {"o": 5, "t": [1]}}}])
    assert mgr.result(rid)["status"] == "accepted"
    mgr._apply_reply({"emitted": {rid: {"o": 5, "t": [1]}}},
                     rank=mgr.describe()["decode_rank"])
    r = mgr.result(rid)
    assert r["status"] == "failed" and "emission gap" in r["error"]
    assert [m.data["status"] for _t, m in delivered] == ["failed"]
    mgr.stop()


def test_dup_dropped_counts_a_redelivery_and_not_the_frames_repeat(
        tmp_path):
    comm = FrameComm(per_tick=3)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=3)
    comm.deliver = lambda rank, frames: feed(mgr, rank, frames[:-1])
    rid = mgr.submit("t1", [5, 9, 2], 9)["rid"]
    mgr._tick()
    assert mgr.describe()["dup_dropped"] == 0
    comm.overlap_next_reply = 2     # this reply also re-sends 2 old ones
    mgr._tick()
    assert mgr.describe()["dup_dropped"] == 2
    run_ticks(mgr, [rid])
    assert mgr.describe()["dup_dropped"] == 2
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 9, notices, delivered)
    mgr.stop()


# ----------------------------------------------------------------------
# journal before push, once; serve_done once


@pytest.mark.parametrize("path", ["frames", "reply"])
def test_the_journal_line_for_a_token_precedes_its_push(tmp_path, path):
    comm = FrameComm(per_tick=2)
    seen: list = []

    def on_push(msg):
        toks = ServeJournal.load(journal_path(str(tmp_path), "serve"))[
            msg.data["rid"]]["tokens"]
        if msg.msg_type == "serve_tokens":
            end = msg.data["o"] + len(msg.data["t"])
            assert toks[msg.data["o"]:end] == msg.data["t"]
        else:
            assert toks == msg.data["tokens"]
        seen.append(msg.msg_type)

    mgr, _d, _n = make_mgr(tmp_path, comm, steps=2)
    mgr._notify = lambda _t, m: on_push(m)
    mgr._deliver = lambda _t, m: on_push(m)
    if path == "frames":
        comm.deliver = lambda rank, frames: feed(mgr, rank, frames)
    rid = mgr.submit("t1", [5, 9, 2], 6)["rid"]
    run_ticks(mgr, [rid])
    assert seen.count("serve_done") == 1 and "serve_tokens" in seen
    mgr.stop()


def test_serve_done_is_delivered_once_when_the_last_tokens_came_by_frame(
        tmp_path):
    comm = FrameComm(per_tick=3)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=3)
    finished_by: list = []

    def deliver_all(rank, frames):
        for f in frames:            # the tick's last step's too
            feed(mgr, rank, [f])
        finished_by.append(mgr.result(rid)["done"])

    comm.deliver = deliver_all
    rid = mgr.submit("t1", [5, 9, 2], 6)["rid"]
    run_ticks(mgr, [rid])
    # done before the last tick's reply was applied: by a frame
    assert finished_by == [False, True]
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 6, notices, delivered)
    assert mgr.describe()["completed"] == 1
    mgr.stop()


def test_two_writers_of_one_stream_never_journal_or_push_a_token_twice(
        tmp_path):
    """The hazard: from reading how much a stream holds to extending it
    is one critical section.  A journal write slow enough that a second
    writer would read the same length inside it."""
    comm = FrameComm(per_tick=2)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=2)
    rid = mgr.submit("t1", [5, 9, 2], 12)["rid"]
    mgr._tick()
    rank = mgr.describe()["decode_rank"]
    have = mgr.result(rid)["tokens"]
    rest = expected_stream([5, 9, 2], 11)[len(have):]
    real_emit = mgr.journal.emit

    def slow_emit(*a):
        time.sleep(0.05)
        real_emit(*a)

    mgr.journal.emit = slow_emit
    frame = {"tenant": "serve", "seq": mgr._seq,
             "emitted": {rid: {"o": len(have), "t": rest[:5]}}}
    reply = {"emitted": {rid: {"o": len(have), "t": rest}}}
    mgr._on_frame(rank, frame_msg(rank, frame))
    writers = [threading.Thread(target=mgr._drain_frames),
               threading.Thread(target=mgr._apply_reply,
                                args=(reply,), kwargs={"rank": rank})]
    for t in writers:
        t.start()
    for t in writers:
        t.join(10)
    mgr.journal.emit = real_emit
    assert mgr.result(rid)["tokens"] == have + rest
    spans = emit_spans(journal_lines(tmp_path), rid)
    assert [a for a, _b in spans[1:]] == [b for _a, b in spans[:-1]]
    pushed, _ = pushes_of(notices, delivered, rid)
    assert [o for o, _t in pushed] == sorted({o for o, _t in pushed})
    assert sum(len(t) for _o, t in pushed) == len(have + rest)
    mgr.stop()


# ----------------------------------------------------------------------
# the applier: what has arrived is one push


def test_merge_frames_extends_overlaps_and_leaves_a_hole_to_the_reply():
    def f(seq, reply=None, **em):
        data = {"seq": seq, "now": float(seq),
                "emitted": {r: {"o": o, "t": list(t)}
                            for r, (o, t) in em.items()}}
        if reply is not None:
            data.update(reply=reply, step_s=0.5)
        return (0, data)

    got = merge_frames([f(4, a=(2, [7]), b=(0, [1])),
                        f(4, a=(3, [8]), b=(0, [1])),     # b again
                        f(4, a=(5, [9]), c=(1, [3])),     # a: hole
                        f(5, a=(6, [2]))])
    assert got == {
        (0, 4): {"now": 4.0, "replies": [],
                 "emitted": {"a": {"o": 2, "t": [7, 8]},
                             "b": {"o": 0, "t": [1]},
                             "c": {"o": 1, "t": [3]}}},
        (0, 5): {"now": 5.0, "replies": [],
                 "emitted": {"a": {"o": 6, "t": [2]}}}}
    assert merge_frames([]) == {}
    # a reply starts where its tick began, before its frames, and ends
    # a step after them: joined on both sides, and listed
    ev = object()
    (got,) = merge_frames([f(7, a=(3, [4]), b=(9, [1])),
                           f(7, a=(4, [5])),
                           f(7, reply=ev, a=(2, [3, 4, 5, 6]),
                             b=(8, [0, 1]))]).values()
    assert got["emitted"] == {"a": {"o": 2, "t": [3, 4, 5, 6]},
                              "b": {"o": 8, "t": [0, 1]}}
    assert [r["reply"] for r in got["replies"]] == [ev]


def test_a_waiting_reply_cuts_a_pass_of_frames_short_and_loses_nothing(
        tmp_path):
    comm = FrameComm(per_tick=3)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=3)

    def reply_is_waiting(rank, frames):
        before = len(notices)
        mgr._replies_waiting += 1       # as _hand_to_applier does
        feed(mgr, rank, frames)
        mgr._replies_waiting -= 1
        assert len(notices) == before   # not one frame was applied

    comm.deliver = reply_is_waiting
    rid = mgr.submit("t1", [5, 9, 2], 8)["rid"]
    run_ticks(mgr, [rid])
    assert_stream_is_the_reply_only_stream(
        mgr, tmp_path, rid, [5, 9, 2], 8, notices, delivered)
    pushed, _ = pushes_of(notices, delivered, rid)
    assert [len(t) for _o, t in pushed] == [3, 3]       # a tick a push
    mgr.stop()


def test_the_driver_applies_a_reply_itself_where_no_applier_runs(
        tmp_path):
    comm = FrameComm(per_tick=2)
    mgr, _d, _n = make_mgr(tmp_path, comm, steps=2)
    assert mgr._applier is None
    assert not mgr._hand_to_applier(0, {}, {}, 0.0)
    # and where it has ended while the driver waited for it
    mgr._applier = threading.Thread(target=lambda: None)
    mgr._applier.start()
    mgr._applier.join()
    rid = mgr.submit("t1", [5, 9, 2], 4)["rid"]
    run_ticks(mgr, [rid])
    assert mgr.result(rid)["tokens"] == expected_stream([5, 9, 2], 4)
    assert not mgr._frames and mgr._replies_waiting == 0
    mgr._applier = None
    mgr.stop()


def test_two_queued_frames_of_one_request_are_one_push(tmp_path):
    comm = FrameComm(per_tick=3)
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=3)
    # the applier was busy while the tick's first two frames arrived
    comm.deliver = lambda rank, frames: feed(mgr, rank, frames[:2])
    rid = mgr.submit("t1", [5, 9, 2], 7)["rid"]
    mgr._tick()
    pushed, _ = pushes_of(notices, delivered, rid)
    want = expected_stream([5, 9, 2], 3)
    assert pushed == [(0, want[:2]), (2, want[2:])]
    assert emit_spans(journal_lines(tmp_path), rid) == [(0, 2), (2, 3)]
    mgr.stop()


# ----------------------------------------------------------------------
# with the threads: the sink, the applier, start and stop


def test_the_applier_thread_applies_frames_beside_the_tick(tmp_path):
    comm = FrameComm(per_tick=4)
    comm.tick_ph = {"sync": 0.01}
    comm.frame_gap = 0.01           # a step between two frames
    comm.reply_delay = 0.03         # and the reply well after the last
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=4)
    mgr.start()
    try:
        assert comm.sinks == [mgr._on_frame]
        assert mgr._applier.is_alive()
        prompts = [[5, 9, 2], [7, 1], [3, 4, 8]]
        rids = [mgr.submit("t1", p, 12)["rid"] for p in prompts]
        wait_done(mgr, rids)
        # the last frame ended the streams; its tick ends a reply later
        assert mgr._tick_idle.wait(5)
        for rid, p in zip(rids, prompts):
            assert_stream_is_the_reply_only_stream(
                mgr, tmp_path, rid, p, 12, notices, delivered)
        d = mgr.describe()
        assert d["dup_dropped"] == 0
        tk = d["lat"]["summary"]["ticks"]
        # the applier kept up: a stream heard (nearly) every step
        assert tk["pushed_share"] > 0.5 and tk["steps_per_push"] < 2
        assert tk["totals"]["pushed"] == 36
        assert tk["applier"]["mean"] > 0
    finally:
        mgr.stop()
    assert comm.sinks == [] and not mgr._applier.is_alive()


def test_a_comm_without_the_sink_behaves_as_before(tmp_path):
    comm = FakeComm(per_tick=4)
    comm.tick_ph = {"sync": 0.01}
    assert not hasattr(comm, "add_notify_callback")
    mgr, delivered, notices = make_mgr(tmp_path, comm, steps=4)
    mgr.start()
    try:
        assert mgr._applier is None
        rid = mgr.submit("t1", [5, 9, 2], 8)["rid"]
        wait_done(mgr, [rid])
        assert_stream_is_the_reply_only_stream(
            mgr, tmp_path, rid, [5, 9, 2], 8, notices, delivered)
        pushed, _ = pushes_of(notices, delivered, rid)
        assert pushed == [(0, expected_stream([5, 9, 2], 4))]
        tk = mgr.describe()["lat"]["summary"]["ticks"]
        # every token arrived with its tick's reply: a tick a push
        assert tk["pushed_share"] == 0 and tk["steps_per_push"] == 4
        assert tk["frames"] == [0, 0]
        assert tk["totals"]["pushed_early"] == 0
        assert tk["totals"]["pushed"] == 8
    finally:
        mgr.stop()


def test_a_reply_that_fails_in_the_applier_is_the_drivers_to_raise(
        tmp_path):
    comm = FrameComm(per_tick=2)
    comm.reply_delay = 0.01
    flight_errors: list = []
    mgr, _d, _n = make_mgr(tmp_path, comm, steps=2)
    mgr._record = lambda event, **kw: flight_errors.append((event, kw))
    real, calls = mgr._apply_emitted, []

    def failing(emitted, rank, **kw):
        calls.append(kw.get("frame_seq"))
        if kw.get("frame_seq") is None and len(calls) < 4:
            raise ValueError("injected")
        return real(emitted, rank, **kw)

    mgr._apply_emitted = failing
    mgr.start()
    try:
        rid = mgr.submit("t1", [5, 9, 2], 4)["rid"]
        wait_done(mgr, [rid])       # the driver went on: a later tick
        assert mgr.result(rid)["tokens"] == expected_stream([5, 9, 2], 4)
        assert mgr._applier.is_alive()
        # the driver's own handler saw the failure, as before PR 38
        assert any(ev == "serve_driver_error" and "injected" in kw["error"]
                   for ev, kw in flight_errors)
    finally:
        mgr.stop()


# ----------------------------------------------------------------------
# the counters


def test_push_counters_are_absent_without_a_push_and_sum_over_ranks():
    obs = ServingObservatory()
    assert set(TICK_TOTALS) >= {"frames", "steps_emitting", "pushed_early",
                                "pushed", "pushes"}
    obs.note_tick(1, 0, {}, {"ph": {}})
    tk = obs.ticks_summary()
    assert not {"pushed_share", "steps_per_push", "frames"} & set(tk)
    assert tk["totals"]["pushes"] == 0
    # reply only on rank 0: 2 pushes of 8 tokens; frames on rank 1
    obs.note_tick(2, 0, {}, {"ph": {}}, pushed=[0, 16, 2])
    obs.note_tick(2, 1, {"applier": 0.002},
                  {"ph": {}, "fr": [7, 8]}, pushed=[21, 24, 16])
    tk = obs.ticks_summary()
    assert tk["pushed_share"] == round(21 / 40, 4)
    assert tk["steps_per_push"] == round(40 / 18, 3)
    assert tk["frames"] == [7, 8]
    assert tk["applier"]["p99"] == 2.0
    tot = tk["totals"]
    assert [tot[k] for k in ("frames", "steps_emitting", "pushed_early",
                             "pushed", "pushes")] == [7, 8, 21, 40, 18]


@pytest.mark.parametrize("frames, want", [
    (True, "pushed early 67%, 1 tokens/push"),
    (False, "pushed early 0%, 3 tokens/push")])
def test_serve_status_line_shows_the_push_counters(tmp_path, capsys,
                                                   frames, want):
    from nbdistributed_tpu.magics.magic import DistributedMagics
    comm = FrameComm(per_tick=3)
    comm.tick_ph = {"sync": 0.01}
    mgr, _d, _n = make_mgr(tmp_path, comm, steps=3)
    if frames:
        comm.deliver = lambda rank, fr: [feed(mgr, rank, [f])
                                         for f in fr[:-1]]
    else:
        comm.deliver = lambda rank, fr: None
    run_ticks(mgr, [mgr.submit("t1", [5, 9, 2], 9)["rid"]])
    DistributedMagics._render_serve_status(mgr.describe())
    mgr.stop()
    assert want in capsys.readouterr().out


# ----------------------------------------------------------------------
# the worker: a frame between two steps, the reply from the tick's start


def _worker(setup, sent, **kw):
    cfg, params = setup
    kw.setdefault("max_batch", 2)
    srv = DecodeServer(params, cfg, pad_to=4, kv_block_tokens=8,
                       max_len=64, **kw)
    w = object.__new__(worker_mod.DistributedWorker)
    w.rank = 3
    w._epoch = 5
    w._serve = {"serve": worker_mod._WorkerServe(srv)}
    w._serve_snap = None
    w._send_shielded = sent.append
    return w


def test_worker_sends_a_frame_between_two_steps_whatever_the_rows(setup):
    sent: list = []
    w = _worker(setup, sent)
    admit = [{"rid": "a", "prompt": [5, 9, 2], "max_new": 20},
             {"rid": "b", "prompt": [7, 1], "max_new": 22}]
    got = {"a": [], "b": []}         # as a gateway merges: by offset
    replies = {"a": [], "b": []}     # the replies alone
    for seq in (1, 2):
        del sent[:]
        d = _step(w, seq, admit=admit if seq == 1 else (), steps=8)
        # one frame before each step that has something to send.  The
        # first tick: the admissions' tokens before its first step,
        # nothing before its second (the first had no step to fetch),
        # six more; the second: before every step but its first (what
        # the step before that fetched left with the first's reply)
        assert len(sent) == 7
        assert d["tick"]["fr"] == [7, 8 - (seq == 1)]
        for m in sent:
            assert (m.msg_type, m.rank, m.tenant, m.epoch) \
                == ("serve_emit", 3, "serve", 5)
            assert set(m.data) == {"tenant", "seq", "emitted", "now"}
            assert m.data["seq"] == seq and m.data["tenant"] == "serve"
            assert set(m.data["emitted"]) == {"a", "b"}
            for rid, em in m.data["emitted"].items():
                assert em["o"] == len(got[rid])       # contiguous
                got[rid] += em["t"]
            # a row gets one token a step; the first frame of the
            # first tick is the admissions' tokens alone (its first
            # step had no step before it to fetch)
            assert all(len(em["t"]) == 1
                       for em in m.data["emitted"].values())
        # the reply: every token of the tick, from where it began
        for rid, em in d["emitted"].items():
            assert em["o"] == len(replies[rid])
            replies[rid] += em["t"]
            assert got[rid] == replies[rid][:len(got[rid])]
            assert len(replies[rid]) - len(got[rid]) == 1   # last step's
            got[rid] = list(replies[rid])
    d = _step(w, 3, steps=8)
    for rid, em in d["emitted"].items():
        replies[rid] += em["t"]
    assert replies["a"] == solo(setup, [5, 9, 2], 20)
    assert replies["b"] == solo(setup, [7, 1], 22)
    assert sorted(d["finished"]) == ["a", "b"]
    st = w._serve["serve"]
    assert st.tokens_total == 42 and st.sent == {"a": 20, "b": 22}


def test_worker_sends_no_frame_with_nothing_new_to_send(setup):
    sent: list = []
    w = _worker(setup, sent)
    d = _step(w, 1, steps=8)                 # nothing to decode
    assert sent == [] and d["tick"]["fr"] == [0, 0] and not d["emitted"]
    # a prompt admitted whole has its first token before the first
    # step: it leaves at once, and the reply repeats it
    d = _step(w, 2, admit=[{"rid": "a", "prompt": [5, 9, 2],
                            "max_new": 4}], steps=1)
    first = {"o": 0, "t": solo(setup, [5, 9, 2], 1)}
    assert [m.data["emitted"] for m in sent] == [{"a": first}]
    assert d["tick"]["fr"] == [1, 0] and d["emitted"]["a"] == first
    # a tick of one step: the step's token leaves with the reply
    del sent[:]
    d = _step(w, 3, steps=1)
    assert sent == [] and d["tick"]["fr"] == [0, 1]
    assert d["emitted"]["a"] == {"o": 1, "t": solo(setup, [5, 9, 2], 2)[1:]}


def test_worker_tick_survives_a_channel_that_cannot_send(setup):
    def broken(_msg):
        raise OSError("no coordinator")

    w = _worker(setup, [])
    w._send_shielded = broken
    d = _step(w, 1, admit=[{"rid": "a", "prompt": [5, 9, 2],
                            "max_new": 6}], steps=8)
    # the frames are lost, the reply still carries the tick whole
    assert d["emitted"]["a"] == {"o": 0, "t": solo(setup, [5, 9, 2], 6)}
    assert d["tick"]["fr"][0] >= 1
