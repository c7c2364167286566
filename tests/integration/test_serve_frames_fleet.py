"""Emission a step on a real one-rank fleet (ISSUE 38): a worker process
on the CPU backend, the real control plane, and a :class:`ServingManager`
on the coordinator's comm.  The worker's ``serve_emit`` frames cross the
wire, the comm's IO thread hands them to the manager's sink, its applier
applies them beside the tick: streams equal ``generate``, a client hears
a token a push while the tick is eight steps, and the counters say so.
"""

import json
import time

import pytest

from nbdistributed_tpu.gateway.serving import (ServeJournal,
                                               ServingManager,
                                               journal_path)
from nbdistributed_tpu.manager import ProcessManager, wait_until_ready
from nbdistributed_tpu.messaging import CommunicationManager

pytestmark = [pytest.mark.integration, pytest.mark.serve]

SPEC = (
    "import jax as _j, jax.numpy as _jn\n"
    "from nbdistributed_tpu.models import tiny_config, init_params\n"
    "cfg = tiny_config(dtype=_jn.float32, use_flash=False, n_layers=1)\n"
    "params = init_params(_j.random.PRNGKey(0), cfg)\n")
STEPS = 8
PROMPTS = [[5, 9, 2], [7, 1], [3, 4, 8, 6]]
MAX_NEW = 30


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One fleet, one serving plane, three requests served to the end:
    what every test below reads."""
    run_dir = str(tmp_path_factory.mktemp("frames"))
    comm = CommunicationManager(num_workers=1, timeout=120)
    pm = ProcessManager()
    pm.add_death_callback(lambda rank, rc: comm.mark_worker_dead(rank))
    pushes: list = []           # (t, kind, data) as a client would see
    mgr = None
    try:
        pm.start_workers(1, comm.port, backend="cpu")
        wait_until_ready(comm, pm, 120)
        mgr = ServingManager(
            comm, run_dir, spec=SPEC, world_size=1, max_batch=4,
            max_len=64, pad_to=4, steps=STEPS, kv_block_tokens=8,
            step_timeout=120.0,
            notify=lambda _t, m: pushes.append(
                (time.monotonic(), m.msg_type, m.data)),
            deliver=lambda _t, m: pushes.append(
                (time.monotonic(), m.msg_type, m.data)))
        mgr.start()
        rids = [mgr.submit("t1", p, MAX_NEW)["rid"] for p in PROMPTS]
        deadline = time.monotonic() + 240
        while not all(mgr.result(r)["done"] for r in rids):
            assert time.monotonic() < deadline, mgr.describe()
            time.sleep(0.02)
        assert mgr._tick_idle.wait(30)
        # the reference, computed where the weights are
        resp = comm.send_to_ranks([0], "execute", {
            "code": ("from nbdistributed_tpu.models import generate\n"
                     "import numpy as _np\n"
                     f"[[int(t) for t in _np.asarray(generate(params, "
                     f"_jn.asarray(p, _jn.int32)[None], cfg, {MAX_NEW}))"
                     f"[0][len(p):]] for p in {PROMPTS!r}]"),
            "target_ranks": [0]}, tenant="serve", timeout=240)
        want = json.loads(resp[0].data["output"])
        yield {"mgr": mgr, "rids": rids, "want": want, "pushes": pushes,
               "run_dir": run_dir, "status": mgr.describe(),
               "comm": comm}
    finally:
        if mgr is not None:
            mgr.stop()
        comm.post([0], "shutdown")
        time.sleep(0.5)
        pm.shutdown()
        comm.shutdown()


def test_streams_equal_generate_and_the_journal(served):
    state = ServeJournal.load(journal_path(served["run_dir"], "serve"))
    for rid, want in zip(served["rids"], served["want"]):
        r = served["mgr"].result(rid)
        assert r["status"] == "completed" and r["tokens"] == want
        assert state[rid]["tokens"] == want
        assert state[rid]["done"] == "completed"
    assert served["status"]["dup_dropped"] == 0
    assert served["status"]["failovers"] == 0


def test_a_client_hears_a_step_a_push_not_a_tick(served):
    for rid, want in zip(served["rids"], served["want"]):
        toks = [d for _t, kind, d in served["pushes"]
                if kind == "serve_tokens" and d["rid"] == rid]
        pos = 0
        for d in toks:              # contiguous, exact, in order
            assert d["o"] == pos and d["t"] == want[pos:pos + len(d["t"])]
            pos += len(d["t"])
        done = [d for _t, kind, d in served["pushes"]
                if kind == "serve_done" and d["rid"] == rid]
        assert len(done) == 1 and done[0]["tokens"] == want
        # a reply-only stream of 30 tokens is 4 pushes of up to 8
        assert len(toks) >= 12
        assert sum(len(d["t"]) == 1 for d in toks) >= len(toks) // 2


def test_the_counters_say_how_often_it_engaged(served):
    tk = served["status"]["lat"]["summary"]["ticks"]
    tot = tk["totals"]
    assert tot["pushed"] == len(PROMPTS) * MAX_NEW
    # a tick's last step arrives with its reply: 7 of 8 at the most
    assert 0.5 < tk["pushed_share"] <= 7 / 8 + 0.05
    assert 1.0 <= tk["steps_per_push"] < 3.0
    assert tot["frames"] >= 12 and tot["steps_emitting"] >= tot["frames"]
    assert tk["frames"][0] == tot["frames"]
    assert tot["pushes"] >= tot["frames"]       # one a row a frame
    assert tk["applier"]["mean"] > 0


def test_the_sink_is_gone_after_stop(served):
    mgr, comm = served["mgr"], served["comm"]
    assert mgr._on_frame in comm._notify_callbacks
    # (stop runs in the fixture's teardown; a second manager's stop
    # shows the removal without ending the module's plane)
    other = ServingManager(comm, served["run_dir"], tenant="other",
                           world_size=1)
    other.start()
    assert other._on_frame in comm._notify_callbacks
    other.stop(close_workers=False)
    assert other._on_frame not in comm._notify_callbacks
    assert not other._applier.is_alive()
    assert mgr._on_frame in comm._notify_callbacks
