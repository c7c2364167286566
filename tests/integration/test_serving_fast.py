"""Serving fast path under chaos (ISSUE 17), end to end on the CPU
backend: the closed-loop load generator drives a PAGED, MULTI-RANK
decode plane at roughly twice its measured sustainable rate while a
decode rank is SIGKILLed mid-run and the survivors drop 8% of
control-plane frames.

The contract under test:

1. **Exactly-once under overload + faults**: every ACCEPTED request
   reaches a terminal verdict exactly once — completed requests carry
   their EXACT solo-``generate`` greedy streams (journal-replay
   re-admission across the failover is bit-identical), everything
   else carries an explicit shed/rejected verdict.  Zero hung
   requests, zero silent drops (the loadgen report's conservation
   check is the arbiter).
2. **Multi-rank decode actually uses the slice**: more than one rank
   takes placements (per-rank ``ranks`` telemetry from
   ``serve_status``), and per-rank KV-block occupancy reaches the
   pool-status heartbeat surface.
3. **Chunked prefill bounds TPOT**: a long prompt streams in chunks
   between decode ticks, so an active short stream keeps emitting
   while the long prompt prefills — and both streams stay bit-exact.

Marked ``slow`` on purpose (pool spin-up); the CI resilience job owns
these (marker ``serve``).  ``test_loadgen_smoke_two_ranks`` is the
~15s CI smoke; the chaos scenario is the full drill.
"""

import ast
import time

import pytest

from nbdistributed_tpu.gateway.client import TenantClient
from nbdistributed_tpu.gateway.daemon import GatewayDaemon
from nbdistributed_tpu.gateway.scheduler import SchedPolicy
from nbdistributed_tpu.observability import flightrec
from nbdistributed_tpu.resilience.faults import FaultPlan
from nbdistributed_tpu.serving_fast import LoadConfig, run_load, \
    synth_schedule, validate_report
from nbdistributed_tpu.serving_fast.loadgen import ClientTransport

pytestmark = [pytest.mark.integration, pytest.mark.serve,
              pytest.mark.gateway, pytest.mark.faults,
              pytest.mark.slow]

WORLD = 3

SPEC = (
    "import jax as _j, jax.numpy as _jn\n"
    "from nbdistributed_tpu.models import tiny_config, init_params\n"
    "cfg = tiny_config(dtype=_jn.float32, use_flash=False)\n"
    "params = init_params(_j.random.PRNGKey(0), cfg)\n")


@pytest.fixture(scope="module")
def pool(tmp_path_factory):
    import os
    run_dir = str(tmp_path_factory.mktemp("servefast"))
    old = {k: os.environ.get(k)
           for k in ("NBD_RUN_DIR", "NBD_RETRY_TIMEOUT_S",
                     "NBD_RETRY_ATTEMPTS")}
    os.environ["NBD_RUN_DIR"] = run_dir
    # Retry layer ON: the 8%-drop phase leans on same-msg-id
    # redelivery + the worker replay cache.
    os.environ["NBD_RETRY_TIMEOUT_S"] = "5"
    os.environ["NBD_RETRY_ATTEMPTS"] = "6"
    flightrec.reset_for_tests()
    gw = GatewayDaemon(
        WORLD, backend="cpu",
        policy=SchedPolicy("fair", mesh_slots=1, tenant_inflight=16,
                           queue_depth=32),
        request_timeout=None, attach_timeout=240.0)
    try:
        yield gw
    finally:
        gw.close()
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def attach(pool, name, **kw):
    return TenantClient(pool.tenant_host, pool.tenant_port, name,
                        pool_token=pool.pool_token, **kw)


def solo_refs(client, jobs) -> list[list[int]]:
    """Solo ``generate`` references for ``[(prompt, max_new)]``,
    computed ON rank 0 (same process family as the decode ranks, so
    the equality check cannot hinge on XLA flag differences)."""
    cell = (
        "import jax as _j, jax.numpy as _jn, numpy as _np\n"
        "from nbdistributed_tpu.models import (tiny_config, "
        "init_params, generate)\n"
        "_cfg = tiny_config(dtype=_jn.float32, use_flash=False)\n"
        "_p = init_params(_j.random.PRNGKey(0), _cfg)\n"
        f"_jobs = {jobs!r}\n"
        "[[int(t) for t in _np.asarray(generate(_p, _jn.asarray(pr, "
        "_jn.int32)[None], _cfg, n))[0][len(pr):]] "
        "for pr, n in _jobs]")
    out = client.execute(cell, target_ranks=[0], timeout=600)
    results = out.get("results") or {}
    assert "0" in results, out
    return ast.literal_eval(results["0"].get("output"))


def assert_completed_bit_identical(client, cfg, report):
    plan = synth_schedule(cfg)      # deterministic: same cfg = same plan
    comp = [r for r in (report.get("requests") or ())
            if r["status"] == "completed"]
    assert comp, f"no completed requests to check: {report}"
    jobs = [(plan[r["i"]]["prompt"], plan[r["i"]]["max_new"])
            for r in comp]
    refs = solo_refs(client, jobs)
    for r, ref in zip(comp, refs):
        assert r["tokens"] == ref, \
            (f"request {r['rid']} (plan item {r['i']}): "
             f"{r['tokens']} != solo {ref}")


def wait_result(client, rid, timeout=240.0) -> dict:
    deadline = time.time() + timeout
    while time.time() < deadline:
        r = client.serve_result(rid)
        if r.get("done"):
            return r
        time.sleep(0.05)
    raise AssertionError(
        f"{rid} never terminal: {client.serve_status()}")


# ----------------------------------------------------------------------


def test_loadgen_smoke_two_ranks(pool):
    """The CI smoke: a short closed-loop run against a 2-decode-rank
    paged plane — everything offered terminalizes, nothing hangs, the
    report passes the pinned-schema + conservation check, and every
    completed stream is bit-identical to its solo reference."""
    t = attach(pool, "smoke")
    try:
        t.serve_start(SPEC, max_batch=2, max_len=48, pad_to=4,
                      steps=2, queue_depth=8, inflight=64,
                      decode_ranks=2, kv_block_tokens=8, timeout=600)
        cfg = LoadConfig(rps=3.0, duration_s=4.0, seed=1,
                         prompt_len=(2, 5), max_new=(4, 4),
                         drain_s=120.0, detail=True)
        rep = run_load(ClientTransport(t), cfg)
        validate_report(rep)
        assert rep["hung"] == 0 and rep["failed"] == 0, rep
        assert rep["completed"] > 0
        assert rep["slo"]["pass"] is True   # no targets, nothing hung
        assert_completed_bit_identical(t, cfg, rep)
        st = t.serve_status()
        assert len(st["decode_ranks"]) == 2, st
        assert st["kv"]["block_tokens"] == 8
        # Each rank's server reports the compiled Pallas kernels in
        # its decode step; the CPU interprets them, so none.
        assert [v["step_kernels"] for v in st["ranks"].values()] \
            == [0, 0], st
        assert t.serve_stop()["status"] == "stopped"
    finally:
        try:
            t.serve_stop()
        except Exception:
            pass
        t.close(detach=True)


def test_overload_sigkill_drops_exactly_once_multirank(pool):
    """The headline drill: calibrate the plane's sustainable rate,
    then offer ~2x that while a decode rank is SIGKILLed mid-run and
    the survivors drop 8% of frames.  Every accepted request
    terminalizes exactly once — completed streams bit-identical to
    solo, overload handled with EXPLICIT shed/rejected verdicts,
    zero hung — and both decode ranks demonstrably served."""
    t = attach(pool, "chaos")
    ranks_seen: set = set()
    try:
        t.serve_start(SPEC, max_batch=2, max_len=48, pad_to=4,
                      steps=2, queue_depth=4, inflight=64,
                      decode_ranks=2, kv_block_tokens=8, timeout=600)

        # Phase A — calibration at a modest rate (no faults).
        cal = LoadConfig(rps=3.0, duration_s=3.0, seed=11,
                         prompt_len=(2, 5), max_new=(4, 4),
                         drain_s=120.0, detail=True)
        rep_a = run_load(ClientTransport(t), cal)
        validate_report(rep_a)
        assert rep_a["hung"] == 0, rep_a
        rate = rep_a["completed"] / max(rep_a["duration_s"], 1e-9)

        # Phase B — ~2x overload with a mid-run SIGKILL + 8% drops.
        state = {"killed": None, "dropped": False, "n": 0}

        def on_progress(counts, n_open):
            state["n"] += 1
            now = time.monotonic()
            if state["killed"] is None and counts["accepted"] >= 4:
                # Seeded SIGKILL on the HIGHEST decode rank: dies on
                # its 3rd control message — a serve_step mid-decode.
                kill = WORLD - 1
                pool.comm.send_to_ranks([kill], "chaos", {
                    "action": "set",
                    "spec": {"seed": 5, "kill_rank": kill,
                             "kill_at": 3}}, timeout=60)
                state["killed"] = now
            elif state["killed"] is not None \
                    and not state["dropped"] \
                    and now - state["killed"] > 2.0:
                live = sorted(set(range(WORLD))
                              - pool.comm.dead_ranks())
                pool.comm.send_to_ranks(live, "chaos", {
                    "action": "set",
                    "spec": {"seed": 9, "drop": 0.08}}, timeout=60)
                pool.comm.set_fault_plan(FaultPlan(seed=11,
                                                   drop=0.08))
                state["dropped"] = True
            if state["n"] % 25 == 0:
                try:
                    for rk, v in (t.serve_status().get("ranks")
                                  or {}).items():
                        if v.get("placed", 0) > 0:
                            ranks_seen.add(rk)
                except Exception:
                    pass

        over = LoadConfig(rps=max(6.0, 2.0 * rate), duration_s=6.0,
                          seed=12, prompt_len=(2, 5), max_new=(4, 4),
                          drain_s=150.0, detail=True)
        try:
            rep_b = run_load(ClientTransport(t), over,
                             on_progress=on_progress)
        finally:
            pool.comm.set_fault_plan(None)
            live = sorted(set(range(WORLD))
                          - pool.comm.dead_ranks())
            pool.comm.send_to_ranks(live, "chaos",
                                    {"action": "clear"}, timeout=60)

        # Zero silent drops: conservation + zero hung is the contract.
        validate_report(rep_b)
        assert rep_b["hung"] == 0, rep_b
        assert rep_b["failed"] == 0, rep_b
        assert rep_b["completed"] > 0, rep_b
        # 2x overload against a 4-slot plane with a depth-4 queue must
        # shed — with a DELIVERED verdict, never silence.
        assert rep_b["shed"] + rep_b["rejected"] >= 1, rep_b
        # Exactly-once, bit-identical: every completed stream (both
        # phases — phase A's plan is disjoint by seed) equals solo.
        assert_completed_bit_identical(t, cal, rep_a)
        assert_completed_bit_identical(t, over, rep_b)

        st = t.serve_status()
        assert st["failovers"] >= 1, st      # the kill landed
        assert st["replayed"] >= 1, st       # journal re-admission
        assert st["dup_dropped"] == 0, st    # offset dedup never fired
        assert len(st["decode_ranks"]) == 2, st
        # Multi-rank decode demonstrably used >1 rank.
        assert len(ranks_seen) >= 2, \
            f"placements only ever seen on ranks {ranks_seen}"
        # Per-rank KV telemetry reached the heartbeat surface.
        deadline = time.time() + 30
        seen_kvb = False
        while time.time() < deadline and not seen_kvb:
            seen_kvb = any((v.get("srv") or {}).get("kvb")
                           for v in pool.status()["ranks"].values())
            if not seen_kvb:
                time.sleep(1.0)
        assert seen_kvb, "no kvb heartbeat piggyback ever arrived"
        status = pool.status()
        assert not status.get("hang_verdicts"), \
            status["hang_verdicts"]
    finally:
        try:
            t.serve_stop()
        except Exception:
            pass
        t.close(detach=True)


def test_chunked_prefill_keeps_short_stream_alive(pool):
    """A 56-token prompt admitted while a short request decodes: with
    ``prefill_chunk`` armed the prompt streams in 4-token chunks
    BETWEEN decode ticks, so the short stream keeps emitting during
    the prefill window (bounded TPOT) — and both streams stay
    bit-identical to their solo references."""
    t = attach(pool, "chunk")
    try:
        t.serve_start(SPEC, max_batch=2, max_len=64, pad_to=4,
                      steps=1, queue_depth=8, inflight=8,
                      decode_ranks=1, kv_block_tokens=8,
                      prefill_chunk=4, timeout=600)
        short_p, short_n = [5, 9, 2], 30
        long_p, long_n = [((7 * i) % 50) + 1 for i in range(56)], 4
        rid_s = t.serve_submit(short_p, short_n)["rid"]
        # Let the short stream start, then admit the long prompt.
        deadline = time.time() + 60
        while not t.serve_result(rid_s).get("tokens"):
            assert time.time() < deadline
            time.sleep(0.05)
        before = len(t.serve_result(rid_s)["tokens"])
        rid_l = t.serve_submit(long_p, long_n)["rid"]
        # While the long prompt prefills (14 chunks, one per tick),
        # the short stream must keep emitting.
        progressed = 0
        while time.time() < deadline:
            rl = t.serve_result(rid_l)
            n_short = len(t.serve_result(rid_s)["tokens"])
            if not rl.get("tokens") and n_short > before:
                progressed = n_short - before
            if rl.get("tokens") or rl.get("done"):
                break
            time.sleep(0.02)
        assert progressed > 0, \
            "short stream starved during the long prefill"
        rs, rl = wait_result(t, rid_s), wait_result(t, rid_l)
        assert rs["status"] == "completed"
        assert rl["status"] == "completed"
        refs = solo_refs(t, [(short_p, short_n), (long_p, long_n)])
        assert rs["tokens"] == refs[0]
        assert rl["tokens"] == refs[1]
        st = t.serve_status()
        assert st["dup_dropped"] == 0, st
    finally:
        try:
            t.serve_stop()
        except Exception:
            pass
        t.close(detach=True)


def test_stage_attribution_and_metrics_consistency(pool):
    """ISSUE 18 pins on a real loadgen run:

    1. Every completed request's contiguous stage decomposition sums
       to its observed e2e within 10% (the acceptance bound; the
       telescoping construction makes it exact, so we also pin 1ms).
    2. TTFT == admit + queue + kv_alloc + prefill (same tolerance).
    3. The loadgen report and the /metrics exposition agree: the
       accepted/shed/rejected verdict counters and the stage-histogram
       completion count match what the CLIENT observed (satellite 3).
    """
    from nbdistributed_tpu.observability import metrics as obs_metrics
    from nbdistributed_tpu.observability.servingobs import SERVE_STAGES

    def metric(line_prefix):
        text = obs_metrics.registry().prometheus_text()
        for ln in text.splitlines():
            if ln.startswith(line_prefix):
                return float(ln.rsplit(" ", 1)[1])
        return None

    # The registry is process-global and the pool fixture is module-
    # scoped, so earlier tests' serving counters are still in it:
    # every counter assertion below is on the DELTA across this run.
    # The verdict/token counters carry the serving plane's OWN tenant
    # label ("serve" — the manager's name, not the attaching tenant);
    # only the per-request stage histograms attribute to "latpin".
    def counters():
        return {
            "accepted": metric('nbd_serve_requests_total'
                               '{tenant="serve",verdict="accepted"}')
            or 0.0,
            "shed": metric('nbd_serve_requests_total'
                           '{tenant="serve",verdict="shed"}') or 0.0,
            "rejected": metric('nbd_serve_requests_total'
                               '{tenant="serve",verdict="rejected"}')
            or 0.0,
            "tokens": metric('nbd_serve_tokens_total'
                             '{tenant="serve"}') or 0.0,
        }

    t = attach(pool, "latpin")
    try:
        t.serve_start(SPEC, max_batch=2, max_len=48, pad_to=4,
                      steps=2, queue_depth=8, inflight=64,
                      decode_ranks=2, kv_block_tokens=8, timeout=600)
        before = counters()
        cfg = LoadConfig(rps=3.0, duration_s=4.0, seed=7,
                         prompt_len=(2, 5), max_new=(4, 4),
                         drain_s=120.0)
        rep = run_load(ClientTransport(t), cfg)
        validate_report(rep)
        assert rep["completed"] > 0 and rep["hung"] == 0, rep

        st = t.serve_status()
        lat = st.get("lat") or {}
        recs = lat.get("records") or []
        finished = [r for r in recs
                    if r["status"] in ("completed", "failed")]
        assert len(finished) >= rep["completed"], (len(finished), rep)
        for r in finished:
            total = sum(r["stages"][s] for s in SERVE_STAGES)
            assert abs(total - r["e2e_s"]) <= max(1e-3,
                                                  0.10 * r["e2e_s"]), \
                (r["rid"], total, r["e2e_s"], r["stages"])
            ttft = (r["stages"]["admit"] + r["stages"]["queue"]
                    + r["stages"]["kv_alloc"] + r["stages"]["prefill"])
            assert abs(ttft - r["ttft_s"]) <= 1e-3, (r["rid"], r)
            assert all(r["stages"][s] >= 0.0 for s in SERVE_STAGES), r
        summ = lat.get("summary") or {}
        assert summ.get("count", 0) >= rep["completed"]

        # Report <-> /metrics consistency: the exposition text is
        # exactly what the scrape endpoint serves.
        after = counters()
        assert after["accepted"] - before["accepted"] \
            == rep["accepted"], (before, after, rep)
        assert after["shed"] - before["shed"] == rep["shed"], \
            (before, after, rep)
        assert after["rejected"] - before["rejected"] \
            == rep["rejected"], (before, after, rep)
        assert after["tokens"] - before["tokens"] \
            >= rep["tokens_total"], (before, after, rep)
        # One stage-histogram observation per finished request, and
        # the stage attribution carries the ATTACHING tenant's name
        # ("latpin" is unique to this test, so no delta needed).
        n = metric('nbd_serve_stage_seconds_count'
                   '{stage="decode",tenant="latpin"}')
        assert n == len(finished), (n, len(finished))
    finally:
        try:
            t.serve_stop()
        except Exception:
            pass
        t.close(detach=True)
