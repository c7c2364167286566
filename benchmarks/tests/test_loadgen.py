"""The load generator: a pure function of the seed, the same work in
every seed, latency from the due instant."""

import threading
import time

from benchmarks import harness as H
from benchmarks import loadgen

CHAT = H.load_json("traffic", "chat_open.json")
DOCS = H.load_json("traffic", "docs_closed.json")


def test_plan_is_a_pure_function_of_the_seed():
    a = loadgen.plan(CHAT, 7, 45.0, 32000)
    b = loadgen.plan(CHAT, 7, 45.0, 32000)
    c = loadgen.plan(CHAT, 8, 45.0, 32000)
    assert a == b and a != c


def test_every_seed_offers_the_same_work():
    """Open loop: the same number of requests and the same gaps in every
    seed, and the same lengths in another order: totals are equal (margin
    0).  Closed loop: the queue is stratified in blocks of 20, so whole
    blocks are equal."""
    tot = []
    for seed in (1, 2, 3, 2**31 + 5):
        p = loadgen.plan(CHAT, seed, 45.0, 32000)
        tot.append((len(p), sum(len(r["prompt"]) for r in p),
                    sum(r["max_new"] for r in p),
                    round(p[-1]["at"], 6)))
    assert len({t[0] for t in tot}) == 1 and len({t[3] for t in tot}) == 1
    for k in (1, 2):
        vals = [t[k] for t in tot]
        assert max(vals) == min(vals), vals
    assert all(0 < r["at"] < 45.0 for r in loadgen.plan(CHAT, 1, 45.0, 32000))
    q = [loadgen.plan(DOCS, s, 45.0, 32000)[:40] for s in (1, 2)]
    assert sum(len(r["prompt"]) for r in q[0]) == \
        sum(len(r["prompt"]) for r in q[1])


class _SlowClient:
    """Accepts after a stall and pushes two tokens a little later."""

    def __init__(self, stall):
        self.stall, self.on_serve, self.n = stall, None, 0

    def serve_submit(self, prompt, max_new):
        time.sleep(self.stall)
        self.n += 1
        rid = f"r{self.n}"

        def push():
            time.sleep(0.05)
            for data in ({"rid": rid, "o": 0, "t": [1]},
                         {"rid": rid, "status": "completed",
                          "tokens": [1, 2]}):
                if self.on_serve is not None:
                    self.on_serve(data)
                time.sleep(0.05)
        threading.Thread(target=push, daemon=True).start()
        return {"status": "accepted", "rid": rid}


def test_latency_is_timed_from_the_due_instant_and_lateness_reported():
    traffic = dict(CHAT, rate_per_s=20.0, prompt_len=[4, 8], max_new=[2, 2])
    reqs = loadgen.plan(traffic, 3, 1.0, 100)
    fast = loadgen.Load(_SlowClient(0.0), reqs, traffic, 1.0)
    fast.run(2.0)
    slow = loadgen.Load(_SlowClient(0.1), reqs, traffic, 1.0)
    slow.run(5.0)
    f, s = fast.summary(H.quantile), slow.summary(H.quantile)
    assert f["offered"] == s["offered"] == len(reqs)
    assert 40 < f["ttft_p50_ms"] < 90
    # a generator stalled 100 ms a request falls behind its schedule: the
    # wait counts in the latency and shows as lateness
    assert s["ttft_p50_ms"] > f["ttft_p50_ms"] + 150
    assert s["late_p99_ms"] > 500 and f["late_p99_ms"] < 20
    assert f["failed"] == 0 and all(len(st.tokens) == 2
                                    for st in fast.finished())
