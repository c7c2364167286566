"""The benchmark's load generator: one general generator that reads a
traffic file's parameters, and a loop that offers the plan through the
gateway client and stamps what the client saw.

A copy, in spirit, of ``serving_fast/loadgen.py`` (its ``synth_schedule``
is a pure function of the seed, and so is ``plan`` here), changed where
the measurement needs it: latency is timed from the instant a request
was *due*, the generator reports its own lateness, token times come from
the gateway's pushes (no polling), and every seed offers the same sizes
and the same gaps between arrivals in another order.  Stdlib only.

Traffic keys: ``loop`` (``open``/``closed``), ``rate_per_s`` (open),
``clients`` (closed), ``prompt_len`` and ``max_new`` as [lo, hi].
"""

from __future__ import annotations

import math
import random
import threading
import time

BLOCK = 10      # a closed loop's queue repeats the same sizes every BLOCK


def _stratified(lo: int, hi: int, n: int) -> list[int]:
    """n values spread evenly over [lo, hi]."""
    return [lo + round((hi - lo) * (i + 0.5) / n) for i in range(n)]


def sizes(traffic: dict, n: int, rng: random.Random,
          block: int) -> list[tuple[int, int]]:
    """n (prompt_len, max_new) pairs in blocks of ``block``.  Open loop
    (one block, the count is known): both lengths cover their range
    evenly and the seed pairs and orders them, so totals are equal in
    every seed.  Closed loop (the count depends on the system): every
    block is the same ``block`` pairs (the i-th prompt length with the
    (3i mod block)-th output length, so long does not always meet long)
    and the seed only orders them; seeds then differ in order alone."""
    out = []
    p = _stratified(*traffic["prompt_len"], block)
    m = _stratified(*traffic["max_new"], block)
    while len(out) < n:
        if traffic["loop"] == "open":
            pp, mm = list(p), list(m)
            rng.shuffle(pp)
            rng.shuffle(mm)
            pairs = list(zip(pp, mm))
        else:
            pairs = [(p[i], m[(3 * i) % block]) for i in range(block)]
            rng.shuffle(pairs)
        out += pairs
    return out[:n]


def plan(traffic: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """The offered load, a pure function of its arguments.  Open loop:
    ``round(rate * seconds)`` requests whose gaps are the quantiles of
    the exponential distribution at that rate, shuffled (a Poisson
    stream with the same gaps in every seed).  Closed loop: a queue of
    requests, long enough for the window, that free clients draw from
    in order (``at`` is None)."""
    rng = random.Random(seed)
    if traffic["loop"] == "open":
        n = max(1, round(traffic["rate_per_s"] * seconds))
        gaps = [-math.log(1 - (i + 0.5) / n) / traffic["rate_per_s"]
                for i in range(n)]
        rng.shuffle(gaps)
        scale = seconds / sum(gaps) * n / (n + 1)   # last arrival inside
        at, t = [], 0.0
        for g in gaps:
            t += g * scale
            at.append(t)
    else:
        n = int(traffic["queue_len"])
        at = [None] * n
    block = n if traffic["loop"] == "open" else BLOCK
    reqs = []
    for i, (plen, new) in enumerate(sizes(traffic, n, rng, block)):
        r = random.Random(seed * 1000003 + i)
        reqs.append({"i": i, "at": at[i], "max_new": new,
                     "prompt": [r.randrange(vocab) for _ in range(plen)]})
    return reqs


class _Stream:
    __slots__ = ("i", "rid", "due", "sent", "times", "tokens", "status",
                 "end", "prompt", "max_new")

    def __init__(self, req, due):
        self.i, self.due, self.rid = req["i"], due, None
        self.prompt, self.max_new = req["prompt"], req["max_new"]
        self.sent = None
        self.times: list[float] = []    # arrival time of every token
        self.tokens: list[int] = []
        self.status, self.end = "offered", None


class Load:
    """Offer a plan through a connected gateway client for ``seconds``
    and keep what came back.  One thread submits; the client's reader
    thread stamps token pushes."""

    def __init__(self, client, reqs: list[dict], traffic: dict,
                 seconds: float):
        self.client, self.reqs, self.traffic = client, reqs, traffic
        self.seconds = seconds
        self.streams: list[_Stream] = []
        self._by_rid: dict[str, _Stream] = {}
        self._early: dict[str, list] = {}   # pushes that beat the verdict
        self._lock = threading.Lock()
        self._freed = threading.Semaphore(0)
        self.t0 = None

    # reader thread
    def _on_serve(self, data: dict):
        now = time.monotonic()
        with self._lock:
            st = self._by_rid.get(data.get("rid"))
            if st is None:
                self._early.setdefault(data.get("rid"), []).append((now, data))
                return
            self._apply(st, now, data)

    def _apply(self, st: _Stream, now: float, data: dict):
        if "o" in data:                         # serve_tokens
            new = list(data["t"])[max(0, len(st.tokens) - data["o"]):]
            st.tokens += new
            st.times += [now] * len(new)
        elif "status" in data:                  # serve_done
            toks = list(data.get("tokens") or [])
            new = toks[len(st.tokens):]
            st.tokens += new
            st.times += [now] * len(new)
            st.status, st.end = data["status"], now
            self._freed.release()

    def _submit(self, req: dict, due: float):
        st = _Stream(req, due)
        self.streams.append(st)
        st.sent = time.monotonic()
        try:
            v = self.client.serve_submit(req["prompt"], req["max_new"])
        except Exception as e:      # boundary: a refusal is a verdict
            st.status, st.end = f"refused: {type(e).__name__}", time.monotonic()
            self._freed.release()
            return
        with self._lock:
            st.rid, st.status = v["rid"], "accepted"
            self._by_rid[st.rid] = st
            for now, data in self._early.pop(st.rid, []):
                self._apply(st, now, data)

    def run(self, drain_s: float):
        """Offer for ``seconds``; then wait, at most ``drain_s``, until
        every request due in the window has its first token."""
        self.client.on_serve = self._on_serve
        self.t0 = t0 = time.monotonic()
        end = t0 + self.seconds
        if self.traffic["loop"] == "open":
            for req in self.reqs:
                due = t0 + req["at"]
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                self._submit(req, due)
        else:
            queue = iter(self.reqs)     # running dry raises: lengthen queue_len
            for _ in range(int(self.traffic["clients"])):
                self._submit(next(queue), time.monotonic())
            while True:
                if not self._freed.acquire(timeout=max(
                        0.0, end - time.monotonic())):
                    break
                if time.monotonic() >= end:
                    break
                self._submit(next(queue), time.monotonic())
        rest = end - time.monotonic()
        if rest > 0:
            time.sleep(rest)
        self.t_end = end
        deadline = time.monotonic() + drain_s
        while time.monotonic() < deadline and any(
                st.status == "accepted" and not st.times
                for st in self.streams):
            time.sleep(0.05)
        self.client.on_serve = None

    # -- what the client saw -------------------------------------------

    def summary(self, quantile) -> dict:
        t0, end = self.t0, self.t_end
        with self._lock:
            streams = list(self.streams)
        gaps, ttft, late, received, bursts = [], [], [], 0, []
        failed = 0
        for st in streams:
            late.append(st.sent - st.due)
            in_win = [t for t in st.times if t <= end]
            received += len(in_win)
            gaps += [b - a for a, b in zip(in_win, in_win[1:])]
            bursts += sorted(set(in_win))
            bad = st.status not in ("accepted", "completed")
            failed += bad
            ttft.append(float("inf") if bad or not st.times
                        else st.times[0] - st.due)
        finite = [t for t in ttft if t != float("inf")]
        worst = max(finite, default=0.0)
        ttft = [worst if t == float("inf") else t for t in ttft]
        # pushes of one tick reach the client together: the period of
        # the serving loop is the gap between distinct arrival instants
        ticks = sorted(set(round(b, 3) for b in bursts))
        periods = [b - a for a, b in zip(ticks, ticks[1:]) if b - a > 0.02]
        out = {"offered": len(streams), "failed": failed,
               "completed": sum(st.status == "completed" for st in streams),
               "tokens_in_window": received,
               "serve_tokens_per_s": received / (end - t0),
               "late_p99_ms": 1e3 * quantile(late, 0.99) if late else None,
               "n_gaps": len(gaps), "n_ttft": len(ttft)}
        if gaps:
            out["itl_p99_ms"] = 1e3 * quantile(gaps, 0.99)
            out["itl_p50_ms"] = 1e3 * quantile(gaps, 0.50)
        if ttft:
            out["ttft_p50_ms"] = 1e3 * quantile(ttft, 0.50)
            out["ttft_p95_ms"] = 1e3 * quantile(ttft, 0.95)
        if periods:
            out["tick_period_p50_ms"] = 1e3 * quantile(periods, 0.50)
        return out

    def finished(self) -> list[_Stream]:
        with self._lock:
            return [st for st in self.streams if st.status == "completed"]
