"""Driver ``serve``: ``%dist_pool start`` -> gateway -> ``%dist_attach``
-> ``%dist_serve start`` -> the gateway client, under the traffic file's
open or closed loop (``loadgen.py``).

Traffic keys: those of ``loadgen.py``; ``warm`` ([prompt_len, max_new]
pairs run during set-up so that nothing compiles in the window);
``drain_s``; ``trace_seconds`` (profiled from the window's fifth second);
``check_requests`` (how many finished requests the
reference reads, the longest always among them); ``check_pad``;
``limits``.  Serving geometry comes from the
configuration file's ``assumed``.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time

from benchmarks import harness as H
from benchmarks import loadgen
from benchmarks.trace import reduce as T

_SPEC = """
{prelude}
from benchmarks.drivers import serve_worker as _sw
cfg = _sw.program_config({cfg!r})
params = _sw.make_params({seed}, {cfg!r})
shared["bench_serving_ns"] = globals()
_sw.break_server({broken!r})
"""

_FACTS = """
from benchmarks.drivers import serve_worker as _sw
_sw.emit("BENCH", rank, **_sw.device_facts())
"""

_TRACE_ON = """
import jax
jax.profiler.start_trace({dir!r} + "/rank" + str(rank))
"""

_TRACE_OFF = """
jax.profiler.stop_trace()
"""

_MEMORY = """
_sw.emit("BENCH", rank, **_sw.memory())
"""

_CHECK = """
shared["bench_serving_ns"].pop("params", None)
import gc; gc.collect()
_freed = _sw.memory()["in_use"]
_sw.emit("BENCH", rank, in_use_after_free=_freed,
         **_sw.check({seed}, {cfg!r}, {pairs!r}, {pad_to}, {control}))
"""


def run(b: H.Bench) -> dict:
    a, t = b.args, b.traffic
    geo = dict(b.cfg["assumed"])
    if a.rehearse:
        geo.update(b.cfg["rehearse"].get("assumed", {}))
    cfg = H.numbers_of(b.cfg)
    vocab = cfg["vocab_size"]
    with b.span("fleet_attach_s"):
        b.new_pool_dir()
        out = b.magic("dist_pool", f"start -n {b.chips} --backend "
                                   f"{b.backend} --run-dir {b.pool_dir}")
        if "pool up" not in out:
            raise H.NoChip("the pool did not start: " + out.strip()[-800:])
        b.magic("dist_attach", f"--tenant bench {b.pool_dir}")
        client = b.DM._tenant
        if client is None:
            raise H.RunFailed("tenant attach failed")
        client.on_serve = None      # the magics' printer of finished streams
        facts = b.run_cell(H.worker_prelude() + _FACTS)
        _check_devices(b, facts)
    with b.span("warm_compile_s"):
        b.ip.user_ns["bench_spec"] = _SPEC.format(
            prelude=H.worker_prelude(), cfg=cfg, seed=a.seed,
            broken=a.broken)
        flags = (f"--max-batch {geo['max_batch']} --max-len {geo['max_len']} "
                 f"--pad-to {geo['pad_to']} --kv-block-tokens "
                 f"{geo['kv_block_tokens']} --prefill-chunk "
                 f"{geo['prefill_chunk']}")
        out = b.magic("dist_serve", f"start --spec bench_spec {flags}")
        if "serving as tenant" not in out:
            raise H.RunFailed("serve start failed: " + out.strip()[-1500:])
        _warm(client, t, a.seed, vocab)
    reqs = loadgen.plan(t, a.seed, a.seconds, vocab)
    load = loadgen.Load(client, reqs, t, a.seconds)
    util: list[dict] = []
    stop = threading.Event()
    poller = threading.Thread(target=_poll_status,
                              args=(client, util, stop), daemon=True)
    b.record["phases"].append(["window", round(time.time() - H.T_START, 3)])
    setup_s = time.time() - H.T_START
    poller.start()
    tracer = None
    if a.trace:
        tracer = threading.Thread(target=_trace_a_while, args=(
            client, b.trace_dir, float(t["trace_seconds"])), daemon=True)
        tracer.start()
    load.run(float(t["drain_s"]))
    stop.set()
    poller.join(timeout=10)
    if tracer:
        tracer.join(timeout=120)
    status = client.serve_status()
    mem = b.run_cell(_MEMORY)
    summary = load.summary(H.quantile)
    # everything the client saw, TTFT included (it is no metric: PERF.md)
    print("LOADGEN " + json.dumps(summary), file=H.sys.stderr)
    pairs = _sample(load.finished(), a.seed, int(t["check_requests"]))
    b.magic("dist_serve", "stop")
    with b.span("check_s"):
        chk = b.run_cell(_CHECK.format(
            seed=a.seed, cfg=cfg, pairs=pairs, pad_to=int(t["check_pad"]),
            control=bool(a.control)))[0] if pairs else None
    b.magic("dist_pool", f"stop --run-dir {b.pool_dir}")
    b.pool_dir = None

    fills = [u["fill_mean"] for u in util if u.get("count")]
    obs = {
        "e2e": {"setup_s": setup_s,
                **{k: summary[k] for k in ("serve_tokens_per_s",
                                           "itl_p99_ms")
                   if k in summary}},
        "spans": dict(b.spans), "loadgen": summary,
        "serve_status": status,
        "util": {"fill_mean": sum(fills) / len(fills)} if fills else {},
        "cfg": b.cfg, "traffic": t, "chips": b.chips,
    }
    if a.trace:
        obs["trace"] = T.mean_over_chips(
            [T.reduce_dir(os.path.join(b.trace_dir, f"rank{r}"))
             for r in range(b.chips)])
    lim = t["limits"]
    checks = [
        {"name": "requests_failed", "value": float(summary["failed"]),
         "limit": 0.0},
        {"name": "requests_unchecked",
         "value": float(int(t["check_requests"]) - len(pairs)),
         "limit": float(lim["requests_unchecked"])},
    ]
    if chk:
        checks.append({"name": "served_logit_gap_max",
                       "value": chk["gap_max"],
                       "limit": lim["served_logit_gap_max"]})
        if a.control:
            print("CONTROL " + json.dumps(chk), file=H.sys.stderr)
    bad = [st.i for st in load.finished() if len(st.tokens) != st.max_new]
    checks.append({"name": "streams_wrong_length", "value": float(len(bad)),
                   "limit": 0.0})
    b.record.update(loadgen=summary, check=chk, util=util,
                    serve_lat=(status.get("lat") or {}).get("summary"),
                    streams=[[st.i, len(st.prompt), st.max_new,
                              round(st.due - load.t0, 4),
                              [round(x - load.t0, 4) for x in
                               sorted(set(st.times))]]
                             for st in load.streams])
    return {"obs": obs, "checks": checks, "attempted": summary["offered"],
            "failed": summary["failed"],
            "memory_peak_bytes": max(m["peak"] for m in mem)}


def _check_devices(b: H.Bench, facts: list[dict]):
    for f in facts:
        if f["platform"] != b.backend or f["local"] != 1 \
                or f["count"] != b.chips:
            raise H.NoChip(f"rank {f['rank']} is not one {b.backend} "
                           f"device of {b.chips}: {f}")
    if len({f["id"] for f in facts}) != b.chips:
        raise H.NoChip(f"ranks share devices: {facts}")
    b.device = {"platform": facts[0]["platform"], "kind": facts[0]["kind"],
                "count": b.chips}


def _warm(client, t: dict, seed: int, vocab: int):
    """Run the shapes the traffic uses once, to the end."""
    rng = random.Random(seed ^ 0x5EED)
    rids = [client.serve_submit([rng.randrange(vocab) for _ in range(p)],
                                n)["rid"] for p, n in t["warm"]]
    deadline = time.time() + 900
    while rids:
        if time.time() > deadline:
            raise H.RunFailed(f"warm-up requests unfinished: {rids}")
        r = client.serve_result(rids[0])
        if r.get("error"):
            raise H.RunFailed(f"warm-up {rids[0]}: {r['error']}")
        if r.get("done"):
            rids.pop(0)
        else:
            time.sleep(0.1)


def _trace_a_while(client, trace_dir: str, seconds: float):
    """A few seconds of the steady window under the profiler: cells on
    the pool switch it on and off between two ticks."""
    time.sleep(5.0)
    client.execute(_TRACE_ON.format(dir=trace_dir), timeout=60)
    time.sleep(seconds)
    client.execute(_TRACE_OFF, timeout=120)


def _poll_status(client, util: list, stop: threading.Event):
    """Every few seconds, the observatory's utilisation block (it keeps
    the last 32 ticks only)."""
    while not stop.wait(4.0):
        try:
            st = client.serve_status()
        except Exception:       # boundary: a reading lost, not a run
            continue
        util.append((st.get("lat") or {}).get("util") or {})


def _sample(finished, seed: int, n: int) -> list:
    """n finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    rng = random.Random(seed + 17)
    longest = max(finished, key=lambda st: len(st.prompt) + len(st.tokens))
    rest = [st for st in finished if st is not longest]
    rng.shuffle(rest)
    return [(st.prompt, st.tokens) for st in [longest] + rest[:n - 1]]
