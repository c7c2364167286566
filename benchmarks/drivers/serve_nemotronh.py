"""Driver ``serve_nemotronh``: ``serve.py``'s path (``%dist_pool start``
-> gateway -> ``%dist_attach`` -> ``%dist_serve start`` -> the gateway
client under the traffic file's loop) with Nemotron-3-Nano's worker
module (``serve_nemotronh_worker.py``: its weights, program config and
reference) in the place of Mistral's, and the checks its routing needs
(``serve_joyai.py``'s).

Traffic keys: ``serve.py``'s, ``trace_start_s`` (the window's second at
which the profiled slice opens: early enough to hold the admissions'
chunk programs), and under ``limits`` ``served_logit_gap_max`` (over
the positions whose routing margins all exceed ``margin_eps``),
``close_share`` (the share of positions left out of it) and
``served_logit_gap_mean`` (over all positions); the traffic file gives
the readings they were set from.  ``--control 1`` also reads the float8
control, ``--control 2`` the control run once (the recurrent state in
bfloat16).

For the readers this cell brings, the driver also hands over what only
the client saw: ``served`` (tokens received and prompt tokens sent in
the window, the mean position of a received token), and what the
program counted (``ticks.totals``, sums since the start) between the
two instants the profiler was switched (``slice_totals``) and between
the window's two ends (``window_totals``); a program that keeps no such
totals yields neither.  Where fewer than ``check_requests`` requests
finished in the window, the reference reads the longest streams still
running.
"""

from __future__ import annotations

import json
import os
import threading
import time

from benchmarks import harness as H
from benchmarks import loadgen
from benchmarks.drivers.serve import (_MEMORY, _TRACE_OFF, _TRACE_ON,
                                      _check_devices, _poll_status,
                                      _sample, _warm)
from benchmarks.trace import by_module
from benchmarks.trace import reduce as T

_SPEC = """
{prelude}
from benchmarks.drivers import serve_nemotronh_worker as _sw
cfg = _sw.program_config({cfg!r})
params = _sw.make_params({seed}, {cfg!r})
shared["bench_serving_ns"] = globals()
_sw.break_server({broken!r})
"""

_FACTS = """
from benchmarks.drivers import serve_nemotronh_worker as _sw
_sw.emit("BENCH", rank, **_sw.device_facts())
"""

_CHECK = """
shared["bench_serving_ns"].pop("params", None)
import gc; gc.collect()
_freed = _sw.memory()["in_use"]
_sw.emit("BENCH", rank, in_use_after_free=_freed,
         **_sw.check({seed}, {cfg!r}, {pairs!r}, {pad_to}, {control},
                     {margin_eps}))
"""


def _totals(client) -> dict | None:
    """The program's sums since the start, or None from a program that
    keeps none."""
    ticks = ((client.serve_status().get("lat") or {}).get("summary")
             or {}).get("ticks") or {}
    return ticks.get("totals")


def _between(first: dict | None, second: dict | None) -> dict | None:
    if not first or not second:
        return None
    return {k: second[k] - first[k] for k in first}


def _trace_a_slice(client, trace_dir: str, start: float, seconds: float,
                   got: dict):
    """``serve._trace_a_while`` from the window's ``start``-th second,
    keeping the program's totals at the two instants the profiler was
    switched (``got["slice_totals"]``: their difference)."""
    time.sleep(start)
    client.execute(_TRACE_ON.format(dir=trace_dir), timeout=60)
    first = _totals(client)
    time.sleep(seconds)
    second = _totals(client)
    client.execute(_TRACE_OFF, timeout=120)
    got["slice_totals"] = _between(first, second)


def run(b: H.Bench) -> dict:
    a, t = b.args, b.traffic
    from nbdistributed_tpu.models import hf
    if not hasattr(hf, "nemotron_h_config_from_hf"):
        # before any process is started: a program without the model
        # fails here, at once
        raise H.RunFailed("this program cannot run model_type "
                          f"{b.cfg['model_type']!r}")
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
                 f"{geo['prefill_chunk']} --inflight {int(t['clients'])}")
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
    tracer, sliced = None, {}
    if a.trace:
        tracer = threading.Thread(target=_trace_a_slice, args=(
            client, b.trace_dir, float(t["trace_start_s"]),
            float(t["trace_seconds"]), sliced),
            daemon=True)
        tracer.start()
    before = _totals(client)
    load.run(float(t["drain_s"]))
    window_totals = _between(before, _totals(client))
    stop.set()
    poller.join(timeout=10)
    if tracer:
        tracer.join(timeout=120)
    status = client.serve_status()
    mem = b.run_cell(_MEMORY)
    summary = load.summary(H.quantile)
    print("LOADGEN " + json.dumps(summary), file=H.sys.stderr)
    pairs = _sample(load.finished(), a.seed, int(t["check_requests"]))
    if len(pairs) < int(t["check_requests"]):
        # Outputs of 512 tokens and more: a window that lost time (the
        # profiler's stop holds the worker for seconds) finishes few.
        # The longest streams still running are then teacher-forced as
        # far as they got: every position of theirs was served too.
        done = {id(st) for st in load.finished()}
        running = sorted((st for st in load.streams
                          if st.tokens and id(st) not in done),
                         key=lambda st: -len(st.tokens))
        pairs += [(st.prompt, list(st.tokens)) for st in
                  running[:int(t["check_requests"]) - len(pairs)]]
    b.magic("dist_serve", "stop")
    lim = t["limits"]
    with b.span("check_s"):
        chk = b.run_cell(_CHECK.format(
            seed=a.seed, cfg=cfg, pairs=pairs, pad_to=int(t["check_pad"]),
            control=int(a.control),
            margin_eps=float(lim["margin_eps"])))[0] if pairs else None
    b.magic("dist_pool", f"stop --run-dir {b.pool_dir}")
    b.pool_dir = None

    # the position in its row of every token a client received
    end = load.t0 + a.seconds
    inside = [len(st.prompt) + i for st in load.streams
              for i, x in enumerate(st.times) if x <= end]
    fills = [u["fill_mean"] for u in util if u.get("count")]
    obs = {
        "e2e": {"setup_s": setup_s,
                "serve_tokens_per_s": summary["serve_tokens_per_s"]},
        "spans": dict(b.spans), "loadgen": summary,
        "serve_status": status,
        "util": {"fill_mean": sum(fills) / len(fills)} if fills else {},
        "cfg": b.cfg, "geo": geo, "traffic": t, "chips": b.chips,
        "served": {
            "seconds": a.seconds, "decode_tokens": len(inside),
            "prompts": sum(1 for st in load.streams if st.times),
            "prompt_tokens": sum(len(st.prompt) for st in load.streams
                                 if st.times),
            "mean_position": sum(inside) / max(1, len(inside))},
        "window_totals": window_totals,
    }
    if a.trace:
        events = [T.events_of(T.find_xplane(
            os.path.join(b.trace_dir, f"rank{r}"))) for r in range(b.chips)]
        obs["trace"] = T.mean_over_chips([T.reduce(e) for e in events])
        # one chip, one process: the decode step's operations apart
        # from the prefill chunk's, which carry the same names
        obs["trace_by_module"] = by_module.ops_by_module(events[0])
        obs["slice_totals"] = sliced.get("slice_totals")
    checks = [
        {"name": "requests_failed", "value": float(summary["failed"]),
         "limit": 0.0},
        {"name": "requests_unchecked",
         "value": float(int(t["check_requests"]) - len(pairs)),
         "limit": float(lim["requests_unchecked"])},
    ]
    if chk:
        checks += [
            {"name": "served_logit_gap_max", "value": chk["gap_max"],
             "limit": lim["served_logit_gap_max"]},
            {"name": "close_share", "value": chk["close_share"],
             "limit": lim["close_share"]},
            {"name": "served_logit_gap_mean", "value": chk["gap_mean"],
             "limit": lim["served_logit_gap_mean"]}]
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
