"""Driver ``serve_sdar``: ``serve.py``'s path (``%dist_pool start`` ->
gateway -> ``%dist_attach`` -> ``%dist_serve start`` -> the gateway
client under the traffic file's loop) with SDAR's worker module
(``serve_sdar_worker.py``: its weights, program config and reference)
in the place of Mistral's, and the checks a block server needs.

Traffic keys: ``serve.py``'s; ``token_ids_below`` (prompts draw their
ids below it: the control tokens, the mask among them, are never
sent); ``trace_start_s`` (the window's second at which the profiled
slice opens); and under ``limits`` ``served_logit_gap_mean`` and
``served_pick_gap_mean`` (over all positions read),
``served_logit_gap_max`` (over the positions whose routing margins all
exceed ``margin_eps``), ``close_share`` (the share of positions left
out of it) and ``passes_off_schedule``; the traffic file gives the
readings they were set from.  ``--control 1`` also reads the float8
control.

``correct`` is decided on what the timed path produced: the sampled
finished requests' tokens as the client received them, and for each
the pass of its block at which every token was fixed, which the
product returns with a finished request's result
(``client.serve_result(rid)["passes"]``; a request without that record
counts as unchecked).

For the readers this cell brings, the driver also hands over what only
the client saw (``served``: tokens received and prompt tokens sent in
the window, the mean position of a received token) and what the
program counted (``ticks.totals``, sums since the start) between the
two instants the profiler was switched (``slice_totals``) and between
the window's two ends (``window_totals``).
"""

from __future__ import annotations

import json
import os
import random
import threading
import time

from benchmarks import harness as H
from benchmarks import loadgen
from benchmarks.drivers.serve import (_MEMORY, _check_devices, _poll_status,
                                      _warm)
from benchmarks.drivers.serve_nemotronh import (_between, _totals,
                                                _trace_a_slice)
from benchmarks.trace import by_module
from benchmarks.trace import reduce as T

_SPEC = """
{prelude}
from benchmarks.drivers import serve_sdar_worker as _sw
cfg = _sw.program_config({cfg!r})
params = _sw.make_params({seed}, {cfg!r})
shared["bench_serving_ns"] = globals()
_sw.break_server({broken!r})
"""

_FACTS = """
from benchmarks.drivers import serve_sdar_worker as _sw
_sw.emit("BENCH", rank, **_sw.device_facts())
"""

_CHECK = """
shared["bench_serving_ns"].pop("params", None)
import gc; gc.collect()
_freed = _sw.memory()["in_use"]
_sw.emit("BENCH", rank, in_use_after_free=_freed,
         **_sw.check({seed}, {cfg!r}, {requests!r}, {pad_to}, {control},
                     {margin_eps}))
"""


def _sampled(client, finished, seed: int, n: int) -> list:
    """n finished requests drawn from the seed, the longest among them,
    each with the record the product keeps of it: (prompt, tokens, the
    pass that fixed each token).  One without a whole record is left
    out (and then counts as unchecked)."""
    if not finished:
        return []
    rng = random.Random(seed + 17)
    longest = max(finished, key=lambda st: len(st.prompt) + len(st.tokens))
    rest = [st for st in finished if st is not longest]
    rng.shuffle(rest)
    out = []
    for st in [longest] + rest[:n - 1]:
        # the record comes with the reply of the tick that finished the
        # request: a stream that ended on a frame may be a tick ahead
        deadline = time.time() + 5.0
        while True:
            res = client.serve_result(st.rid)
            if res.get("passes") is not None or time.time() > deadline:
                break
            time.sleep(0.2)
        passes = res.get("passes")
        if passes is None or res.get("passes_from") \
                or list(res.get("tokens") or ()) != list(st.tokens):
            continue
        out.append((list(st.prompt), list(st.tokens), list(passes)))
    return out


def run(b: H.Bench) -> dict:
    a, t = b.args, b.traffic
    from nbdistributed_tpu.models import hf
    if not hasattr(hf, "sdar_config_from_hf"):
        # before any process is started: a program without the model
        # fails here, at once
        raise H.RunFailed("this program cannot run model_type "
                          f"{b.cfg['model_type']!r}")
    geo = dict(b.cfg["assumed"])
    if a.rehearse:
        geo.update(b.cfg["rehearse"].get("assumed", {}))
    cfg = H.numbers_of(b.cfg)
    vocab = int(t["token_ids_below"])
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
    requests = _sampled(client, load.finished(), a.seed,
                        int(t["check_requests"]))
    b.magic("dist_serve", "stop")
    lim = t["limits"]
    with b.span("check_s"):
        chk = b.run_cell(_CHECK.format(
            seed=a.seed, cfg=cfg, requests=requests,
            pad_to=int(t["check_pad"]), control=int(a.control),
            margin_eps=float(lim["margin_eps"])))[0] if requests else None
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
        # one chip, one process: the denoise program's operations apart
        # from the prefill chunk's, which carry the same names
        obs["trace_by_module"] = by_module.ops_by_module(events[0])
        obs["slice_totals"] = sliced.get("slice_totals")
    checks = [
        {"name": "requests_failed", "value": float(summary["failed"]),
         "limit": 0.0},
        {"name": "requests_unchecked",
         "value": float(int(t["check_requests"]) - len(requests)),
         "limit": float(lim["requests_unchecked"])},
    ]
    if chk:
        checks += [
            {"name": "passes_off_schedule",
             "value": float(chk["off_schedule"]),
             "limit": float(lim["passes_off_schedule"])},
            {"name": "served_logit_gap_max", "value": chk["gap_max"],
             "limit": lim["served_logit_gap_max"]},
            {"name": "close_share", "value": chk["close_share"],
             "limit": lim["close_share"]},
            {"name": "served_logit_gap_mean", "value": chk["gap_mean"],
             "limit": lim["served_logit_gap_mean"]},
            {"name": "served_pick_gap_mean", "value": chk["pick_mean"],
             "limit": lim["served_pick_gap_mean"]}]
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
