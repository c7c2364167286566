"""Driver ``train``: ``%dist_init`` with one worker per chip, then the
user's training loop in ``%%distributed`` cells (``train_worker.py``).

Traffic keys: ``seq_len``, ``rows_per_rank``, ``fetch_every`` (steps
between fetches of the loss), ``trace_seconds``, ``limits``.
"""

from __future__ import annotations

import json
import os

from benchmarks import harness as H
from benchmarks.model import reference as R
from benchmarks.trace import reduce as T

_SETUP = """
from benchmarks.drivers import train_worker as _tw
_bench = _tw.Trainer({seed}, {cfg!r}, {traffic!r}, rank, world_size,
                     {broken!r})
_tw.emit("BENCH", rank, **_bench.warm_up())
"""

_WINDOW = """
_tw.emit("BENCH", rank, **_bench.window({seconds}, {fetch_every}, {trace!r}))
"""

# the reference reads every rank's rows itself: one rank runs it
_CHECK = """
_peak = _bench.memory_peak()
_bench.free()
_tw.emit("BENCH", rank, memory_peak_bytes=_peak,
         **(_bench.reference({control}) if rank == 0 else {{}}))
"""


def run(b: H.Bench) -> dict:
    a, t = b.args, b.traffic
    with b.span("fleet_attach_s"):
        b.magic("dist_init", f"-n {b.chips} --backend {b.backend} "
                             f"--attach-timeout 240")
        if b.DM._comm is None:
            raise H.NoChip("no fleet came up")
        b.check_devices(b.fleet_status())
    with b.span("warm_compile_s"):
        warm = b.run_cell(H.worker_prelude() + _SETUP.format(
            seed=a.seed, cfg=H.numbers_of(b.cfg), traffic=H.numbers_of(t),
            broken=a.broken))
    trace = None
    if a.trace:
        trace = (b.trace_dir, float(t["trace_seconds"]))
    b.record["phases"].append(["window", round(H.time.time() - H.T_START, 3)])
    setup_s = H.time.time() - H.T_START
    code = _WINDOW.format(seconds=a.seconds, fetch_every=t["fetch_every"],
                          trace=trace)
    win = b.run_cell(code)
    lat = (b.DM._comm.lat.records(1) or [{}])[-1]
    with b.span("check_s"):
        chk = b.run_cell(_CHECK.format(control=bool(a.control)))
    b.magic("dist_shutdown")

    # the slowest rank's window is the fleet's: every rank ran the same
    # steps, and a step is done when it is ready on every rank
    w = max(win, key=lambda r: r["seconds"])
    obs = {
        "e2e": {"train_tokens_per_s": w["tokens"] / w["seconds"],
                "setup_s": setup_s},
        "spans": dict(b.spans), "window": w, "warm": warm[0],
        "cell_lat": lat.get("stages") or {},
        "cfg": b.cfg, "traffic": t, "chips": b.chips,
    }
    if trace:
        runs = [T.reduce_dir(os.path.join(trace[0], f"rank{r['rank']}"))
                for r in win]
        obs["trace"] = T.mean_over_chips(runs)
    got = {k: warm[0][k] for k in ("losses", "grad_norms", "change_norms")}
    checks = _judge(R.compare_train(got, chk[0]["ref"]), t["limits"])
    # every rank must hold the same model after the all-reduce
    spread = max(abs(x - warm[0]["losses"][i])
                 for r in warm for i, x in enumerate(r["losses"]))
    checks.append({"name": "rank_loss_spread", "value": spread, "limit": 0.0})
    if a.control:
        b.record["control"] = R.compare_train(chk[0]["control"],
                                              chk[0]["ref"])
        print("CONTROL " + json.dumps(b.record["control"]), file=H.sys.stderr)
    b.record.update(window=w, warm=warm, reference_s=chk[0]["reference_s"],
                    losses_ref=chk[0]["ref"]["losses"])
    return {"obs": obs, "checks": checks, "attempted": w["steps"],
            "failed": 0,
            "memory_peak_bytes": max(c["memory_peak_bytes"] for c in chk)}


def _judge(numbers: dict, limits: dict) -> list[dict]:
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in numbers.items()]

