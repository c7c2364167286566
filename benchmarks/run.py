#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see BENCHMARK.json's
contract in PERF.md); the numbers `correct` was decided by are the last
lines of standard error and the last key of that line.  Without a chip
(or with fewer than the cell asks for) the exit code is 2 and nothing is
printed as a result.  ``--rehearse`` runs the same path end to end on
the CPU at the tiny sizes each file gives under ``rehearse`` and marks
its last line as no measurement.
"""

from __future__ import annotations

import argparse
import importlib
import os
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEADLINE_S = 1150       # a cold run may take 1200 s, teardown included


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; no measurement")
    ap.add_argument("--control", type=int, default=0,
                    help="also read the lower-precision control (chip "
                         "readings for the limits; not a benchmark run)")
    ap.add_argument("--rate", type=float, default=None,
                    help="the knee sweep only: offer this rate instead of "
                         "the traffic file's")
    ap.add_argument("--broken", default=None,
                    help="tests only: break the timed path underneath")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        import nbdistributed_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the nbdistributed_tpu package is not beside "
              f"benchmarks/ ({e})", file=sys.stderr)
        return 2
    from benchmarks import harness as H

    def _bail(signum, _frame):
        raise SystemExit(f"benchmark: stopped by signal {signum}")

    signal.signal(signal.SIGTERM, _bail)
    signal.signal(signal.SIGALRM, _bail)
    signal.alarm(DEADLINE_S)

    bench = H.load_benchmark()
    cell = H.find_cell(bench, args.workload)
    traffic = H.traffic_of(cell, args.rehearse)
    if args.rate is not None:
        traffic["rate_per_s"] = args.rate
    b = H.Bench(args, cell, H.config_of(bench, cell, args.rehearse), traffic)
    driver = importlib.import_module("benchmarks.drivers."
                                     + traffic["driver"])
    try:
        b.shell()
        res = driver.run(b)
    except H.NoChip as e:
        print(f"benchmark: no chip to measure on — {e}", file=sys.stderr)
        return 2
    finally:
        b.cleanup()

    obs = res["obs"]
    obs["device"] = b.device
    if not args.rehearse:
        obs["peak"] = H.peak_for(b.device["kind"])
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in H.metrics_for(bench, cell["name"], kind):
        value = H.read_metric(m["name"], obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(b.device, memory_peak_bytes=res["memory_peak_bytes"])
    breakdown = None
    if args.trace and obs.get("trace"):
        from benchmarks.trace import reduce as T
        device.update(busy_s=obs["trace"]["busy_s"],
                      window_s=obs["trace"]["window_s"])
        breakdown = T.breakdown(obs["trace"])
    correct = all(c["value"] <= c["limit"] for c in res["checks"])
    b.record.update(metrics=metrics, checks=res["checks"], correct=correct,
                    spans=b.spans, device=device,
                    trace=obs.get("trace"), total_s=time.time() - H.T_START)
    b.write_record()
    line = H.result_line(correct, res["attempted"], res["failed"], metrics,
                         device, res["checks"], breakdown, args.rehearse)
    sys.stdout.flush()
    for c in res["checks"]:
        print(f"compared {c['name']} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
