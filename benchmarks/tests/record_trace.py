#!/usr/bin/env python3
"""Cut a small slice out of a profiler trace taken on the chip and keep
it, with what the reducer read from it, as ``recorded_trace.json``:

    python benchmarks/tests/record_trace.py <trace dir> <milliseconds>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.readers import trace_share          # noqa: E402
from benchmarks.trace import reduce as T            # noqa: E402

if __name__ == "__main__":
    events = T.events_of(T.find_xplane(sys.argv[1]))
    dev = [e for e in events if e[0].startswith(T.DEVICE_PLANE)
           and e[1] in (T.OPS_LINE, T.MODULES_LINE)]
    t0 = min(e[3] for e in dev if e[1] == T.OPS_LINE)
    cut = t0 + int(float(sys.argv[2]) * 1e6)
    def brief(n):       # the HLO text, cut; a kernel keeps its mark
        mark = ' custom_call_target="tpu_custom_call"'
        return n[:160] + (mark if mark.strip() in n[160:] else "")

    keep = [[p, l, brief(n), s - t0, d] for p, l, n, s, d in dev
            if s + d <= cut]
    r = T.mean_over_chips([T.reduce(keep)])
    out = {"events": keep, "expected": {
        "busy_s": r["busy_s"], "window_s": r["window_s"], "ops": r["ops"],
        "mosaic_time_share": trace_share.read(
            {"trace": r}, {"match": ["tpu_custom_call"]})}}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_trace.json")
    with open(path, "w") as f:
        json.dump(out, f)
    print(len(keep), "events ->", path, os.path.getsize(path), "bytes")
