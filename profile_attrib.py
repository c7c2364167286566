"""Profile-attribute the flagship forward: where does the non-MFU
time go?

Runs the tinyllama-1.1B forward (bench shape B8 S2048 bf16 flash)
under ``jax.profiler.trace``, parses the Chrome-trace device lanes,
and buckets device time into: flash-attention custom calls, GEMM
fusions (dot/convolution), other fusions (elementwise/layernorm/
rotary), and infeed/outfeed/host.  Writes ``PROFILE_1B.json`` at the
repo root and prints it.  Runs in-process (it holds the chip: never in
the same parent as a worker fleet); any failure degrades to an error
record.

``NBD_PROFILE_CPU_SMOKE=1`` shrinks to the tiny config on CPU to
validate the harness end-to-end without a chip.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SMOKE = bool(os.environ.get("NBD_PROFILE_CPU_SMOKE"))


def _bucket(name: str) -> str:
    n = name.lower()
    if "flash" in n or "custom-call" in n or "custom_call" in n:
        return "flash_attention"
    if "dot" in n or "conv" in n or "gemm" in n or "matmul" in n:
        return "gemm"
    if any(t in n for t in ("infeed", "outfeed", "copy", "transfer",
                            "reshape", "transpose")):
        return "data_movement"
    if "fusion" in n or "loop" in n:
        return "other_fusion"
    return "other"


def _parse_trace(trace_dir: str) -> dict:
    """Aggregate device-lane complete events by bucket from the
    newest trace.json.gz under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        return {"error": "no trace.json.gz produced"}
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # Device lanes: pid whose process_name metadata mentions the
    # accelerator (TPU/device); fall back to all X events.
    dev_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname = str(e.get("args", {}).get("name", "")).lower()
            if any(t in pname for t in ("tpu", "device", "/device",
                                        "xla")):
                dev_pids.add(e.get("pid"))
    buckets: dict[str, float] = {}
    names: dict[str, float] = {}
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if dev_pids and e.get("pid") not in dev_pids:
            continue
        nm_raw = str(e.get("name", ""))
        # Host python-trace frames (only reached in the no-device-lane
        # fallback, e.g. CPU smoke) would swamp the op accounting.
        if nm_raw.startswith("$") or ".py:" in nm_raw \
                or "ThunkExecutor" in nm_raw:
            continue
        dur = float(e["dur"])          # microseconds
        total += dur
        b = _bucket(e.get("name", ""))
        buckets[b] = buckets.get(b, 0.0) + dur
        nm = e.get("name", "?")[:80]
        names[nm] = names.get(nm, 0.0) + dur
    if total == 0.0:
        return {"error": "no timed device events in trace",
                "trace_file": paths[-1]}
    top = sorted(names.items(), key=lambda kv: -kv[1])[:15]
    return {
        "total_device_ms": round(total / 1e3, 2),
        "buckets_ms": {k: round(v / 1e3, 2)
                       for k, v in sorted(buckets.items(),
                                          key=lambda kv: -kv[1])},
        "buckets_pct": {k: round(100 * v / total, 1)
                        for k, v in sorted(buckets.items(),
                                           key=lambda kv: -kv[1])},
        "top_ops": [{"name": n, "ms": round(v / 1e3, 2)}
                    for n, v in top],
        "trace_file": paths[-1],
    }


def main() -> int:
    import jax
    import jax.numpy as jnp

    from nbdistributed_tpu.models import (forward, init_params,
                                          tiny_config,
                                          tinyllama_1b_config)

    if jax.default_backend() != "tpu" and not SMOKE:
        print("profile_attrib.py needs a live TPU "
              f"(backend={jax.default_backend()})", file=sys.stderr)
        return 1

    if SMOKE:
        cfg = tiny_config(dtype=jnp.float32, use_flash=True)
        B, S, steps = 2, 64, 2
    else:
        cfg = tinyllama_1b_config(dtype=jnp.bfloat16, use_flash=True)
        B, S, steps = 8, 2048, 3

    out: dict = {"config": type(cfg).__name__,
                 "shape": f"B{B} S{S}",
                 "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                              time.gmtime())}
    try:
        params = init_params(jax.random.PRNGKey(0), cfg)
        tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab_size)
        f = jax.jit(lambda p, t: forward(p, t, cfg))
        float(f(params, tok)[0, 0, 0])                 # compile outside
        trace_dir = "/tmp/nbd_profile"
        os.makedirs(trace_dir, exist_ok=True)
        with jax.profiler.trace(trace_dir):
            o = None
            for i in range(steps):
                # Fresh token values per step and a value fetch at the
                # end (the ops/timing.py contract): every traced step
                # does its own work and the trace closes after it.
                o = f(params, (tok + i + 1) % cfg.vocab_size)
            float(o[0, 0, 0])
        out.update(_parse_trace(trace_dir))
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"

    path = os.path.join("/tmp" if SMOKE else REPO, "PROFILE_1B.json")
    with open(path + ".tmp", "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps(out, indent=1))
    return 0 if "error" not in out else 1


if __name__ == "__main__":
    sys.exit(main())
