"""What a serving cell runs on the pool's workers: the model-spec cell's
helpers (weights from the seed, in the program's layout), the memory
reading, the profiler switch and, once the window has closed and the
program's state is freed, the plain reference over the sampled requests.
"""

from __future__ import annotations

import functools
import time

from benchmarks.drivers.train_worker import emit, program_config  # noqa: F401
from benchmarks.model import reference as R
from benchmarks.model import weights as W


def make_params(seed: int, cfg: dict):
    import jax
    return jax.jit(functools.partial(W.make_weights, cfg=cfg))(
        W.seed_key(seed))


def device_facts() -> dict:
    import jax
    d = jax.local_devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "local": jax.local_device_count(), "count": jax.device_count(),
            "id": d.id}


def memory() -> dict:
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return {"peak": int(stats.get("peak_bytes_in_use", 0)),
            "in_use": int(stats.get("bytes_in_use", 0))}


def check(seed: int, cfg: dict, pairs, pad_to: int, control: bool) -> dict:
    t0 = time.perf_counter()
    out = R.served_logit_gaps(seed, cfg, pairs, pad_to,
                              control=R.fp8 if control else None)
    res = {"gap_max": float(out["gap"].max()),
           "gap_mean": float(out["gap"].mean()),
           "tokens": int(out["gap"].size),
           "reference_s": time.perf_counter() - t0}
    if control:
        res["control_gap_max"] = float(out["control_gap"].max())
        res["control_gap_mean"] = float(out["control_gap"].mean())
    return res


def break_server(how):
    """Tests only: break the timed path where tokens are produced."""
    if not how:
        return
    from nbdistributed_tpu.models.serving import DecodeServer
    real = DecodeServer._emit

    def emit_broken(self, slot, rid, toks):
        if how == "token":      # every produced token is off by one
            toks = [(t + 1) % self._cfg.vocab_size for t in toks]
        elif how == "stall":
            time.sleep(0.05)
        return real(self, slot, rid, toks)

    DecodeServer._emit = emit_broken
