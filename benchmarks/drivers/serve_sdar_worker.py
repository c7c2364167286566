"""What the SDAR serving cell runs on the pool's workers:
``serve_worker.py``'s helpers with this configuration's weights,
program config and reference in the places of Mistral's.  The published
``config.json`` keys are read in one place, the program's
``models/hf.py``; the three generation settings the published config
does not state (``block_length``, ``denoise_steps``, ``mask_token_id``)
ride the configuration file beside them.
"""

from __future__ import annotations

import functools
import time


from benchmarks.drivers import serve_worker
from benchmarks.drivers.serve_nemotronh_worker import (  # noqa: F401
    _stats, program_config)
from benchmarks.drivers.serve_worker import (  # noqa: F401
    device_facts, emit, memory)
from benchmarks.model import sdar_reference as R
from benchmarks.model import sdar_weights as W

EPS_TABLE = (1e-6, 1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 2e-3, 3e-3)


def make_params(seed: int, cfg: dict):
    import jax
    return jax.jit(functools.partial(W.make_weights, cfg=cfg))(
        W.seed_key(seed))


def break_server(how):
    """Tests only: break the timed path underneath.  ``passes``: every
    open position of a block is fixed by its first pass (host and
    device agree on it, as a change that left passes out to go faster
    would make them); the rest are ``serve_worker``'s."""
    if how == "passes":
        from nbdistributed_tpu.models.sdar import SDARConfig
        SDARConfig.fixed_per_pass = property(lambda self: self.block_length)
        return
    serve_worker.break_server(how)


def check(seed: int, cfg: dict, requests, pad_to: int, control: int,
          margin_eps: float) -> dict:
    """The reference over the sampled requests ((prompt, served tokens,
    the pass that fixed each)).  ``gap_max`` is taken over the
    positions whose routing margins all exceed ``margin_eps``
    (elsewhere bfloat16 rounding may choose another expert, and the
    token served from that set is no fault); the quantiles, the means
    and ``close_share`` (the share of positions left out of
    ``gap_max``) are over all positions read.  ``off_schedule``: blocks
    whose passes were not the schedule's.  ``control`` 1 also reads the
    float8 control and the tables the limits are set from."""
    t0 = time.perf_counter()
    kw = dict(block=cfg["block_length"], steps=cfg["denoise_steps"],
              mask_id=cfg["mask_token_id"])
    res = {"off_schedule": R.schedule_faults(requests, kw["block"],
                                             kw["steps"])}
    out = R.served_gaps(seed, cfg, requests, pad_to,
                        control=R.fp8 if control == 1 else None, **kw)
    clear = out["margin"] > margin_eps
    res.update({**_stats(out["token_gap"], clear, "gap"),
                **_stats(out["pick_gap"], clear, "pick"),
                "tokens": int(out["token_gap"].size),
                "skipped": int(out["skipped"]),
                "close_share": float(1.0 - clear.mean()),
                "margin_min": float(out["margin"].min())})
    if control == 1:
        res.update(_stats(out["control_token_gap"], clear, "control_gap"))
        res.update(_stats(out["control_pick_gap"], clear, "control_pick"))
        # the readings a limit is set from: for each epsilon the share
        # of positions left out and the largest gap among those kept
        res["by_eps"] = [
            [eps, float((out["margin"] <= eps).mean())]
            + [float(g[out["margin"] > eps].max())
               if (out["margin"] > eps).any() else None
               for g in (out["token_gap"], out["control_token_gap"])]
            for eps in EPS_TABLE]
    res["reference_s"] = time.perf_counter() - t0
    return res
