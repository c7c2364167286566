"""What the Nemotron-3-Nano serving cell runs on the pool's workers:
``serve_worker.py``'s helpers with this configuration's weights,
program config and reference in the places of Mistral's.  The published
``config.json`` keys are read in one place, the program's
``models/hf.py``.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks.drivers.serve_worker import (  # noqa: F401
    break_server, device_facts, emit, memory)
from benchmarks.model import nemotronh_reference as R
from benchmarks.model import nemotronh_weights as W

EPS_TABLE = (1e-5, 1e-4, 3e-4, 1e-3, 2e-3, 3e-3, 4e-3, 5e-3, 1e-2)


def program_config(cfg: dict):
    """The program's own config object at the file's sizes."""
    import jax.numpy as jnp
    from nbdistributed_tpu.models.hf import config_from_hf_json
    return config_from_hf_json(cfg, dtype=jnp.dtype(cfg["torch_dtype"]),
                               use_flash=True)


def make_params(seed: int, cfg: dict):
    import jax
    return jax.jit(functools.partial(W.make_weights, cfg=cfg))(
        W.seed_key(seed))


def _stats(gap, clear, name: str) -> dict:
    kept = gap[clear] if clear.any() else gap
    return {name + "_max": float(kept.max()),
            name + "_max_all": float(gap.max()),
            name + "_p99": float(np.quantile(gap, 0.99)),
            name + "_p90": float(np.quantile(gap, 0.90)),
            name + "_p50": float(np.quantile(gap, 0.50)),
            name + "_mean": float(gap.mean())}


def check(seed: int, cfg: dict, pairs, pad_to: int, control: int,
          margin_eps: float) -> dict:
    """The reference over the sampled requests.  ``gap_max`` is taken
    over the positions whose routing margins all exceed ``margin_eps``
    (elsewhere bfloat16 rounding may choose another expert, and the
    token served from that set is no fault); the quantiles, the mean
    and ``close_share`` (the share of positions left out of
    ``gap_max``) are over all positions.  ``control`` 1 also reads the
    float8 control and the tables the limits are set from; 2 the
    control the builder runs once (the recurrent state kept in
    bfloat16)."""
    t0 = time.perf_counter()
    out = R.served_logit_gaps(seed, cfg, pairs, pad_to,
                              control=R.fp8 if control == 1 else None)
    clear = out["margin"] > margin_eps
    res = {**_stats(out["gap"], clear, "gap"),
           "tokens": int(out["gap"].size),
           "close_share": float(1.0 - clear.mean()),
           "margin_min": float(out["margin"].min())}
    if control == 1:
        res.update(_stats(out["control_gap"], clear, "control_gap"))
        # the readings a limit is set from: for each epsilon the share
        # of positions left out and the largest gap among those kept
        res["by_eps"] = [
            [eps, float((out["margin"] <= eps).mean())]
            + [float(g[out["margin"] > eps].max())
               if (out["margin"] > eps).any() else None
               for g in (out["gap"], out["control_gap"])]
            for eps in EPS_TABLE]
    if control == 2:
        gap = R.served_logit_gaps(seed, cfg, pairs, pad_to,
                                  variant="state_bf16")["control_gap"]
        res.update(_stats(gap, clear, "state_bf16_gap"))
    res["reference_s"] = time.perf_counter() - t0
    return res
