"""What the Phi-4-mini-flash serving cell runs on the pool's workers:
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
from benchmarks.model import phi4flash_reference as R
from benchmarks.model import phi4flash_weights as W

VARIANTS = ("state_bf16", "gmu_gated")


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


def _stats(gap, name: str) -> dict:
    return {name + "_max": float(gap.max()),
            name + "_p99": float(np.quantile(gap, 0.99)),
            name + "_p90": float(np.quantile(gap, 0.90)),
            name + "_p50": float(np.quantile(gap, 0.50)),
            name + "_mean": float(gap.mean())}


def check(seed: int, cfg: dict, pairs, pad_to: int, control: int) -> dict:
    """The reference over the sampled requests: the gap of every served
    token.  ``control`` 1 also reads the float8 control; 2 the two
    controls the builder runs once (``VARIANTS``), each the gap of the
    token that control puts first."""
    t0 = time.perf_counter()
    out = R.served_logit_gaps(seed, cfg, pairs, pad_to,
                              control=R.fp8 if control == 1 else None)
    res = {**_stats(out["gap"], "gap"), "tokens": int(out["gap"].size)}
    if control == 1:
        res.update(_stats(out["control_gap"], "control_gap"))
    if control == 2:
        for variant in VARIANTS:
            gap = R.served_logit_gaps(seed, cfg, pairs, pad_to,
                                      variant=variant)["control_gap"]
            res.update(_stats(gap, variant + "_gap"))
    res["reference_s"] = time.perf_counter() - t0
    return res
