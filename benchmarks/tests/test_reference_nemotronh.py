"""Nemotron-3-Nano's plain reference against the program at the
``rehearse`` size, and the control: the program agrees with the
reference inside the cell's limits, and the reference in float8 put in
the program's place comes out as not correct, by one of them."""

import jax
import numpy as np
import pytest

from benchmarks import harness as H
from benchmarks.drivers.serve_nemotronh_worker import (check, make_params,
                                                       program_config)

CFG = {**H.load_json("configs", "nemotron3-nano-serve.json")}
CFG.update(CFG["rehearse"])
CFG = H.numbers_of(CFG)
LIMITS = H.load_json("traffic", "agent_closed.json")["limits"]
SEED = 2**31 + 34


@pytest.fixture(scope="module")
def served():
    """Five requests through ``DecodeServer`` at the rehearsal's
    geometry and the configuration's dtype."""
    from nbdistributed_tpu.models import DecodeServer
    srv = DecodeServer(make_params(SEED, CFG), program_config(CFG),
                       max_batch=4, max_len=256, pad_to=16,
                       kv_block_tokens=16, prefill_chunk=32,
                       interleave_prefill=True)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, CFG["vocab_size"], n).tolist()
               for n in (40, 90, 33, 64, 120)]
    rids = [srv.submit(p, 24) for p in prompts]
    outs = srv.run_until_done(2000)
    return [(p, outs[r]) for p, r in zip(prompts, rids)]


def test_program_agrees_and_float8_control_is_not_correct(served):
    got = check(SEED, CFG, served, 256, 1, LIMITS["margin_eps"])
    sound = {"served_logit_gap_max": got["gap_max"],
             "served_logit_gap_mean": got["gap_mean"],
             "close_share": got["close_share"]}
    assert all(sound[k] <= LIMITS[k] for k in sound), sound
    control = {"served_logit_gap_max": got["control_gap_max"],
               "served_logit_gap_mean": got["control_gap_mean"]}
    assert any(control[k] > LIMITS[k] for k in control), control
    assert got["control_gap_mean"] >= 3 * got["gap_mean"]
    assert got["tokens"] == 5 * 24


def test_random_tokens_are_far_from_greedy(served):
    rng = np.random.default_rng(9)
    pairs = [(p, rng.integers(0, CFG["vocab_size"], len(o)).tolist())
             for p, o in served[:2]]
    got = check(SEED, CFG, pairs, 256, 0, 0.0)
    assert got["gap_mean"] > 10 * LIMITS["served_logit_gap_mean"]
