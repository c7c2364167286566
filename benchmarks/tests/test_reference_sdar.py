"""SDAR's plain reference against the program at the ``rehearse``
size, and the controls: the program agrees with the reference inside the
cell's limits; the reference in float8 put in the program's place comes
out as not correct, by one of them; a program that leaves passes out is
refused by ``passes_off_schedule``."""

import numpy as np
import pytest

from benchmarks import harness as H
from benchmarks.drivers.serve_sdar_worker import (check, make_params,
                                                  program_config)

CFG = {**H.load_json("configs", "sdar-30b-a3b-serve.json")}
CFG.update(CFG["rehearse"])
CFG = H.numbers_of(CFG)
LIMITS = H.load_json("traffic", "blocks_closed.json")["rehearse"]["limits"]
SEED = 2**31 + 39


def _serve(cfg=CFG):
    from nbdistributed_tpu.models import DecodeServer
    srv = DecodeServer(make_params(SEED, cfg), program_config(cfg),
                       max_batch=4, max_len=256, pad_to=16,
                       kv_block_tokens=16, prefill_chunk=32,
                       interleave_prefill=True)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 500, n).tolist()
               for n in (40, 90, 33, 64, 118)]
    rids = [srv.submit(p, 24) for p in prompts]
    outs = srv.run_until_done(2000)
    return [(p, outs[r], list(srv.fixed_at[r]))
            for p, r in zip(prompts, rids)]


@pytest.fixture(scope="module")
def served():
    """Five requests through ``DecodeServer`` at the rehearsal's
    geometry and the configuration's dtype."""
    return _serve()


def test_program_agrees_and_float8_control_is_not_correct(served):
    got = check(SEED, CFG, served, 256, 1, LIMITS["margin_eps"])
    sound = {"served_logit_gap_max": got["gap_max"],
             "served_logit_gap_mean": got["gap_mean"],
             "served_pick_gap_mean": got["pick_mean"],
             "close_share": got["close_share"],
             "passes_off_schedule": got["off_schedule"]}
    assert all(sound[k] <= LIMITS[k] for k in sound), sound
    assert got["control_gap_mean"] >= 3 * got["gap_mean"]
    assert got["control_pick_mean"] > got["pick_mean"]
    assert got["tokens"] + got["skipped"] == 5 * 24


def test_random_tokens_are_far_from_greedy(served):
    rng = np.random.default_rng(9)
    pairs = [(p, rng.integers(0, 500, len(o)).tolist(), w)
             for p, o, w in served[:2]]
    got = check(SEED, CFG, pairs, 256, 0, 0.0)
    # the best of 512 logits lies about three above their mean
    assert got["gap_mean"] > 4 * LIMITS["served_logit_gap_mean"]


def test_a_program_that_leaves_passes_out_is_off_schedule(served):
    """One pass a block where the configuration states four: what a
    change that skipped passes to go faster would serve."""
    fast = _serve({**CFG, "denoise_steps": 1})
    assert all(set(w) == {0} for _, _, w in fast)
    got = check(SEED, CFG, fast, 256, 0, 0.0)
    assert got["off_schedule"] >= sum(len(t) // 4 for _, t, _ in fast) - 5
    assert check(SEED, CFG, served, 256, 0, 0.0)["off_schedule"] == 0
