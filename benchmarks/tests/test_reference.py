"""The plain reference against the program at a size a test run holds,
and the control: the reference in float8 put in the program's place has
to come out as not correct, by the cell's own limit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import harness as H
from benchmarks.drivers.train_worker import program_config
from benchmarks.model import reference as R
from benchmarks.model import weights as W

CFG = {**H.load_json("configs", "mistral7b-train.json")}
CFG.update(CFG["rehearse"])
LIMITS = H.load_json("traffic", "train_s4096_b1.json")["limits"]
SERVE_LIMITS = H.load_json("traffic", "chat_open.json")["limits"]
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def trained():
    """Three steps of the program's own step builder on seeded rows."""
    from nbdistributed_tpu.models import loss_fn
    from nbdistributed_tpu.parallel.mesh import make_mesh
    from nbdistributed_tpu.parallel.tensor_parallel import make_tp_train_step
    pc = program_config(CFG)
    params = jax.jit(functools.partial(W.make_weights, cfg=CFG))(
        W.seed_key(SEED))
    first = jax.tree.map(jnp.copy, params)
    batches = [W.tokens_for(SEED, i, (1, 128), CFG["vocab_size"])
               for i in range(3)]
    opt = optax.adamw(R.ADAMW["lr"])
    step = make_tp_train_step(lambda p, b: loss_fn(p, b, pc), opt,
                              make_mesh({"dp": 1}), None)
    state, got = opt.init(params), {"losses": []}
    for i, rows in enumerate(batches):
        params, state, loss = step(params, state, {"tokens": jnp.asarray(rows)})
        got["losses"].append(float(loss))
        if i == 0:
            got["grad_norms"] = {k: v / (1 - R.ADAMW["b1"]) for k, v in
                                 R.leaf_norms(state[0].mu).items()}
        if i == 1:
            got["change_norms"] = R.leaf_norms(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                params, first))
    return got, batches


def test_program_agrees_and_float8_control_is_not_correct(trained):
    got, batches = trained
    ref = R.train_reference(SEED, CFG, batches)
    sound = R.compare_train(got, ref)
    assert all(sound[k] <= LIMITS[k] for k in sound), sound
    control = R.compare_train(R.train_reference(SEED, CFG, batches, q=R.fp8),
                              ref)
    assert any(control[k] > LIMITS[k] for k in control), control
    assert control["loss_gap"] >= 3 * sound["loss_gap"]


def test_a_step_that_returns_its_state_unchanged_is_caught(trained):
    got, batches = trained
    ref = R.train_reference(SEED, CFG, batches)
    stuck = dict(got, change_norms={k: 0.0 for k in got["change_norms"]})
    assert R.compare_train(stuck, ref)["change_gap"] > LIMITS["change_gap"]


def test_served_tokens_agree_and_float8_control_is_not_correct():
    """Greedy tokens of the reference itself have gap 0; tokens the
    float8 control puts first lie below the reference's best by more
    than the serving cells' limit."""
    serve = {**H.load_json("configs", "mistral7b-serve.json")}
    serve.update(serve["rehearse"])
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, serve["vocab_size"], n).tolist()
               for n in (40, 90, 17)]
    pairs = [(p, rng.integers(0, serve["vocab_size"], 24).tolist())
             for p in prompts]
    out = R.served_logit_gaps(SEED, serve, pairs, 128, control=R.fp8)
    assert out["gap"].shape == (72,) and out["gap"].min() >= 0
    assert out["control_gap"].max() > SERVE_LIMITS["served_logit_gap_max"] \
        or out["control_gap"].max() > 3 * 0.05
    # random "served" tokens are far from greedy: the check sees it
    assert out["gap"].max() > 10 * SERVE_LIMITS["served_logit_gap_max"]
